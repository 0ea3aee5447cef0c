#!/usr/bin/env python3
"""The knee sweep of a `sessions` cell: offered arrival rates, one after
another, on one engine in one process.

    python3 chipbench/sweep.py --workload shd_dhsnn.utterances \
        --rates 500,1000,2000 --seconds 6 --seed 1

For each rate it prints one JSON line: the rate, `window_p99_ms`,
`session_steps_per_s`, how late the generator ran (`gen_lag_p99_ms`), the
scheduler's queueing (`queue_wait_p99_ms`), the windows due but never
delivered, and the backlog: the mean number of ready windows at the
engine steps of the window's last fifth minus its first fifth. The knee
is the highest rate at which the backlog does not grow and
`window_p99_ms` stays under one window of signal. The cell's traffic
file then takes 0.8 x knee as its `rate_per_s`.
"""

import argparse
import json
import os
import sys

import numpy as np

import generator
import harness


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated arrival rates, sessions/s")
    p.add_argument("--seconds", type=float, default=6.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    cell = harness.Cell(args.workload)
    os.environ["REPRO_STRICT"] = "1"
    try:
        devs = harness.require_device(cell.chips)
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import paths
    paths.enable_compile_cache()
    entry = cell.module("entry")
    cfg, mix = cell.cfg, cell.traffic
    E = entry.Engine(cell, args.seed)
    pool = generator.session_pool(
        mix, cfg["n_in"], harness.np_rng(args.seed, entry.TRAFFIC))
    E.warm(cfg["n_in"])
    spans = harness.Spans(False)
    t_open = float(mix["warmup_s"])
    t_close = t_open + args.seconds
    for rate in [float(r) for r in args.rates.split(",")]:
        sched = generator.session_schedule(
            mix, t_close, harness.np_rng(args.seed, entry.SCHEDULE),
            rate_per_s=rate)
        rec = entry.drive(E, pool, sched, mix, t_open, t_close, spans,
                          drain_s=10.0)
        s = entry.summarize(rec, args.seconds)
        depth = s["ready_depth"]
        fifth = max(1, len(depth) // 5)
        print(json.dumps({
            "rate_per_s": rate,
            "window_p99_ms": s["latency_ms"]["p99"],
            "latency_ms": s["latency_ms"],
            "session_steps_per_s": s["session_steps_per_s"],
            "offered_steps_per_s": float(np.mean(sched["length"])) * rate,
            "gen_lag_p99_ms": 1e3 * float(np.percentile(s["gen_lag_s"], 99)),
            "queue_wait_p99_ms": 1e3 * float(np.percentile(
                s["queue_wait_s"], 99)) if len(s["queue_wait_s"]) else None,
            "engine_step_ms": 1e3 * float(np.mean(s["step_s"])),
            "steps": int(len(s["step_s"])),
            "submit_share": rec["submit_s"] / args.seconds,
            "step_share": rec["step_total_s"] / args.seconds,
            "windows_due": s["windows_due"],
            "windows_undelivered": s["windows_undelivered"],
            "backlog_growth": float(np.mean(depth[-fifth:])
                                    - np.mean(depth[:fifth])),
            "device": harness.describe(devs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
