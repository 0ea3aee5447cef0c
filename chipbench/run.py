#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks
for. It exits non-zero, printing no result, when JAX finds no TPU or too
few chips. The last line of stdout is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, and last `compared`: each number that decides `correct`
beside its limit (also the last lines of stderr).

`--control 1` also reads the control, the plain reference at the next
lower precision, on the same sample, and prints its numbers under
`control`. The benchmark's own runs do not use it.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import harness  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


class Run:
    """What an entry needs from the harness during one run."""

    def __init__(self, cell, args, devs, t0: float):
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.control = bool(args.control)
        self.devs = devs
        self.t0 = t0
        self.spans = harness.Spans(self.trace)
        self.setup_s = None
        self.trace_dir = None

    def mark_setup(self, ahead) -> None:
        """Set-up ends now, or `ahead` seconds from now where the entry's
        own schedule opens the window."""
        self.setup_s = time.perf_counter() + (ahead or 0.0) - self.t0

    def start_trace(self) -> None:
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1        # the benchmark's own spans only
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def memory_peak(self):
        return harness.memory_peak(self.devs) if self.devs else None


def per_layer(cell, res, reduced, peaks, seconds):
    view = SimpleNamespace(trace=reduced, records=res["records"],
                           peaks=peaks, cfg=cell.cfg, traffic=cell.traffic,
                           seconds=seconds)
    out = {}
    for m in cell.per_layer():
        mod = harness.load_module(os.path.join(harness.BENCH_DIR, "metrics",
                                               m["name"] + ".py"))
        v = mod.read(view)
        if v is not None:
            out[m["name"]] = harness.metric(v, m["unit"])
    return out


def main(argv=None, require_chip: bool = True, cell=None) -> int:
    """`require_chip=False` and a `cell` whose sizes were changed are for
    the benchmark's own tests on the CPU."""
    args = parse(argv)
    cell = cell if cell is not None else harness.Cell(args.workload)
    os.environ["REPRO_STRICT"] = "1"
    import jax

    if require_chip:
        try:
            devs = harness.require_device(cell.chips)
        except harness.NoDevice as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 3
        peaks = harness.peaks_for(devs[0].device_kind)
    else:
        devs = jax.devices()[:cell.chips]
        peaks = harness.peaks_for("TPU v5 lite")
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import paths
    from repro.kernels import incidents
    paths.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    run = Run(cell, args, devs if require_chip else None, T0)
    entry = cell.module("entry")
    try:
        res = entry.run(run)
        result = {"correct": False, "attempted": res["attempted"],
                  "failed": res["failed"]}
        limits = dict(cell.cfg["correct"]["limits"])
        values = dict(res["compare"] or {})
        values["incidents"] = len(incidents())
        values["sample_missing"] = res["missing_in_sample"]
        limits.update(incidents=0, sample_missing=0)
        compared = harness.check(values, limits)
        result["correct"] = all(c["ok"] for c in compared.values())
        device = harness.describe(devs)
        device["memory_peak_bytes"] = res["memory_peak_bytes"]
        if run.trace:
            from tracing import Reduced, load
            reduced = Reduced(load(run.trace_dir))
            result["metrics"] = per_layer(cell, res, reduced, peaks,
                                          args.seconds)
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s
            result["device"] = device
            result["breakdown"] = reduced.breakdown()
        else:
            e2e = dict(res["e2e"], setup_s=run.setup_s)
            result["metrics"] = {m["name"]: harness.metric(e2e[m["name"]],
                                                           m["unit"])
                                 for m in cell.end_to_end()}
            result["device"] = device
        result["info"] = dict(res["info"], compare=res["compare"])
        if res.get("control") is not None:
            result["control"] = res["control"]
        result["compared"] = compared
    finally:
        if run.trace_dir:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
