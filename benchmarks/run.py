"""Run benchmark suites: `PYTHONPATH=src python -m benchmarks.run [--only a,b]`.

Emits machine-readable JSON so CI can archive a perf trajectory:

  experiments/BENCH_<suite>.json   one file per suite, schema below
  experiments/bench_results.json   the aggregate (back-compat)

`--repeats K` replays the selection into `r0/..r{K-1}/` subdirectories;
`benchmarks/compare.py` takes the per-row best across repeats before
gating, so one noisy repeat cannot fake a regression.

Per-suite schema (v1):
  {"schema": 1, "suite": str, "created_unix": float, "host": {...},
   "seconds": float, "ok": bool, "result": {...} | "error": str}

`--only kernels,topology_storage` is the CI benchmarks-smoke selection.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import time
import traceback

from benchmarks import (bench_applications, bench_energy, bench_kernels,
                        bench_mapping_tradeoff, bench_plasticity,
                        bench_roofline, bench_serving, bench_snn_engine,
                        bench_snn_models, bench_spiking_lm,
                        bench_topology_exec, bench_topology_storage)
from repro import paths

SUITES = [
    ("topology_storage", bench_topology_storage),
    ("topology_exec", bench_topology_exec),
    ("snn_models", bench_snn_models),
    ("snn_engine", bench_snn_engine),
    ("serving", bench_serving),
    ("plasticity", bench_plasticity),
    ("mapping_tradeoff", bench_mapping_tradeoff),
    ("kernels", bench_kernels),
    ("energy", bench_energy),
    ("applications", bench_applications),
    ("spiking_lm", bench_spiking_lm),
    ("roofline", bench_roofline),
]

SCHEMA_VERSION = 1


def _git_sha() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True, timeout=5,
                              ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _host_meta() -> dict:
    import jax

    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "python": platform.python_version(),
        "platform": platform.platform(),
        "git_sha": _git_sha(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run benchmark suites and emit BENCH_*.json artifacts.")
    ap.add_argument("--only", default="",
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--out-dir", default="experiments",
                    help="directory for BENCH_*.json + aggregate")
    ap.add_argument("--list", action="store_true",
                    help="list suite names and exit")
    ap.add_argument("--repeats", type=int, default=1, metavar="K",
                    help="run the selection K times into r0/..r{K-1}/ "
                         "subdirs (the perf gate's min-of-k noise guard)")
    args = ap.parse_args(argv)

    if args.list:
        for name, _ in SUITES:
            print(name)
        return 0

    selected = SUITES
    if args.only:
        wanted = [s.strip() for s in args.only.split(",") if s.strip()]
        known = {name for name, _ in SUITES}
        unknown = sorted(set(wanted) - known)
        if unknown:
            ap.error(f"unknown suites {unknown}; known: {sorted(known)}")
        selected = [(n, m) for n, m in SUITES if n in wanted]

    if args.repeats < 1:
        ap.error("--repeats must be >= 1")
    if args.repeats > 1:
        rc = 0
        for i in range(args.repeats):
            sub = os.path.join(args.out_dir, f"r{i}")
            print(f"\n########## repeat {i} -> {sub} ##########")
            rc |= _run_suites(selected, sub)
        return rc
    return _run_suites(selected, args.out_dir)


def _run_suites(selected, out_dir: str) -> int:
    paths.enable_compile_cache()
    host = _host_meta()
    os.makedirs(out_dir, exist_ok=True)
    aggregate = {"schema": SCHEMA_VERSION, "created_unix": time.time(),
                 "host": host, "suites": {}}
    failures = 0
    for name, mod in selected:
        print(f"\n{'='*72}\n[{name}]")
        entry = {"schema": SCHEMA_VERSION, "suite": name,
                 "created_unix": time.time(), "host": host}
        t0 = time.time()
        try:
            entry["result"] = mod.run()
            entry["ok"] = True
        except Exception as e:
            failures += 1
            entry["error"] = repr(e)
            entry["ok"] = False
            traceback.print_exc()
        entry["seconds"] = round(time.time() - t0, 1)
        path = os.path.join(out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(entry, f, indent=1, default=str)
        print(f"[{name}] {'ok' if entry['ok'] else 'FAILED'} "
              f"in {entry['seconds']}s -> {path}")
        aggregate["suites"][name] = entry
    agg_path = os.path.join(out_dir, "bench_results.json")
    with open(agg_path, "w") as f:
        json.dump(aggregate, f, indent=1, default=str)
    print(f"\n{'='*72}\nwrote {agg_path}; "
          f"{len(selected) - failures}/{len(selected)} suites ok")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
