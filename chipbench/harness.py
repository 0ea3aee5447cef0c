"""What every cell of the benchmark shares: finding a cell's files by name,
the device check, host spans, and the result line.

A cell is one entry of `workloads` in BENCHMARK.json. Its configuration
file (`configs/<config>.json`) names the model family; the family's
program adapter (`configs/<family>_model.py`) and plain reference
(`configs/<family>_reference.py`) sit beside it. Its traffic mix
(`traffic/<traffic>.json`) names the entry that drives the program
(`entries/<entry>.py`). Each per-layer metric has a reader of its own
(`metrics/<metric>.py`). Nothing here knows a cell by name.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

from tracing import SPAN_PREFIX

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "chipbench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_spec() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One workload with its configuration, traffic mix and code, found by
    the names BENCHMARK.json gives."""

    def __init__(self, workload: str, spec: Optional[Dict[str, Any]] = None):
        self.spec = spec if spec is not None else bench_spec()
        by_name = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in by_name:
            raise KeyError(f"unknown workload {workload!r}; "
                           f"known: {sorted(by_name)}")
        self.workload = by_name[workload]
        self.name = workload
        cfg_entry = next(c for c in self.spec["configs"]
                         if c["name"] == self.workload["config"])
        self.cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", self.workload["traffic"] + ".json"))
        self.chips = int(self.workload["chips"])

    def module(self, kind: str):
        """kind: 'model' | 'reference' (the family's files) | 'entry'."""
        if kind == "entry":
            path = os.path.join(BENCH_DIR, "entries",
                                self.traffic["entry"] + ".py")
        else:
            path = os.path.join(BENCH_DIR, "configs",
                                f"{self.cfg['family']}_{kind}.py")
        return load_module(path)

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict[str, Any]]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def seed_words(seed: int, stream: int) -> List[int]:
    """Two 32-bit words for one random stream of a run: weights, inputs
    and the arrival schedule each draw from their own stream, and a seed
    of more than 32 bits keeps all of its bits."""
    import numpy as np
    ss = np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 stream])
    return [int(w) for w in ss.generate_state(2)]


def np_rng(seed: int, stream: int):
    import numpy as np
    return np.random.default_rng(seed_words(seed, stream))


def jax_key(seed: int, stream: int):
    import jax
    a, b = seed_words(seed, stream)
    return jax.random.fold_in(jax.random.PRNGKey(a), b)


# --- the device ---------------------------------------------------------------


def peaks_for(kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def require_device(chips: int):
    """The cell's chips, or NoDevice. There is no fallback to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def describe(devs) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# --- host spans -------------------------------------------------------------------


class Spans:
    """Host spans around the calls into each layer. With tracing on they
    are profiler annotations, so they share the device trace's clock;
    with tracing off they cost nothing. A span starts when it is made."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        if self.on:
            return self._ann(SPAN_PREFIX + name)
        return contextlib.nullcontext()


# --- the result line -----------------------------------------------------------


def metric(value: float, unit: str) -> Dict[str, Any]:
    return {"value": float(value), "unit": unit}


def emit(result: Dict[str, Any]) -> None:
    """Each compared number beside its limit, as the last lines of stderr
    and as the last key of the result line; the result line last on
    stdout."""
    compared = result.pop("compared")
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAIL'})", file=sys.stderr,
              flush=True)
    result["compared"] = compared
    print(json.dumps(result), flush=True)


def check(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, Any]]:
    """Each number held to its limit (at most the limit passes)."""
    out = {}
    for name, limit in limits.items():
        v = values.get(name)
        ok = v is not None and v == v and v <= limit
        out[name] = {"value": v, "limit": limit, "ok": bool(ok)}
    return out
