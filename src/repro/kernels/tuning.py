"""Autotuner + persistent tuning cache for registered kernels.

Block shapes that are optimal for one (shape, backend) pair are rarely
optimal for another — VMEM working set, grid shape, and the serial-in-time
chunk trade all move. Instead of hand-picking per call site, the autotuner
sweeps each kernel's declared candidate block configs on representative
inputs, times the jitted Pallas path, and persists the winner to a JSON
cache keyed by

    (kernel name, jax backend, shape bucket)

where the shape bucket rounds every logical dimension up to a power of two
("B8_D512_T256") so one tuning run covers a neighborhood of shapes.
`registry.KernelSpec.resolve_blocks` consults the cache on every dispatch;
a cache miss silently falls back to the spec's hand-tuned defaults, so
tuning is always an optimization, never a correctness dependency.

Before anything is timed, candidates whose estimated VMEM working set
(`KernelSpec.vmem_bytes`) exceeds the budget (`REPRO_VMEM_LIMIT_MB`,
default 14 MiB — one TPU core's ~16 MiB minus headroom) are pruned: an
infeasible tile would either crash Mosaic or thrash, and either way timing
it wastes sweep budget. The spec-default config is never pruned — it is
what dispatch falls back to anyway, so it must stay the measured baseline.

Cache location: `$REPRO_TUNING_CACHE`, else `experiments/kernel_tuning.json`
in the checkout (gitignored; `repro.paths.TUNING_CACHE_PATH`).
Misses fall through to the checked-in cache (`kernels/tuned/ci_cache.json`),
which pins the winners for the CI / nightly-benchmark shapes so fresh
checkouts dispatch with tuned blocks from the first call.
`benchmarks/bench_kernels.py` exercises the sweep and archives the winners.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Mapping, Optional, Tuple

import jax

from repro import paths
from repro.core import faults
from repro.kernels import registry
# import names from the submodule directly: the `repro.kernels` package
# re-exports an `incidents()` *function* shadowing the module attribute
from repro.kernels.incidents import (FallbackEvent, degrade, record,
                                     strict_mode)

_ENV_CACHE = "REPRO_TUNING_CACHE"
_ENV_VMEM_LIMIT = "REPRO_VMEM_LIMIT_MB"
_SCHEMA_VERSION = 1
_VMEM_LIMIT_MB_DEFAULT = 14.0

BUNDLED_CACHE_PATH = os.path.join(os.path.dirname(__file__), "tuned",
                                  "ci_cache.json")


def default_cache_path() -> str:
    return os.environ.get(_ENV_CACHE, paths.TUNING_CACHE_PATH)


def shape_bucket(dims: Mapping[str, int]) -> str:
    """Canonical bucket key: dims sorted by name, sizes rounded up to pow2."""
    parts = []
    for k in sorted(dims):
        n = max(1, int(dims[k]))
        parts.append(f"{k}{1 << (n - 1).bit_length()}")
    return "_".join(parts)


class TuningCache:
    """JSON-backed map: kernel|backend|bucket -> winning block config."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._data: Optional[Dict[str, Any]] = None

    def _load(self) -> Dict[str, Any]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    raw = json.load(f)
                if raw.get("version") != _SCHEMA_VERSION:
                    raw = {"version": _SCHEMA_VERSION, "entries": {}}
            except (OSError, ValueError):
                raw = {"version": _SCHEMA_VERSION, "entries": {}}
            self._data = raw
        return self._data

    @staticmethod
    def _key(kernel: str, backend: str, bucket: str) -> str:
        return f"{kernel}|{backend}|{bucket}"

    def lookup(self, kernel: str, backend: str,
               bucket: str) -> Optional[Dict[str, int]]:
        entry = self._load()["entries"].get(self._key(kernel, backend, bucket))
        if entry is None:
            return None
        return {k: int(v) for k, v in entry["blocks"].items()}

    def put(self, kernel: str, backend: str, bucket: str,
            blocks: Mapping[str, int],
            stats: Optional[Mapping[str, Any]] = None) -> None:
        data = self._load()
        data["entries"][self._key(kernel, backend, bucket)] = {
            "blocks": dict(blocks), "stats": dict(stats or {})}

    def save(self) -> str:
        data = self._load()
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)
        return self.path

    def entries(self):
        """Iterate (kernel, backend, bucket, blocks) over every cached
        winner — the static analyzer lints stored block keys against the
        owning spec's axes (`repro.analysis.check_kernel`, TB308)."""
        for key, entry in self._load()["entries"].items():
            kernel, backend, bucket = key.split("|", 2)
            yield kernel, backend, bucket, {
                k: int(v) for k, v in entry["blocks"].items()}

    def __len__(self) -> int:
        return len(self._load()["entries"])


_DEFAULT_CACHE: Optional[TuningCache] = None
_BUNDLED_CACHE: Optional[TuningCache] = None


def default_cache() -> TuningCache:
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None or _DEFAULT_CACHE.path != default_cache_path():
        _DEFAULT_CACHE = TuningCache()
    return _DEFAULT_CACHE


def bundled_cache() -> TuningCache:
    """The read-only cache checked into the package (CI / bench shapes)."""
    global _BUNDLED_CACHE
    if _BUNDLED_CACHE is None:
        _BUNDLED_CACHE = TuningCache(BUNDLED_CACHE_PATH)
    return _BUNDLED_CACHE


def lookup_tuned(kernel: str,
                 dims: Mapping[str, int]) -> Optional[Dict[str, int]]:
    """Dispatch-time hook used by `KernelSpec.resolve_blocks`.

    User/process cache first; a miss falls through to the checked-in CI
    cache so known shapes start tuned on a fresh checkout.
    """
    try:
        backend = jax.default_backend()
        bucket = shape_bucket(dims)
        hit = default_cache().lookup(kernel, backend, bucket)
        if hit is not None:
            return hit
        return bundled_cache().lookup(kernel, backend, bucket)
    except Exception:  # a corrupt cache must never break dispatch
        return None


def vmem_limit_bytes() -> int:
    """VMEM budget in bytes (MiB via REPRO_VMEM_LIMIT_MB) used by autotune
    pruning and the dispatch-time VMEM rejection guard. Simulated pressure
    (a `vmem_limit` fault, see repro.core.faults) only ever *shrinks* it."""
    try:
        mb = float(os.environ.get(_ENV_VMEM_LIMIT, _VMEM_LIMIT_MB_DEFAULT))
    except ValueError:
        mb = _VMEM_LIMIT_MB_DEFAULT
    limit = int(mb * 2 ** 20)
    injected = faults.vmem_limit_override_bytes()
    return limit if injected is None else min(limit, injected)


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------


def _time_once(fn, args) -> float:
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def autotune(name: str, args: Optional[tuple] = None, *,
             cache: Optional[TuningCache] = None, repeats: int = 3,
             save: bool = True, **static) -> Tuple[Dict[str, int], Dict]:
    """Sweep `spec.candidates` (plus the spec defaults) for kernel `name`.

    Returns (winning blocks, report). The winner is persisted to `cache`
    (default: the process-wide cache) under the input's shape bucket, so
    subsequent `registry.dispatch` calls on same-bucket shapes pick it up.
    """
    spec = registry.get(name)
    if args is None:
        if spec.make_inputs is None:
            raise ValueError(f"kernel {name!r} has no make_inputs; "
                             "pass explicit args to autotune()")
        args = spec.make_inputs(jax.random.PRNGKey(0))
    static = {**spec.tune_static, **static}   # required statics (e.g. amps)
    if cache is None:  # NOT `or`: an empty TuningCache is falsy (__len__)
        cache = default_cache()
    dims = spec.dims_of(*args)
    bucket = shape_bucket(dims)
    backend = jax.default_backend()
    interpret = registry.interpret_mode()

    # Fit every candidate to the actual dims, dedupe, and always include the
    # spec's hand-tuned defaults as the baseline candidate. Candidates whose
    # modeled VMEM working set busts the budget are pruned before timing —
    # except the defaults, which dispatch uses on a cache miss regardless.
    limit = vmem_limit_bytes()
    seen, fitted, pruned = set(), [], []
    for i, cand in enumerate(({},) + tuple(spec.candidates)):
        blocks = spec.resolve_blocks(dims, overrides=cand, use_cache=False)
        key = tuple(sorted(blocks.items()))
        if key in seen:
            continue
        seen.add(key)
        est = spec.vmem_bytes(dims, blocks) if spec.vmem_bytes else None
        if i > 0 and est is not None and est > limit:
            pruned.append({"blocks": blocks, "vmem_bytes": int(est)})
            continue
        fitted.append(blocks)

    report: Dict[str, Any] = {"kernel": name, "backend": backend,
                              "bucket": bucket, "timings": [],
                              "pruned": pruned,
                              "vmem_limit_bytes": limit}
    best_blocks, best_t = None, float("inf")
    for blocks in fitted:
        def fn(*a, _b=blocks):
            faults.maybe_fail_compile(name, autotune=True)
            return spec.pallas(*a, blocks=_b, interpret=interpret, **static)

        fn = jax.jit(fn)
        try:
            compile_s = _time_once(fn, args)           # includes compilation
            runs = [_time_once(fn, args) for _ in range(repeats)]
        except Exception as e:
            # an infeasible tile is a loser, not a crash: record it and
            # keep sweeping the remaining candidates
            report["timings"].append({"blocks": blocks, "error": repr(e),
                                      "infeasible": True})
            record(FallbackEvent(
                kind="autotune", family=name, stage="candidate",
                error=repr(e), dims=dict(dims), blocks=dict(blocks)))
            continue
        t = min(runs)
        report["timings"].append({"blocks": blocks, "best_s": t,
                                  "runs_s": runs, "compile_s": compile_s})
        if t < best_t:
            best_blocks, best_t = blocks, t
    if best_blocks is None:
        # every candidate was infeasible: degrade to the spec defaults
        # (what dispatch uses on a cache miss anyway) rather than abort
        # the sweep; REPRO_STRICT=1 still makes this fatal.
        defaults = spec.resolve_blocks(dims, use_cache=False)
        degrade("autotune", name, "sweep",
                f"every candidate failed; falling back to spec "
                f"defaults {defaults}", dims=dims, blocks=defaults)
        report["winner"] = {"blocks": defaults, "best_s": None,
                           "degraded": True}
        return defaults, report
    report["winner"] = {"blocks": best_blocks, "best_s": best_t}
    cache.put(name, backend, bucket, best_blocks,
              stats={"best_s": best_t, "n_candidates": len(fitted)})
    if save:
        cache.save()
    return best_blocks, report


def autotune_all(*, cache: Optional[TuningCache] = None, repeats: int = 3,
                 save: bool = True) -> Dict[str, Dict]:
    """Tune every registered kernel on its canonical inputs.

    One kernel blowing up must not abort the whole sweep: its error is
    recorded (report entry + incident) and the sweep continues — except
    under REPRO_STRICT=1, where the failure propagates.
    """
    registry.ensure_registered()
    reports = {}
    for name in registry.names():
        if registry.get(name).make_inputs is None:
            continue
        try:
            _, reports[name] = autotune(name, cache=cache, repeats=repeats,
                                        save=save)
        except Exception as e:
            if strict_mode():
                raise
            record(FallbackEvent(
                kind="autotune", family=name, stage="kernel", error=repr(e)))
            reports[name] = {"kernel": name, "error": repr(e)}
    return reports


__all__ = ["TuningCache", "autotune", "autotune_all", "bundled_cache",
           "BUNDLED_CACHE_PATH", "default_cache", "default_cache_path",
           "lookup_tuned", "shape_bucket", "vmem_limit_bytes"]
