"""Serving benchmark: continuous batching vs one-session-at-a-time.

The multi-tenant claim behind `repro.serve.engine`: packing many
streaming sessions into one resident fixed-shape `plan.run` window step
amortizes launch + INTEG cost the way TaiBai amortizes its resident
program across spike streams. This suite replays one deterministic
ragged arrival trace — N concurrent sessions with staggered arrival
times, uneven stream lengths, and uneven chunk sizes — through both
engines and times the whole serve (admission -> cohort windows -> drain):

  * batched: capacity-C cohorts, the continuous-batching path
  * naive:   the same engine at capacity 1, one session a window

Timing is paired-adjacent (batched/naive alternating), median per-pair
ratio as the speedup — the same noise discipline as `bench_snn_engine`.
The tracked gate row is `serve_throughput/speedup_x` (relative, survives
runner swaps); sessions/sec, p99 window latency, occupancy, and cache
hit rate ride along for the perf trajectory. Both engines' outputs are
parity-checked (allclose: XLA reduction order differs across batch
shapes, so cross-engine equality is approximate — the *bit-exact*
isolation invariants live in tests/test_serve_engine.py).
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import numpy as np

from repro.core.snn_layers import make_dhsnn_shd
from repro.serve import EngineConfig, make_engine

N_SESSIONS = 96          # >= 64 concurrent (the acceptance scenario)
WINDOW = 32
CAPACITY = 64
N_IN, N_HIDDEN, N_OUT = 700, 64, 20


def _trace(n_sessions: int, seed: int = 0
           ) -> List[Tuple[int, str, np.ndarray]]:
    """One deterministic ragged arrival trace.

    Returns [(round, sid, chunk)]: session i arrives at round i % 8 and
    then submits one chunk per round until its stream (96..152 steps,
    varying by session) is exhausted. Chunk sizes cycle 17/23/31/40 so
    window boundaries never align with submit boundaries.
    """
    rng = np.random.default_rng(seed)
    sizes = (17, 23, 31, 40)
    ev: List[Tuple[int, str, np.ndarray]] = []
    for i in range(n_sessions):
        sid = f"s{i}"
        total = 96 + 8 * (i % 8)
        x = (rng.random((total, N_IN)) < 0.08).astype(np.float32)
        off, r = 0, i % 8
        while off < total:
            n = min(sizes[(i + r) % len(sizes)], total - off)
            ev.append((r, sid, x[off:off + n]))
            off += n
            r += 1
    ev.sort(key=lambda e: e[0])
    return ev


def _drive(capacity: int, nodes, params, trace, cache_bytes=None):
    """Replay the trace through one engine; returns (wall_s, engine)."""
    eng = make_engine(nodes, params,
                      EngineConfig(window=WINDOW, capacity=capacity,
                                   queue_limit=None,
                                   cache_bytes=cache_bytes))
    last_round: Dict[str, int] = {}
    for r, sid, _ in trace:
        last_round[sid] = max(last_round.get(sid, -1), r)
    t0 = time.perf_counter()
    cur = 0
    for r, sid, chunk in trace:
        while r > cur:                      # round boundary: run a window
            eng.step()
            cur += 1
        if sid not in eng.scheduler.sessions:
            eng.open(sid)
        eng.submit(sid, chunk)
        if last_round[sid] == r:
            eng.close(sid)
    eng.drain()
    return time.perf_counter() - t0, eng


def measure(repeats: int = 3) -> Dict:
    nodes, params = make_dhsnn_shd(jax.random.PRNGKey(0), n_in=N_IN,
                                   n_hidden=N_HIDDEN, n_out=N_OUT,
                                   dendritic=False)
    trace = _trace(N_SESSIONS)
    total_steps = sum(len(c) for _, _, c in trace)

    # warm both resident steps (compile outside the timed region)
    _, eb = _drive(CAPACITY, nodes, params, trace)
    _, en = _drive(1, nodes, params, trace)

    # cross-engine parity on a few sessions (allclose, see module doc)
    max_err = 0.0
    for sid in ("s0", "s31", "s95"):
        a, b = eb.outputs(sid), en.outputs(sid)
        assert a.shape == b.shape and a.shape[0] > 0
        max_err = max(max_err, float(np.max(np.abs(a - b))))
    assert max_err < 1e-4, f"engines diverged: max_abs_err={max_err}"

    tb, tn, ratios = [], [], []
    for _ in range(repeats):
        t1, eng_b = _drive(CAPACITY, nodes, params, trace)
        t2, _ = _drive(1, nodes, params, trace)
        tb.append(t1)
        tn.append(t2)
        ratios.append(t2 / t1)
    ratios.sort()
    t_batched, t_naive = min(tb), min(tn)
    snap = eng_b.stats()
    return {
        "n_sessions": N_SESSIONS,
        "window": WINDOW,
        "capacity": CAPACITY,
        "total_steps": total_steps,
        "batched_s": t_batched,
        "naive_s": t_naive,
        "speedup_x": ratios[len(ratios) // 2],
        "speedup_minmax_x": (ratios[0], ratios[-1]),
        "batched_sessions_per_s": N_SESSIONS / t_batched,
        "naive_sessions_per_s": N_SESSIONS / t_naive,
        "batched_steps_per_s": total_steps / t_batched,
        "p50_window_s": snap["window_latency_s"]["p50"],
        "p99_window_s": snap["window_latency_s"]["p99"],
        "occupancy_mean": snap["occupancy"]["mean"],
        "cache_hit_rate": snap["cache_hit_rate"],
        "max_abs_err": max_err,
    }


def measure_cache_pressure() -> Dict:
    """The same trace under a budget that keeps only half the fleet hot:
    spill/restore cost shows up as batched_s inflation, hit rate < 1."""
    nodes, params = make_dhsnn_shd(jax.random.PRNGKey(0), n_in=N_IN,
                                   n_hidden=N_HIDDEN, n_out=N_OUT,
                                   dendritic=False)
    from repro.analysis import session_footprint
    fp = session_footprint(nodes, params)
    trace = _trace(N_SESSIONS)
    budget = (N_SESSIONS // 2) * fp
    t, eng = _drive(CAPACITY, nodes, params, trace, cache_bytes=budget)
    snap = eng.stats()
    return {
        "cache_bytes": budget,
        "session_footprint": fp,
        "batched_s": t,
        "cache_hit_rate": snap["cache_hit_rate"],
        "cache_evictions": snap["cache_evictions"],
        "cache_restores": snap["cache_restores"],
    }


def run() -> Dict:
    print("=== serving: continuous batching vs naive one-at-a-time ===")
    m = measure()
    print(f"{m['n_sessions']} sessions x ~{m['total_steps'] // m['n_sessions']}"
          f" steps (W={m['window']}, C={m['capacity']})\n"
          f"batched {m['batched_s']:6.2f} s  naive {m['naive_s']:6.2f} s  "
          f"({m['speedup_x']:4.2f}x, "
          f"{m['batched_sessions_per_s']:6.1f} sessions/s, "
          f"p99 window {1e3 * m['p99_window_s']:.1f} ms, "
          f"occ {m['occupancy_mean']:.2f})")
    assert m["speedup_x"] > 1.0, (
        "continuous batching must beat the naive baseline at "
        f"{m['n_sessions']} concurrent sessions (got {m['speedup_x']:.2f}x)")
    p = measure_cache_pressure()
    print(f"cache pressure: budget {p['cache_bytes']} B "
          f"({p['cache_bytes'] // p['session_footprint']} hot sessions) -> "
          f"{p['batched_s']:6.2f} s, hit rate {p['cache_hit_rate']:.3f}, "
          f"{p['cache_evictions']} evictions")
    return {"serve_throughput": m, "cache_pressure": p}


if __name__ == "__main__":
    run()
