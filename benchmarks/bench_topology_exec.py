"""Compressed-topology *execution*: throughput and peak memory vs dense.

`bench_topology_storage` reproduces the paper's Fig. 14 storage claims;
this suite measures what the tables buy at run time now that the stack
executes them directly through the `spikemm_gather` channel:

  exec_vs_dense   paired throughput, gather channel on IE tables vs the
                  dense spikemm on `dense_equivalent()` — same banded
                  connectivity, moderate scale where dense is feasible
  scale_1e5/1e6   brain-scale banded nets (10^5 / 10^6 neurons) run
                  compressed-only; the dense path is *modeled* (its
                  weight tensor alone is 40 GB / 4 TB) and reported as a
                  bytes ratio — the row CI gates is deterministic
  stream_memory   subprocess peak-RSS rows: `plan.run_stream` on an 8x
                  longer stream must hold RSS constant while the one-shot
                  full-time path pays linearly (same property
                  `tests/test_topology_exec.py` asserts). The children run
                  on the CPU backend: host RSS is what they measure, and a
                  chip belongs to one process, which is this one.

All gated rows are relative (paired speedups, byte ratios, RSS ratios) so
they survive runner hardware swaps, matching the tracked.json contract.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topology as topo
from repro.kernels.spikemm.gather import spikemm_gather
from repro.kernels.spikemm.ops import spikemm


def _banded(n: int, band: int, seed: int = 0):
    """Local/banded connectivity: each neuron reaches ±band neighbours —
    the locality regime where block-structured IE lowering is dense per
    occupied block (cortical-sheet-like wiring)."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n), 2 * band + 1)
    cols = rows + np.tile(np.arange(-band, band + 1), n)
    keep = (cols >= 0) & (cols < n)
    w = 0.1 * rng.standard_normal(keep.sum()).astype(np.float32)
    return topo.encode((rows[keep], cols[keep], w), kind="sparse_coo",
                       n_pre=n, n_post=n)


def _tables_bytes(t) -> int:
    return int(t.wblk.nbytes + t.jj.nbytes + t.kk.nbytes + t.act.nbytes)


def _paired(fa, fb, repeats: int = 9):
    """Adjacent-pair timing (same rationale as bench_snn_engine)."""
    fa().block_until_ready()
    fb().block_until_ready()
    ratios, ta, tb = [], [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fa().block_until_ready()
        t1 = time.perf_counter()
        fb().block_until_ready()
        t2 = time.perf_counter()
        ta.append(t1 - t0)
        tb.append(t2 - t1)
        ratios.append((t2 - t1) / (t1 - t0))
    ratios.sort()
    return min(ta), min(tb), ratios[len(ratios) // 2]


def measure_exec_vs_dense(n: int = 8192, band: int = 64,
                          m: int = 64) -> Dict:
    enc = _banded(n, band)
    tables = enc.lowering()
    w_dense = jnp.asarray(enc.dense_equivalent())
    x = jnp.asarray((np.random.default_rng(1).random((m, n)) < 0.2),
                    jnp.float32)
    f_gather = jax.jit(lambda: spikemm_gather(x, tables))
    f_dense = jax.jit(lambda: spikemm(x, w_dense))
    err = float(jnp.max(jnp.abs(f_gather() - f_dense())))
    t_g, t_d, speedup = _paired(f_gather, f_dense)
    return {
        "n": n, "band": band, "edges": int(enc.meta["n_connections"]),
        "gather_ms": 1e3 * t_g, "dense_ms": 1e3 * t_d,
        "speedup_x": speedup,                 # dense time / gather time
        "max_abs_err": err,
        "dense_bytes": int(w_dense.size * 4),
        "compressed_bytes": _tables_bytes(tables),
    }


def measure_scale(n: int, band: int, bk: int, steps: int = 8) -> Dict:
    """Compressed-only execution at a scale where dense is infeasible."""
    t0 = time.perf_counter()
    enc = _banded(n, band)
    tables = enc.lowering(bk=bk, bn=bk)
    build_s = time.perf_counter() - t0
    x = jnp.asarray((np.random.default_rng(2).random((8, n)) < 0.1),
                    jnp.float32)
    f = jax.jit(lambda s: spikemm_gather(s, tables))
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        f(x).block_until_ready()
    dt = (time.perf_counter() - t0) / steps
    comp = _tables_bytes(tables)
    dense_model = n * n * 4
    return {
        "n": n, "band": band, "bk": bk,
        "edges": int(enc.meta["n_connections"]),
        "build_s": build_s, "step_ms": 1e3 * dt,
        "steps_per_s": 1.0 / dt,
        "compressed_bytes": comp,
        "modeled_dense_bytes": dense_model,
        "mem_ratio_dense_over_compressed": dense_model / comp,
        "storage_table_bytes": enc.storage_bits() // 8,
    }


_MEM_SCRIPT = textwrap.dedent("""
    import sys
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import events, plan
    from repro.core import topology as topo
    from repro.core.events import Connection
    from repro.core.neuron import LI, LIF
    from repro.core.snn_layers import ff_integrate

    mode, T = sys.argv[1], int(sys.argv[2])
    n, band, chunk = 8192, 64, 64
    rows = np.repeat(np.arange(n), 2 * band + 1)
    cols = rows + np.tile(np.arange(-band, band + 1), n)
    keep = (cols >= 0) & (cols < n)
    w = 0.05 * np.ones(keep.sum(), np.float32)
    enc = topo.encode((rows[keep], cols[keep], w), kind="sparse_coo",
                      n_pre=n, n_post=n)
    nodes = [
        events.LayerNode("h", LIF(tau=0.8, v_th=0.6), ff_integrate,
                         (Connection("input", topology=enc),), n),
        events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 8),
    ]
    params = {"h": {}, "ro": {"w_h": 0.1 * np.ones((n, 8), np.float32)}}
    rng = np.random.default_rng(0)

    def chunks():
        for _ in range(T // chunk):
            yield jnp.asarray((rng.random((chunk, 1, n)) < 0.2),
                              jnp.float32)

    if mode == "stream":
        for st, out in plan.run_stream(nodes, params, chunks()):
            out.block_until_ready()
    else:
        x = jnp.concatenate(list(chunks()), axis=0)
        _, out, _ = plan.run(nodes, params, x)
        out.block_until_ready()
    # peak RSS via VmHWM: unlike ru_maxrss it resets on exec, so a large
    # launching process cannot taint the measurement through fork
    hwm = [l for l in open("/proc/self/status") if l.startswith("VmHWM")]
    print(hwm[0].split()[1])
""")


def _peak_rss_kb(mode: str, T: int) -> int:
    r = subprocess.run([sys.executable, "-c", _MEM_SCRIPT, mode, str(T)],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return int(r.stdout.strip().splitlines()[-1])


def measure_stream_memory(t_short: int = 256, t_long: int = 2048) -> Dict:
    short = _peak_rss_kb("stream", t_short)
    long_ = _peak_rss_kb("stream", t_long)
    oneshot = _peak_rss_kb("oneshot", t_long)
    return {
        "t_short": t_short, "t_long": t_long,
        "stream_short_rss_kb": short,
        "stream_long_rss_kb": long_,
        "oneshot_long_rss_kb": oneshot,
        # constancy: ~1.0 when streaming peak memory is flat in T
        "long_over_short_rss": long_ / short,
        # linear growth of the full-time path over the streaming footprint
        "oneshot_over_stream_rss": oneshot / long_,
    }


def run() -> Dict:
    print("=== compressed-topology execution vs dense ===")
    out: Dict = {}

    r = measure_exec_vs_dense()
    out["exec_vs_dense"] = r
    print(f"n={r['n']} band={r['band']}: gather {r['gather_ms']:.2f} ms vs "
          f"dense {r['dense_ms']:.2f} ms  -> {r['speedup_x']:.2f}x "
          f"(err {r['max_abs_err']:.1e}, "
          f"{r['dense_bytes'] / r['compressed_bytes']:.0f}x less memory)")

    for key, (n, band, bk) in {"scale_1e5": (100_000, 32, 128),
                               "scale_1e6": (1_000_000, 2, 32)}.items():
        r = measure_scale(n, band, bk)
        out[key] = r
        print(f"n={r['n']:>9,} band={r['band']}: {r['step_ms']:8.2f} ms/step "
              f"compressed ({r['compressed_bytes'] / 2**20:.0f} MB tables); "
              f"dense modeled {r['modeled_dense_bytes'] / 2**30:.0f} GB "
              f"-> {r['mem_ratio_dense_over_compressed']:.0f}x")

    r = measure_stream_memory()
    out["stream_memory"] = r
    print(f"stream RSS T={r['t_short']}: {r['stream_short_rss_kb']//1024} MB"
          f"  T={r['t_long']}: {r['stream_long_rss_kb']//1024} MB "
          f"(x{r['long_over_short_rss']:.2f}); one-shot T={r['t_long']}: "
          f"{r['oneshot_long_rss_kb']//1024} MB "
          f"(x{r['oneshot_over_stream_rss']:.2f} over streaming)")
    return out


if __name__ == "__main__":
    run()
