"""Block-sparse spikemm channel: visit ONLY the occupied MXU blocks.

The dense kernel (`kernel.py`) already *gates* the MXU op on the per-block
occupancy bitmap, but its grid still iterates every (M/bm, N/bn, K/bk)
step: silent blocks cost a grid iteration and, off the `@pl.when` fast
path, a spike-block DMA. This module goes the rest of the way — the
paper's event-driven claim is that silent work is never *issued*:

  1. `compact_blocks` turns the (M/bm, K/bk) occupancy bitmap into a
     row-major compacted list of occupied (i, k) block coordinates.
  2. `spikemm_sparse_pallas` launches a grid over (N/bn, n_selected) —
     the compacted list, not the dense block lattice — using a
     scalar-prefetch index map (`pltpu.PrefetchScalarGridSpec`) so Mosaic
     streams exactly the occupied spike/weight blocks and accumulates
     into the output tile across consecutive same-row entries.
  3. `spikemm_sparse_ref` is the XLA twin: drop silent block-rows and
     block-columns, one dense matmul over the occupied slab, scatter the
     row blocks back. On CPU this is what converts low occupancy into
     wall-clock (compute scales with the occupied slab, not M*K), so the
     efficiency claim is measurable off-TPU too.

Compaction subtleties (both paths share `compact_blocks`):

  * Every row block contributes at least one entry — silent rows get a
    single *inactive* sentinel — so the kernel's output-revisit
    accounting initializes and writes every output block exactly once
    per (row, j); no aliased zero-init of the output is needed.
  * When `flags` is a tracer (sparse channel forced under jit), the
    entry count is data-dependent, so the list is padded to the static
    Mb*Kb capacity. Padding replicates the *last* row's block
    coordinates, inactive: the out-block index never moves after the
    last real entry, so padded steps neither thrash DMA nor write back
    a stale tile. Correctness is preserved; the grid shrink (and hence
    the speedup) needs concrete occupancy, which eager dispatch has.

The density threshold that routes spikemm here lives in the tuning cache
(`tune_sparse_threshold` times dense-vs-sparse on a density ladder and
persists the crossover under kernel key "spikemm.sparse_th"), so the
policy is autotuned per (backend, shape bucket) like block sizes are.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import f32_precision


def compact_blocks(flags: jax.Array,
                   size: Optional[int] = None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compact an occupancy bitmap into (idx_i, idx_k, active) lists.

    flags: (Mb, Kb) int; returns three (n,) int32 arrays sorted by row
    block. `active[t] == 0` marks sentinel entries (one per silent row so
    every output block is visited) and capacity padding (traced path) —
    the kernel skips their MXU work. Padded entries point at the last
    row's block so the output tile never revisits an already-flushed
    block.
    """
    Mb, Kb = flags.shape
    occ = flags != 0
    # one sentinel column flagging rows with no occupied block
    aug = jnp.concatenate([occ, ~jnp.any(occ, axis=1, keepdims=True)], axis=1)
    if size is None:
        if isinstance(aug, jax.core.Tracer):
            size = Mb * Kb          # nnz + sentinels <= Mb*Kb (each row <= Kb)
        else:
            size = int(jnp.sum(aug))
    ii, cc = jnp.nonzero(aug, size=size, fill_value=(Mb - 1, Kb))
    active = cc < Kb
    kk = jnp.where(active, cc, 0)
    return (ii.astype(jnp.int32), kk.astype(jnp.int32),
            active.astype(jnp.int32))


def _sparse_kernel(ii_ref, kk_ref, act_ref, s_ref, w_ref, o_ref, acc_scr):
    del kk_ref  # consumed by the index maps only
    t = pl.program_id(1)
    prev_i = ii_ref[jnp.maximum(t - 1, 0)]

    @pl.when((t == 0) | (ii_ref[t] != prev_i))
    def _():                                   # first entry for this row block
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(act_ref[t] > 0)
    def _():                                   # sentinels/padding skip the MXU
        s, w = s_ref[...], w_ref[...]
        acc_scr[...] += jax.lax.dot_general(
            s, w, (((1,), (0,)), ((), ())), precision=f32_precision(s, w),
            preferred_element_type=jnp.float32)

    # Same-row entries are contiguous, so consecutive writes land in the
    # same VMEM-resident output block; Mosaic flushes it once per (row, j).
    o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def spikemm_sparse_pallas(idx_i: jax.Array, idx_k: jax.Array,
                          active: jax.Array, spikes: jax.Array, w: jax.Array,
                          *, bm: int = 128, bk: int = 512, bn: int = 512,
                          interpret: bool = False) -> jax.Array:
    """Gather-style spikemm over the compacted block list.

    idx_i/idx_k/active: (n,) int32 from `compact_blocks`; spikes: (M, K);
    w: (K, N); all dims divisible by their block size. grid = (N/bn, n)
    with the compacted list innermost — the scalar-prefetch index maps
    pull block (idx_i[t], idx_k[t]) instead of walking the dense lattice.
    """
    M, K = spikes.shape
    N = w.shape[1]
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (N // bn, idx_i.shape[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, t, ii, kk, act: (ii[t], kk[t])),
            pl.BlockSpec((bk, bn), lambda j, t, ii, kk, act: (kk[t], j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, t, ii, kk, act: (ii[t], j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _sparse_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), spikes.dtype),
        name="spikemm_sparse_pallas",
        interpret=interpret,
    )(idx_i, idx_k, active, spikes, w)


@jax.jit
def _rowcol_any(flags: jax.Array) -> Tuple[jax.Array, jax.Array]:
    occ = flags != 0
    return jnp.any(occ, axis=1), jnp.any(occ, axis=0)


def _pad_count(n: int) -> int:
    """Round a selection count up the {1, 1.5} * 2^k ladder (1, 2, 3, 4,
    6, 8, 12, ...): recompiles stay logarithmic in the raster shape while
    padding waste stays <= 33% (a pure pow2 ladder wastes up to 2x)."""
    p = 1 << (max(1, n) - 1).bit_length()
    if n <= (p // 4) * 3:
        return (p // 4) * 3
    return p


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def _slab_matmul(spikes: jax.Array, w: jax.Array, ridx: jax.Array,
                 cidx: jax.Array, *, bm: int, bk: int) -> jax.Array:
    """Occupied-slab matmul: gather the selected block-rows/-columns, one
    dense matmul over the compacted slab, scatter the rows back. Sentinel
    indices (== Mb / Kb, out of range) gather zeros and scatter into a
    discarded overflow row, so pow2-padded index lists stay exact."""
    M, K = spikes.shape
    N = w.shape[1]
    Mb, Kb = M // bm, K // bk
    r, c = ridx.shape[0], cidx.shape[0]
    sb = spikes.reshape(Mb, bm, Kb, bk)
    s_sel = jnp.take(sb, ridx, axis=0, mode="fill", fill_value=0)
    s_sel = jnp.take(s_sel, cidx, axis=2, mode="fill", fill_value=0)
    w_sel = jnp.take(w.reshape(Kb, bk, N), cidx, axis=0, mode="fill",
                     fill_value=0)
    prod = jnp.dot(s_sel.reshape(r * bm, c * bk), w_sel.reshape(c * bk, N),
                   preferred_element_type=jnp.float32)
    out = jnp.zeros((Mb + 1, bm, N), jnp.float32)
    out = out.at[ridx].set(prod.reshape(r, bm, N))
    return out[:Mb].reshape(M, N).astype(spikes.dtype)


def spikemm_sparse_ref(flags: jax.Array, spikes: jax.Array, w: jax.Array, *,
                       bm: int, bk: int) -> jax.Array:
    """XLA twin of the sparse kernel: skip silent block-rows and -columns.

    flags: (M/bm, K/bk) occupancy; spikes: (M, K) with M, K divisible by
    bm, bk; w: (K, N), N unconstrained. XLA has no compacted-grid analogue
    of the Pallas kernel, so the gather happens at slab granularity: block
    rows/columns with no events anywhere are dropped before ONE dense
    matmul over the occupied slab — compute and bandwidth scale with the
    occupied fraction, which is what converts low occupancy into
    wall-clock on backends without the Mosaic kernel. Index lists are
    padded up a {1, 1.5} * 2^k ladder (sentinel entries gather zeros /
    scatter into a discarded row) so recompiles stay logarithmic in the
    raster shape.

    Needs concrete occupancy to shrink anything; under tracing it degrades
    to the dense oracle (same values, no skip) — the Pallas channel is the
    one that stays block-sparse under jit via capacity padding.
    """
    if isinstance(flags, jax.core.Tracer):
        return jnp.dot(spikes, w, preferred_element_type=jnp.float32
                       ).astype(spikes.dtype)
    Mb, Kb = flags.shape
    row_any, col_any = _rowcol_any(flags)
    rows = jnp.nonzero(row_any)[0]
    cols = jnp.nonzero(col_any)[0]
    if rows.shape[0] == 0:
        return jnp.zeros((spikes.shape[0], w.shape[1]), spikes.dtype)
    ridx = jnp.full((_pad_count(rows.shape[0]),), Mb, jnp.int32
                    ).at[:rows.shape[0]].set(rows.astype(jnp.int32))
    cidx = jnp.full((_pad_count(cols.shape[0]),), Kb, jnp.int32
                    ).at[:cols.shape[0]].set(cols.astype(jnp.int32))
    return _slab_matmul(spikes, w, ridx, cidx, bm=bm, bk=bk)


# ---------------------------------------------------------------------------
# threshold autotuning: where does sparse stop paying?
# ---------------------------------------------------------------------------


def tune_sparse_threshold(M: int, K: int, N: int, *,
                          densities: Tuple[float, ...] = (
                              0.02, 0.05, 0.1, 0.2, 0.35, 0.5, 0.75),
                          repeats: int = 3, cache=None, save: bool = True,
                          key: Optional[jax.Array] = None):
    """Time dense vs sparse dispatch on a block-occupancy ladder and persist
    the crossover occupancy (as permille) to the tuning cache under kernel
    key "spikemm.sparse_th", bucketed like block configs. The dispatch
    policy (`ops._select_channel`) looks it up per shape; a miss falls back
    to the conservative default.

    Returns (threshold fraction, report). Rasters are population-packed
    (active corner), the layout the mapping pass produces and the only one
    where word sparsity survives to block granularity.
    """
    import time

    from repro.kernels import registry, tuning

    spec = registry.get("spikemm")
    key = jax.random.PRNGKey(0) if key is None else key
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    dims = {"M": M, "K": K, "N": N}
    blocks = spec.resolve_blocks(dims, use_cache=False)

    def timed(fn, reps):
        fn().block_until_ready()                         # warm/compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn().block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    use_pallas = registry.use_pallas()
    interpret = registry.interpret_mode()

    def dense(s):
        if use_pallas:
            return spec.pallas(s, w, blocks=blocks, interpret=interpret)
        return spec.ref(s, w)

    def sparse(s):
        ch = spec.channels["sparse"]
        if use_pallas:
            return ch.pallas(s, w, blocks=blocks, interpret=interpret)
        return ch.ref(s, w, blocks=blocks)

    report = {"dims": dims, "blocks": blocks, "ladder": []}
    threshold = 0.0
    for d in densities:
        s = _packed_raster(key, M, K, d)
        occ = _occupancy(s, blocks["bm"], blocks["bk"])
        t_dense = timed(lambda: dense(s), repeats)
        t_sparse = timed(lambda: sparse(s), repeats)
        win = t_dense / max(t_sparse, 1e-12)
        report["ladder"].append({"density": d, "occupancy": occ,
                                 "dense_s": t_dense, "sparse_s": t_sparse,
                                 "speedup_x": win})
        if win >= 1.0:
            threshold = max(threshold, occ)
    report["threshold"] = threshold
    if cache is None:
        cache = tuning.default_cache()
    cache.put("spikemm.sparse_th", jax.default_backend(),
              tuning.shape_bucket(dims),
              {"permille": int(round(1000 * threshold))},
              stats={"ladder_points": len(densities)})
    if save:
        cache.save()
    return threshold, report


def _packed_raster(key, M: int, K: int, density: float,
                   rate: float = 0.5) -> jax.Array:
    """Population-packed spike raster at a target word density: activity
    fills a dense corner (the mapping pass's channel-order packing), so
    block occupancy tracks density instead of being defeated by it."""
    f = min(1.0, float(density / rate) ** 0.5)
    m_act, k_act = max(1, int(M * f)), max(1, int(K * f))
    body = (jax.random.uniform(key, (m_act, k_act)) < rate
            ).astype(jnp.float32)
    return jnp.zeros((M, K), jnp.float32).at[:m_act, :k_act].set(body)


def _occupancy(s, bm: int, bk: int) -> float:
    from repro.kernels.spikemm.ops import occupancy_fraction

    return float(occupancy_fraction(s, bm, bk))


__all__ = ["compact_blocks", "spikemm_sparse_pallas", "spikemm_sparse_ref",
           "tune_sparse_threshold"]
