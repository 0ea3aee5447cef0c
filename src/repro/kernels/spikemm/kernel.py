"""Event-gated block-sparse spike matmul (FINDIDX + LOCACC on TPU).

TaiBai skips computation for silent neurons at word granularity via the
event-driven NoC. The MXU's granularity is a 128x128 tile, so the TPU-native
translation is: partition the spike matrix into (bm x bk) blocks, precompute
a per-block occupancy bitmap (the FINDIDX bitmap, lifted to block level),
and skip the matmul + accumulation for blocks with no events. At the paper's
measured spike rates (1.2-13 %, §V) most K-blocks of a well-laid-out spike
matrix are silent, so the MXU executes a fraction of the dense FLOPs.

grid = (M/bm, N/bn, K/bk), K innermost; fp32 VMEM scratch accumulates across
K. The flattened occupancy bitmap is scalar-prefetched into SMEM (a (1, 1)
VMEM block would break the TPU's (8, 128) tiling once the bitmap has more
than one entry); `@pl.when` gates BOTH the weight load (no HBM->VMEM stream
for dead blocks under Mosaic's lazy block fetch) and the MXU op.

VMEM per step (defaults bm=128, bk=512, bn=512, bf16 in / fp32 acc):
  spikes 128*512*2 = 128 KiB, w 512*512*2 = 512 KiB, acc 128*512*4 = 256 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import f32_precision


def _spikemm_kernel(flags_ref, s_ref, w_ref, o_ref, acc_scr):
    i_idx = pl.program_id(0)
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(flags_ref[i_idx * nk + k_idx] > 0)
    def _():
        s_blk = s_ref[...]
        w_blk = w_ref[...]
        acc_scr[...] += jax.lax.dot_general(
            s_blk, w_blk, (((1,), (0,)), ((), ())),
            precision=f32_precision(s_blk, w_blk),
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def spikemm_pallas(flags: jax.Array, spikes: jax.Array, w: jax.Array, *,
                   bm: int = 128, bk: int = 512, bn: int = 512,
                   interpret: bool = False) -> jax.Array:
    """flags: (M/bm, K/bk) int32 block occupancy; spikes: (M, K); w: (K, N)."""
    M, K = spikes.shape
    N = w.shape[1]
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (M // bm, N // bn, K // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                               # flags
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, f: (i, k)),  # spikes
            pl.BlockSpec((bk, bn), lambda i, j, k, f: (k, j)),  # weights
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, f: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _spikemm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), spikes.dtype),
        name="spikemm_pallas",
        interpret=interpret,
    )(flags.reshape(-1).astype(jnp.int32), spikes, w)
