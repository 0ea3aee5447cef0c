"""Chunked diagonal linear-recurrence Pallas kernel (the DIFF instruction).

Computes  y_t = a_t * y_{t-1} + x_t  over the leading (time) axis for a
(T, B, D) tensor, carrying hidden state across time chunks.

TPU mapping
-----------
grid = (B/bb, D/bd, T/ct) with the TIME dimension innermost: TPU grids
execute sequentially, so a VMEM scratch tile h:(bb, bd) carries the state
from one time chunk to the next without HBM round-trips. Within a chunk the
scan is computed in log2(ct) Hillis-Steele doubling steps over the VMEM
block — all (ct, bb, bd) elementwise VPU work, no serial per-timestep loop.

VMEM working set per grid step (fp32 compute):
    a, x, y blocks: 3 * ct*bb*bd * 4 B   (+ scratch bb*bd)
Default tile (ct, bb, bd) = (256, 8, 512) -> 12.6 MiB of ~16 MiB VMEM.
bd is a multiple of 128 (lane width); bb a multiple of 8 (sublanes, fp32).

FLOPs: 3 * T*B*D * log2(ct) fp32 VPU flops vs 2*T*B*D for the serial form —
the kernel trades ~3.5x arithmetic for chunk-parallel VPU execution; the op
is HBM-bandwidth-bound (arithmetic intensity < 2 flops/byte), so the extra
flops are free and the roofline term is the 3 tensor streams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _linrec_kernel(a_ref, x_ref, h0_ref, y_ref, hT_ref, h_scratch, *, ct: int):
    t_idx = pl.program_id(2)
    nt = pl.num_programs(2)

    # First time-chunk: seed the carried state from h0.
    @pl.when(t_idx == 0)
    def _():
        h_scratch[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)          # (ct, bb, bd)
    x = x_ref[...].astype(jnp.float32)

    # Hillis-Steele inclusive scan of the monoid (a, x) along time.
    off = 1
    while off < ct:                             # static python loop
        a_prev = jnp.pad(a[:-off], ((off, 0), (0, 0), (0, 0)),
                         constant_values=1.0)
        x_prev = jnp.pad(x[:-off], ((off, 0), (0, 0), (0, 0)))
        x = x + a * x_prev
        a = a * a_prev
        off *= 2

    h = h_scratch[...]
    y = x + a * h[None]                         # inject carry
    y_ref[...] = y.astype(y_ref.dtype)
    # a static slice: `y[-1]` would lower to dynamic_slice, which the TPU
    # lowering does not implement
    y_last = jax.lax.index_in_dim(y, ct - 1, 0, keepdims=False)
    h_scratch[...] = y_last

    @pl.when(t_idx == nt - 1)
    def _():
        hT_ref[...] = y_last.astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "bb", "bd", "interpret"))
def linrec_pallas(a: jax.Array, x: jax.Array, h0: jax.Array, *,
                  ct: int = 256, bb: int = 8, bd: int = 512,
                  interpret: bool = False):
    """a, x: (T, B, D); h0: (B, D). T % ct == 0, B % bb == 0, D % bd == 0.

    Returns (y: (T, B, D), h_final: (B, D)).
    """
    T, B, D = x.shape
    assert T % ct == 0 and B % bb == 0 and D % bd == 0, (T, B, D, ct, bb, bd)
    grid = (B // bb, D // bd, T // ct)

    return pl.pallas_call(
        functools.partial(_linrec_kernel, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ct, bb, bd), lambda i, j, t: (t, i, j)),   # a
            pl.BlockSpec((ct, bb, bd), lambda i, j, t: (t, i, j)),   # x
            pl.BlockSpec((bb, bd), lambda i, j, t: (i, j)),          # h0
        ],
        out_specs=[
            pl.BlockSpec((ct, bb, bd), lambda i, j, t: (t, i, j)),   # y
            pl.BlockSpec((bb, bd), lambda i, j, t: (i, j)),          # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, D), x.dtype),
            jax.ShapeDtypeStruct((B, D), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, bd), jnp.float32)],
        name="linrec_pallas",
        interpret=interpret,
    )(a, x, h0)
