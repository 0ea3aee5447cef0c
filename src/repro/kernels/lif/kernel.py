"""Fused LIF integrate-and-fire Pallas kernel (DIFF + threshold + SEND).

Unlike the pure linear recurrence, LIF's reset makes the scan
non-associative, so time is processed serially *inside* the kernel — but the
whole (T_chunk, bb, bn) current block lives in VMEM, so the serial loop is
VPU-bound with zero HBM traffic per step, and states never round-trip to HBM
(on chip, this is exactly why TaiBai keeps v in NC-local memory).

grid = (B/bb, N/bn, T/ct), time innermost; VMEM scratch v:(bb, bn) carries
the membrane across chunks. Default tile (256, 8, 512): current + spikes
blocks = 8.4 MiB VMEM.

The threshold is a scalar; per-neuron decay arrives as a (1, bn) block so
heterogeneous populations (ALIF/PLIF-trained taus) use the same kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lif_kernel(cur_ref, tau_ref, v0_ref, s_ref, vT_ref, v_scr, *,
                ct: int, v_th: float, reset: str):
    t_idx = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t_idx == 0)
    def _():
        v_scr[...] = v0_ref[...].astype(jnp.float32)

    tau = tau_ref[...].astype(jnp.float32)           # (1, bn)

    # Each step reads and writes its time slice through the refs: a dynamic
    # index on the leading (untiled) ref axis is a plain VMEM address on
    # Mosaic, whereas indexing a loaded value lowers to dynamic_slice, which
    # the TPU lowering does not implement.
    def step(t, v):
        v = tau * v + cur_ref[t].astype(jnp.float32)
        s = (v >= v_th).astype(jnp.float32)
        v = v - v_th * s if reset == "subtract" else v * (1.0 - s)
        s_ref[t] = s.astype(s_ref.dtype)
        return v

    v = jax.lax.fori_loop(0, ct, step, v_scr[...])
    v_scr[...] = v

    @pl.when(t_idx == nt - 1)
    def _():
        vT_ref[...] = v.astype(vT_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("ct", "bb", "bn", "v_th", "reset",
                                    "interpret"))
def lif_pallas(current: jax.Array, tau: jax.Array, v0: jax.Array, *,
               v_th: float = 1.0, reset: str = "zero", ct: int = 256,
               bb: int = 8, bn: int = 512, interpret: bool = False):
    """current: (T, B, N); tau: (N,); v0: (B, N). Dims divisible by tiles."""
    T, B, N = current.shape
    assert T % ct == 0 and B % bb == 0 and N % bn == 0
    grid = (B // bb, N // bn, T // ct)
    tau2 = tau.reshape(1, N)

    return pl.pallas_call(
        functools.partial(_lif_kernel, ct=ct, v_th=v_th, reset=reset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ct, bb, bn), lambda i, j, t: (t, i, j)),  # current
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),          # tau
            pl.BlockSpec((bb, bn), lambda i, j, t: (i, j)),         # v0
        ],
        out_specs=[
            pl.BlockSpec((ct, bb, bn), lambda i, j, t: (t, i, j)),  # spikes
            pl.BlockSpec((bb, bn), lambda i, j, t: (i, j)),         # vT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, N), current.dtype),
            jax.ShapeDtypeStruct((B, N), current.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        name="lif_pallas",
        interpret=interpret,
    )(current, tau2, v0)
