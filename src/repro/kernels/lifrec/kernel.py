"""Fused recurrent-LIF Pallas kernel (DIFF + LOCACC(self) + threshold + SEND).

Like `lif/kernel.py`, the reset makes the scan non-associative, so time runs
serially inside the kernel — but here every step also applies the recurrent
weights to the previous step's spikes. The win is residency: W_rec stays in
VMEM for the whole time chunk (on chip this is the NC-local weight SRAM),
the per-step (bb, N) x (N, N) matmul feeds the MXU from VMEM, and neither
membrane state nor spikes round-trip to HBM between steps.

The neuron axis is NOT blocked: the recurrence couples all N outputs to all
N previous spikes, so the whole (N, N) weight block must be resident. SNN
populations are small (64-2048 neurons); the wrapper pads N to the 128-lane
boundary. grid = (B/bb, T/ct), time innermost; scratch v and s: (bb, N)
carry the state across time chunks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import f32_precision


def _lifrec_kernel(cur_ref, w_ref, tau_ref, v0_ref, s0_ref, s_out_ref,
                   vT_ref, v_scr, s_scr, *, ct: int, v_th: float):
    t_idx = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t_idx == 0)
    def _():
        v_scr[...] = v0_ref[...].astype(jnp.float32)
        s_scr[...] = s0_ref[...].astype(jnp.float32)

    w = w_ref[...].astype(jnp.float32)               # (N, N)
    tau = tau_ref[...].astype(jnp.float32)           # (1, N)

    # per-step time slices go through the refs (see lif/kernel.py)
    def step(t, carry):
        v, s = carry
        rec = jax.lax.dot_general(s, w, (((1,), (0,)), ((), ())),
                                  precision=f32_precision(s, w),
                                  preferred_element_type=jnp.float32)
        v = tau * v + cur_ref[t].astype(jnp.float32) + rec
        spk = (v >= v_th).astype(jnp.float32)
        v = v * (1.0 - spk)
        s_out_ref[t] = spk.astype(s_out_ref.dtype)
        return v, spk

    v, s = jax.lax.fori_loop(0, ct, step, (v_scr[...], s_scr[...]))
    v_scr[...] = v
    s_scr[...] = s

    @pl.when(t_idx == nt - 1)
    def _():
        vT_ref[...] = v.astype(vT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "bb", "v_th", "interpret"))
def lifrec_pallas(current: jax.Array, w_rec: jax.Array, tau: jax.Array,
                  v0: jax.Array, s0: jax.Array, *, v_th: float = 1.0,
                  ct: int = 128, bb: int = 8, interpret: bool = False):
    """current: (T, B, N); w_rec: (N, N); tau: (N,); v0/s0: (B, N).

    T % ct == 0, B % bb == 0, N a multiple of 128 (wrapper pads).
    """
    T, B, N = current.shape
    assert T % ct == 0 and B % bb == 0
    grid = (B // bb, T // ct)
    tau2 = tau.reshape(1, N)

    return pl.pallas_call(
        functools.partial(_lifrec_kernel, ct=ct, v_th=v_th),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ct, bb, N), lambda i, t: (t, i, 0)),   # current
            pl.BlockSpec((N, N), lambda i, t: (0, 0)),           # w_rec
            pl.BlockSpec((1, N), lambda i, t: (0, 0)),           # tau
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # v0
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # s0
        ],
        out_specs=[
            pl.BlockSpec((ct, bb, N), lambda i, t: (t, i, 0)),   # spikes
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # vT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, N), current.dtype),
            jax.ShapeDtypeStruct((B, N), current.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, N), jnp.float32),
                        pltpu.VMEM((bb, N), jnp.float32)],
        name="lifrec_pallas",
        interpret=interpret,
    )(current, w_rec, tau2, v0, s0)
