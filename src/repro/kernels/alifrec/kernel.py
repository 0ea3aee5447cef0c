"""Fused adaptive-threshold LIF Pallas kernels (DIFF + moving th + SEND).

Two variants of the `lif`/`lifrec` serial-in-time scheme, each carrying one
extra VMEM-resident state plane — the adaptation trace `a` — and comparing
against the moving threshold `v_th + beta * a` instead of a scalar:

  * `alif_pallas`    feed-forward: like `lif/kernel.py`, the neuron axis is
    blocked (adaptation is elementwise), grid (B/bb, N/bn, T/ct), scratch
    v and a carry state across time chunks.
  * `alifrec_pallas` self-recurrent: like `lifrec/kernel.py`, the (N, N)
    recurrent weights stay VMEM-resident and every step applies them to
    the previous spikes, so the neuron axis is NOT blocked (wrapper pads
    N to the 128-lane boundary); grid (B/bb, T/ct), scratch v, a, s.

On chip the adaptation trace is just another NC-local DIFF register —
TaiBai's point that "new neuron model" means "new program", not new
silicon; here it means one extra scratch plane, not a new engine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import f32_precision


def _alif_kernel(cur_ref, tau_ref, rho_ref, v0_ref, a0_ref, s_ref, vT_ref,
                 aT_ref, v_scr, a_scr, *, ct: int, v_th: float, beta: float):
    t_idx = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t_idx == 0)
    def _():
        v_scr[...] = v0_ref[...].astype(jnp.float32)
        a_scr[...] = a0_ref[...].astype(jnp.float32)

    tau = tau_ref[...].astype(jnp.float32)           # (1, bn)
    rho = rho_ref[...].astype(jnp.float32)           # (1, bn)

    # per-step time slices go through the refs (see lif/kernel.py)
    def step(t, carry):
        v, a = carry
        v = tau * v + cur_ref[t].astype(jnp.float32)
        s = (v >= v_th + beta * a).astype(jnp.float32)
        v = v * (1.0 - s)
        a = rho * a + s
        s_ref[t] = s.astype(s_ref.dtype)
        return v, a

    v, a = jax.lax.fori_loop(0, ct, step, (v_scr[...], a_scr[...]))
    v_scr[...] = v
    a_scr[...] = a

    @pl.when(t_idx == nt - 1)
    def _():
        vT_ref[...] = v.astype(vT_ref.dtype)
        aT_ref[...] = a.astype(aT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "bb", "bn", "v_th",
                                             "beta", "interpret"))
def alif_pallas(current: jax.Array, tau: jax.Array, rho: jax.Array,
                v0: jax.Array, a0: jax.Array, *, v_th: float = 1.0,
                beta: float = 1.8, ct: int = 256, bb: int = 8, bn: int = 512,
                interpret: bool = False):
    """current: (T, B, N); tau/rho: (N,); v0/a0: (B, N). Dims tile exactly."""
    T, B, N = current.shape
    assert T % ct == 0 and B % bb == 0 and N % bn == 0
    grid = (B // bb, N // bn, T // ct)

    return pl.pallas_call(
        functools.partial(_alif_kernel, ct=ct, v_th=v_th, beta=beta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ct, bb, bn), lambda i, j, t: (t, i, j)),  # current
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),          # tau
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),          # rho
            pl.BlockSpec((bb, bn), lambda i, j, t: (i, j)),         # v0
            pl.BlockSpec((bb, bn), lambda i, j, t: (i, j)),         # a0
        ],
        out_specs=[
            pl.BlockSpec((ct, bb, bn), lambda i, j, t: (t, i, j)),  # spikes
            pl.BlockSpec((bb, bn), lambda i, j, t: (i, j)),         # vT
            pl.BlockSpec((bb, bn), lambda i, j, t: (i, j)),         # aT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, N), current.dtype),
            jax.ShapeDtypeStruct((B, N), current.dtype),
            jax.ShapeDtypeStruct((B, N), current.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32),
                        pltpu.VMEM((bb, bn), jnp.float32)],
        name="alif_pallas",
        interpret=interpret,
    )(current, tau.reshape(1, N), rho.reshape(1, N), v0, a0)


def _alifrec_kernel(cur_ref, w_ref, tau_ref, rho_ref, v0_ref, a0_ref, s0_ref,
                    s_out_ref, vT_ref, aT_ref, v_scr, a_scr, s_scr, *,
                    ct: int, v_th: float, beta: float):
    t_idx = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t_idx == 0)
    def _():
        v_scr[...] = v0_ref[...].astype(jnp.float32)
        a_scr[...] = a0_ref[...].astype(jnp.float32)
        s_scr[...] = s0_ref[...].astype(jnp.float32)

    w = w_ref[...].astype(jnp.float32)               # (N, N)
    tau = tau_ref[...].astype(jnp.float32)           # (1, N)
    rho = rho_ref[...].astype(jnp.float32)           # (1, N)

    # per-step time slices go through the refs (see lif/kernel.py)
    def step(t, carry):
        v, a, s = carry
        rec = jax.lax.dot_general(s, w, (((1,), (0,)), ((), ())),
                                  precision=f32_precision(s, w),
                                  preferred_element_type=jnp.float32)
        v = tau * v + cur_ref[t].astype(jnp.float32) + rec
        spk = (v >= v_th + beta * a).astype(jnp.float32)
        v = v * (1.0 - spk)
        a = rho * a + spk
        s_out_ref[t] = spk.astype(s_out_ref.dtype)
        return v, a, spk

    v, a, s = jax.lax.fori_loop(0, ct, step,
                                (v_scr[...], a_scr[...], s_scr[...]))
    v_scr[...] = v
    a_scr[...] = a
    s_scr[...] = s

    @pl.when(t_idx == nt - 1)
    def _():
        vT_ref[...] = v.astype(vT_ref.dtype)
        aT_ref[...] = a.astype(aT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "bb", "v_th", "beta",
                                             "interpret"))
def alifrec_pallas(current: jax.Array, w_rec: jax.Array, tau: jax.Array,
                   rho: jax.Array, v0: jax.Array, a0: jax.Array,
                   s0: jax.Array, *, v_th: float = 1.0, beta: float = 1.8,
                   ct: int = 128, bb: int = 8, interpret: bool = False):
    """current: (T, B, N); w_rec: (N, N); tau/rho: (N,); v0/a0/s0: (B, N).

    T % ct == 0, B % bb == 0, N a multiple of 128 (wrapper pads).
    """
    T, B, N = current.shape
    assert T % ct == 0 and B % bb == 0
    grid = (B // bb, T // ct)

    return pl.pallas_call(
        functools.partial(_alifrec_kernel, ct=ct, v_th=v_th, beta=beta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ct, bb, N), lambda i, t: (t, i, 0)),   # current
            pl.BlockSpec((N, N), lambda i, t: (0, 0)),           # w_rec
            pl.BlockSpec((1, N), lambda i, t: (0, 0)),           # tau
            pl.BlockSpec((1, N), lambda i, t: (0, 0)),           # rho
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # v0
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # a0
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # s0
        ],
        out_specs=[
            pl.BlockSpec((ct, bb, N), lambda i, t: (t, i, 0)),   # spikes
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # vT
            pl.BlockSpec((bb, N), lambda i, t: (i, 0)),          # aT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, N), current.dtype),
            jax.ShapeDtypeStruct((B, N), current.dtype),
            jax.ShapeDtypeStruct((B, N), current.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, N), jnp.float32),
                        pltpu.VMEM((bb, N), jnp.float32),
                        pltpu.VMEM((bb, N), jnp.float32)],
        name="alifrec_pallas",
        interpret=interpret,
    )(current, w_rec, tau.reshape(1, N), rho.reshape(1, N), v0, a0, s0)
