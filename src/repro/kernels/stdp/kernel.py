"""Fused STDP weight-update Pallas kernels (the paper's on-chip learning in
one pass over the weight tile).

Two kernels share the tile layout: `stdp_pallas` applies ONE pair-rule
step given precomputed traces; `stdp_seq_pallas` is the generalized form
the plan compiler lowers `SynapseProgram`s to — K signed outer-product
term planes applied over T serial steps with the weight tile VMEM-resident
for the whole window (one HBM round-trip per window, not per step).

One STDP step over a batch of B parallel synapse-update events:

    dw = a_plus * x_pre^T @ s_post  -  a_minus * s_pre^T @ x_post
    w' = clip(w + dw, w_min, w_max)

Both outer products are MXU matmuls with the BATCH as the contraction dim;
the clip and accumulate fuse into the same VMEM tile visit, so the weight
matrix streams HBM->VMEM->HBM exactly once per step (on chip, this is the
FIRE-stage weight update touching each synapse once — §III-B).

grid = (N_pre/bm, N_post/bn); B (the contraction) is kept whole per tile —
STDP batches are small (events of one timestep), so B<=512 fits VMEM:
tiles at defaults (bm=bn=256, B=256, f32): x_pre 256 KiB, s_post 256 KiB,
w 256 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import f32_precision


def _stdp_kernel(xpre_ref, spost_ref, spre_ref, xpost_ref, w_ref, out_ref, *,
                 a_plus: float, a_minus: float, w_min: float, w_max: float):
    xpre = xpre_ref[...].astype(jnp.float32)      # (B, bm)
    spost = spost_ref[...].astype(jnp.float32)    # (B, bn)
    spre = spre_ref[...].astype(jnp.float32)      # (B, bm)
    xpost = xpost_ref[...].astype(jnp.float32)    # (B, bn)
    pot = jax.lax.dot_general(xpre, spost, (((0,), (0,)), ((), ())),
                              precision=f32_precision(xpre, spost),
                              preferred_element_type=jnp.float32)
    dep = jax.lax.dot_general(spre, xpost, (((0,), (0,)), ((), ())),
                              precision=f32_precision(spre, xpost),
                              preferred_element_type=jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    w = w + a_plus * pot - a_minus * dep
    out_ref[...] = jnp.clip(w, w_min, w_max).astype(out_ref.dtype)


def _stdp_seq_kernel(p_ref, q_ref, w_ref, out_ref, *,
                     amps: tuple, w_min: float, w_max: float, nsteps: int):
    w = w_ref[...].astype(jnp.float32)            # (bm, bn), VMEM-resident

    # P/Q arrive as (K, T, B, block): step t indexes a leading, untiled
    # axis, so no dynamic offset ever lands on the sublane (B) axis, which
    # Mosaic could only take at a provable multiple of 8.
    def step(t, w):
        dw = jnp.zeros_like(w)
        for k, amp in enumerate(amps):            # K static: unrolled
            p = p_ref[k, t].astype(jnp.float32)   # (B, bm)
            q = q_ref[k, t].astype(jnp.float32)   # (B, bn)
            dw = dw + amp * jax.lax.dot_general(
                p, q, (((0,), (0,)), ((), ())),
                precision=f32_precision(p, q),
                preferred_element_type=jnp.float32)
        return jnp.clip(w + dw, w_min, w_max)

    w = jax.lax.fori_loop(0, nsteps, step, w)
    out_ref[...] = w.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("amps", "w_min", "w_max",
                                             "batch", "bm", "bn", "interpret"))
def stdp_seq_pallas(P: jax.Array, Q: jax.Array, w: jax.Array, *,
                    amps: tuple, w_min: float, w_max: float, batch: int,
                    bm: int = 256, bn: int = 256,
                    interpret: bool = False) -> jax.Array:
    """Generalized multi-step STDP: K term planes over T serial steps.

    P: (K, T*B, M); Q: (K, T*B, N); w: (M, N). The weight tile stays
    VMEM-resident across ALL T steps — one HBM->VMEM->HBM pass over the
    weight matrix per *window*, vs per step for the single-step kernel.
    Both outer products per step are MXU matmuls with the batch as the
    contraction dim; the clip fuses into the same tile visit.
    """
    K, TB, M = P.shape
    N = Q.shape[2]
    assert M % bm == 0 and N % bn == 0 and TB % batch == 0, (M, N, TB)
    T = TB // batch
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_stdp_seq_kernel, amps=amps, w_min=w_min,
                          w_max=w_max, nsteps=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, T, batch, bm), lambda i, j: (0, 0, 0, i)),  # P
            pl.BlockSpec((K, T, batch, bn), lambda i, j: (0, 0, 0, j)),  # Q
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),                 # w
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), w.dtype),
        name="stdp_seq_pallas",
        interpret=interpret,
    )(P.reshape(K, T, batch, M), Q.reshape(K, T, batch, N), w)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "a_plus", "a_minus",
                                             "w_min", "w_max", "interpret"))
def stdp_pallas(x_pre: jax.Array, s_post: jax.Array, s_pre: jax.Array,
                x_post: jax.Array, w: jax.Array, *,
                a_plus: float, a_minus: float, w_min: float, w_max: float,
                bm: int = 256, bn: int = 256,
                interpret: bool = False) -> jax.Array:
    """x_pre/s_pre: (B, N_pre); x_post/s_post: (B, N_post); w: (N_pre, N_post)."""
    B, M = x_pre.shape
    N = x_post.shape[1]
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_stdp_kernel, a_plus=a_plus, a_minus=a_minus,
                          w_min=w_min, w_max=w_max),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, bm), lambda i, j: (0, i)),   # x_pre
            pl.BlockSpec((B, bn), lambda i, j: (0, j)),   # s_post
            pl.BlockSpec((B, bm), lambda i, j: (0, i)),   # s_pre
            pl.BlockSpec((B, bn), lambda i, j: (0, j)),   # x_post
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),  # w
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), w.dtype),
        name="stdp_pallas",
        interpret=interpret,
    )(x_pre, s_post, s_pre, x_post, w)
