"""Flash attention forward Pallas kernel (online softmax, causal + window).

grid = (BH, Tq/bq, S/bk) with the KV dimension innermost. Scratch carries
(acc: (bq, d), m: (bq, 128), l: (bq, 128)) across KV blocks (m/l replicated
over the 128-lane minor dim — TPU VREGs have no efficient (bq, 1) layout).

Causality is exploited structurally: KV blocks entirely above the diagonal
are skipped with `@pl.when` (no MXU work, no softmax) — the same
block-granular event-skipping idea as spikemm, applied to the causal mask;
sliding-window attention additionally skips blocks below the window band,
making the kernel O(T*W) for window W (zamba2's 500k-context hybrid blocks).

VMEM at defaults (bq=512, bk=512, d<=256, bf16): q 256 KiB, k/v 512 KiB,
acc/m/l fp32 ~1.3 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import f32_precision

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_scr, m_scr, l_scr, *,
                  bq: int, bk: int, causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_start = qi * bq
    k_start = kj * bk

    def compute():
        q = q_ref[0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=f32_precision(q, k),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        d = q_pos - k_pos
        ok = jnp.ones((bq, bk), bool)
        if causal:
            ok &= d >= 0
        if window > 0:
            ok &= d < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                 # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)             # (bq, 1)
        l_new = l_scr[...][:, :1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=f32_precision(p, v),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal or window > 0:
        gate = jnp.asarray(True)
        if causal:
            # skip blocks strictly above the diagonal
            gate = jnp.logical_and(gate, k_start <= q_start + bq - 1)
        if window > 0:
            # skip blocks entirely below the sliding-window band
            gate = jnp.logical_and(gate,
                                   k_start + bk - 1 >= q_start - window + 1)

        @pl.when(gate)
        def _():
            compute()
    else:
        compute()

    @pl.when(kj == nk - 1)
    def _():
        lsum = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(lsum, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bq", "bk", "causal", "window", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           bq: int = 512, bk: int = 512, causal: bool = True,
                           window: int = 0, interpret: bool = False):
    """q: (BH, T, d); k, v: (BH, S, d). T % bq == 0, S % bk == 0."""
    BH, T, d = q.shape
    S = k.shape[1]
    assert T % bq == 0 and S % bk == 0
    grid = (BH, T // bq, S // bk)
    scale = d ** -0.5

    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        name="flash_attention_pallas",
        interpret=interpret,
    )(q, k, v)
