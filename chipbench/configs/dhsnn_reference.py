"""Plain reference of the DH-SNN (Zheng et al., Nat. Commun. 15:277, 2024)
and the weights every run draws from its seed.

    d_t = sigmoid(l_d) * d_{t-1} + x_t @ W_in[k]        per branch k
    v_t = sigmoid(l_s) * v_{t-1} + sum_k d_t[k]         soma
    s_t = v_t >= v_th ;  v_t <- v_t * (1 - s_t)         spike, hard reset
    y_t = tau_ro * y_{t-1} + s_t @ W_ro                 LI readout

stepped in time order in float32, with every product at full f32
precision. It imports nothing of the program and takes nothing the
program made.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def make_weights(cfg: Dict[str, Any], key) -> Dict[str, jax.Array]:
    """Seeded weights on the device, in one call, f32 as served.

    W_in ~ N(0, 1/n_in) of shape (branches, n_in, hidden) and W_ro ~
    N(0, 1/hidden); the decay logits are fixed by the configuration:
    branch logits evenly spaced over `branch_tau_logit_range`, the soma's
    `soma_tau_logit`."""
    k_b, n_in, n_h = cfg["n_branches"], cfg["n_in"], cfg["n_hidden"]
    lo, hi = cfg["branch_tau_logit_range"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return {
            "w_in": jax.random.normal(k1, (k_b, n_in, n_h), jnp.float32)
            / np.sqrt(n_in),
            "w_ro": jax.random.normal(k2, (n_h, cfg["n_out"]), jnp.float32)
            / np.sqrt(n_h),
            "tau_d_logit": jnp.broadcast_to(
                jnp.linspace(lo, hi, k_b, dtype=jnp.float32)[:, None],
                (k_b, n_h)),
            "tau_s_logit": jnp.full((n_h,), cfg["soma_tau_logit"],
                                    jnp.float32),
        }

    return make(key)


def three_pass(w: jax.Array) -> jax.Array:
    """What the MXU's three-pass f32 matmul (`high`) keeps of a weight
    whose other operand is a spike (exact in bf16): the sum of its two
    leading bf16 parts, 16 significant bits."""
    hi = w.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (w - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi + lo


def lower_precision(w: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The control's weights: every matrix that meets spikes at `high`."""
    return dict(w, w_in=three_pass(w["w_in"]), w_ro=three_pass(w["w_ro"]))


def forward(cfg: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array
            ) -> jax.Array:
    """x: (T, B, n_in) spikes from rest -> readout (T, B, n_out)."""
    return _forward(w, x, float(cfg["v_th"]), float(cfg["readout_tau"]))


@jax.jit
def _forward_impl(w, x, v_th, tau_ro):
    tau_d = jax.nn.sigmoid(w["tau_d_logit"])            # (K, H)
    tau_s = jax.nn.sigmoid(w["tau_s_logit"])            # (H,)
    B = x.shape[1]
    K, _, H = w["w_in"].shape
    d0 = jnp.zeros((B, K, H), jnp.float32)
    v0 = jnp.zeros((B, H), jnp.float32)
    y0 = jnp.zeros((B, w["w_ro"].shape[1]), jnp.float32)

    def step(carry, x_t):
        d, v, y = carry
        cur = jnp.einsum("bi,kih->bkh", x_t, w["w_in"], precision=HIGHEST)
        d = tau_d * d + cur
        v = tau_s * v + jnp.sum(d, axis=1)
        s = (v >= v_th).astype(jnp.float32)
        v = v * (1.0 - s)
        y = tau_ro * y + jnp.dot(s, w["w_ro"], precision=HIGHEST)
        return (d, v, y), y

    _, ys = jax.lax.scan(step, (d0, v0, y0), x)
    return ys


def _forward(w, x, v_th, tau_ro):
    return _forward_impl(w, jnp.asarray(x, jnp.float32), v_th, tau_ro)
