"""Shared kernel utilities: backend detection, padding, matmul precision.

Dispatch policy and block sizing used to live here too; they moved into
`repro.kernels.registry` (`use_pallas` / `interpret_mode` / `fit_block`)
so that every family resolves them through one code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.cache
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_axis(x: jax.Array, axis: int, mult: int, value=0.0):
    """Pad `axis` of x up to a multiple of `mult`. Returns (padded, orig_len)."""
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


def f32_precision(*operands):
    """Precision for a kernel's `dot_general`: full f32 when every operand
    is f32, the MXU's native pass otherwise.

    At default precision the TPU (Mosaic and XLA alike) rounds f32 matmul
    operands to bf16 for one MXU pass. The kernels promise their f32
    references' numerics, and the rounding breaks that promise: on a v5e
    it flipped 1.5% of the SHD model's hidden spikes and 27% of the
    recurrent ECG model's against the f32 stepper."""
    if all(o.dtype == jnp.float32 for o in operands):
        return jax.lax.Precision.HIGHEST
    return None
