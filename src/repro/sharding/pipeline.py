"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The paper's model-pipeline analogue: TaiBai runs network layers as a
pipeline across CC cores, with spike packets flowing stage-to-stage while
every stage works on a different timestep's data (§III-A "model pipeline
parallel computation mechanism"). Here the stages are mesh devices along a
`stage` axis, the packets are microbatch activations moved by
`lax.ppermute`, and the schedule is the classic GPipe fill-drain:

  tick t (0 <= t < M + S - 1): stage s computes microbatch (t - s) if valid,
  then shifts its output one stage rightward.

Stage parameters live sharded over the stage axis (leading dim = S); each
device sees only its own stage's weights, so a model S times larger than
one device's HBM fits. Differentiable (jax.grad through the shard_map),
composable with the DP/TP axes of the same mesh.

Bubble fraction: (S-1)/(M+S-1) — the usual GPipe trade; pick M >= 4*S.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(stage_fn: Callable[[Any, Array], Array],
                   stage_params: Any, x: Array, mesh: Mesh,
                   axis: str = "stage") -> Array:
    """Run `stage_fn` S times as a pipeline over `axis`.

    stage_params: pytree whose leaves have leading dim S (one slice per
      stage), sharded over `axis`.
    x: (M, mb, ...) microbatched input (M microbatches), replicated.
    Returns (M, mb, ...) output of the last stage, replicated.
    """
    S = mesh.shape[axis]
    M = x.shape[0]

    def per_stage(params, x):
        # params: this stage's slice (leading dim 1); x: full (M, mb, ...)
        params = jax.tree.map(lambda p: p[0], params)
        s = jax.lax.axis_index(axis)
        n_ticks = M + S - 1
        buf = jnp.zeros_like(x[0])                  # current inbound act
        outs = jnp.zeros_like(x)                    # last stage collects

        def tick(carry, t):
            buf, outs = carry
            mb_idx = t - s                           # microbatch this stage works on
            valid = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 reads from the input stream; others from the buffer
            x_in = jnp.where(s == 0,
                             x[jnp.clip(t, 0, M - 1)], buf)
            y = stage_fn(params, x_in)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # collect at the last stage
            outs = jnp.where(
                (s == S - 1) & valid,
                outs.at[jnp.clip(mb_idx, 0, M - 1)].set(y), outs)
            # shift rightward: stage s -> s+1 (ring; the wraparound value
            # lands in stage 0's buffer and is never read)
            buf = jax.lax.ppermute(y, axis,
                                   [(i, (i + 1) % S) for i in range(S)])
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # replicate the last stage's collected outputs to all stages
        outs = jax.lax.psum(
            jnp.where(s == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(per_stage, mesh=mesh,
                         in_specs=(pspec, P()), out_specs=P(),
                         check_vma=False)(stage_params, x)


def microbatch(x: Array, n_micro: int) -> Array:
    """(B, ...) -> (M, B/M, ...)."""
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    return x.reshape((n_micro, B // n_micro) + x.shape[1:])


def pipeline_loss_fn(stage_fn: Callable, loss_head: Callable,
                     mesh: Mesh, axis: str = "stage",
                     n_micro: int = 8):
    """Build a differentiable pipelined loss:
    loss = mean over microbatches of loss_head(pipeline(x), y)."""

    def loss(stage_params, batch_x, batch_y):
        xm = microbatch(batch_x, n_micro)
        ym = microbatch(batch_y, n_micro)
        out = pipeline_apply(stage_fn, stage_params, xm, mesh, axis)
        return jnp.mean(jax.vmap(loss_head)(out, ym))

    return loss
