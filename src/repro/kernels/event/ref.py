"""Plain oracle of the event-driven INTEG step: every live lane of every
window of the table, weighted by the spike of the window's neuron, added
into the arrival ring."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def event_push_ref(ring, spikes, t, tables, *, shift: int, width: int,
                   cap: int):
    """ring (R*B*N,) f32, R slots of B*N one after another, spikes (B*N,)
    0/1, t the step -> (the ring in the same layout, stats).

    stats (uint32[3]): spikes, synaptic events delivered, and 1 where the
    step's spiking neurons hold more than `cap` windows (a ranking push
    needs an extra pass)."""
    M = spikes.shape[0]
    R = ring.shape[0] // M
    N = tables.win_start.shape[0]
    B = M // N
    win = jnp.arange(tables.windows.shape[0])
    # the last neuron whose windows start at or before this one; a lane
    # is its if it lies before that row's end (else padding or a spare)
    owner = jnp.searchsorted(tables.win_start, win, side="right") - 1
    nth = win - tables.win_start[owner]
    k = jnp.arange(width)
    live = (nth[:, None] * width + k[None, :]
            < tables.row_count[owner][:, None])
    pk = tables.windows[:, :width]
    w = jax.lax.bitcast_convert_type(tables.windows[:, width:], jnp.float32)
    s = spikes.reshape(B, N).astype(ring.dtype)
    wb = jnp.where(live[None], w[None] * s[:, owner, None], 0.0)
    slot = (t + (pk >> shift)) % R
    tgt = pk & ((1 << shift) - 1)
    b = jnp.arange(B)[:, None, None]
    out = ring.reshape(R, B, N).at[slot[None], b, tgt[None]].add(wb)
    fired = s > 0
    n = jnp.sum(fired, dtype=jnp.int32)
    events = jnp.sum(jnp.where(fired, tables.row_count[None, :], 0),
                     dtype=jnp.int32)
    segs = jnp.sum(jnp.where(fired, tables.seg_count[None, :], 0),
                   dtype=jnp.int32)
    stats = jnp.stack([n, events, (segs > cap).astype(jnp.int32)])
    return out.reshape(-1), stats.astype(jnp.uint32)
