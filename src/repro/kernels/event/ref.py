"""Plain oracle of the event-driven INTEG step: every entry of the table,
weighted by its neuron's spike, added into the arrival ring."""

from __future__ import annotations

import jax.numpy as jnp


def event_push_ref(ring, spikes, t, tables, *, shift: int, width: int,
                   cap: int):
    """ring (R, B*N) f32, spikes (B*N,) 0/1, t the step -> (ring, stats).

    stats (uint32[3]): spikes, synaptic events delivered, and 1 where the
    step's spiking neurons hold more than `cap` windows (a ranking push
    needs an extra pass)."""
    R, M = ring.shape
    N = tables.row_start.shape[0]
    B = M // N
    e = jnp.arange(tables.flat_pk.shape[0])
    # the last neuron whose row starts at or before the entry; the entry
    # is its if it lies before that row's end (else a spare one)
    owner = jnp.searchsorted(tables.row_start, e, side="right") - 1
    live = e < tables.row_start[owner] + tables.row_count[owner]
    s = spikes.reshape(B, N).astype(ring.dtype)
    wb = jnp.where(live[None], tables.flat_w[None] * s[:, owner], 0.0)
    slot = (t + (tables.flat_pk >> shift)) % R
    tgt = tables.flat_pk & ((1 << shift) - 1)
    b = jnp.arange(B)[:, None]
    out = ring.reshape(R, B, N).at[slot[None], b, tgt[None]].add(wb)
    fired = s > 0
    n = jnp.sum(fired, dtype=jnp.int32)
    events = jnp.sum(jnp.where(fired, tables.row_count[None, :], 0),
                     dtype=jnp.int32)
    segs = jnp.sum(jnp.where(fired, tables.seg_count[None, :], 0),
                   dtype=jnp.int32)
    stats = jnp.stack([n, events, (segs > cap).astype(jnp.int32)])
    return out.reshape(R, M), stats.astype(jnp.uint32)
