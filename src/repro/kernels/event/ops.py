"""Event-driven INTEG: push the fan-out of this step's spikes into a ring
of arriving currents (ROADMAP Reach 3).

A group of populations joined by `FanOut` edges (a delay per synapse,
`core/topology.py`) is laid out once as one table over the group's
neurons (`build_event_tables`, on the host): neuron j's synapses, its
edges' live entries one after another, fill the `seg_count[j]` rows from
`win_start[j]` of a table of windows, `width` entries a row. A row holds
lanes `[0, width)` packed `(delay << shift) | target` and lanes
`[width, 2 * width)` the float32 weights' bits; a neuron's last window
is padded to the row's end with inert entries (weight 0), and spare
rows of them end the table, which holds `ceil(entries / width) + N`
rows whatever the rows' lengths. A step then costs what its spikes fan out
to, not what the table holds:

  1. rank: every spiking neuron contributes its windows; a cumulative
     sum over the neurons ranks them, and a pass takes the next `cap`
     windows (window r belongs to the neuron whose cumulative count
     first exceeds r: the count of cumulative sums <= r);
  2. gather those windows: one row gather of the table;
  3. scatter-add their weights into the ring at slot (t + delay) mod R.

The ring is one flat float32 array of R * B * N entries, slot after slot,
each slot a batch row after another: the push takes and returns it in
that layout, and its caller (`core/plan.py`, the EVENT segment) keeps it
so through every step of a chunk. On a TPU a 3-D (R, B, N) ring and a
flat one are tiled differently, so each reshape between them would copy
the whole ring.

Scattering is what the chip does slowest here (an XLA scatter on a TPU
v5e takes ~9 ns an update, PERF.md), so a spike scatters its own synapses
and at most one cut window. A step with more than `cap` windows takes
further passes, never drops a spike, and counts itself in `stats[2]`.
The whole push is one jitted function, `event_push_xla`: its device work
runs under the name scope `event_push`, and its only scatter is the push
into the ring.

The plain oracle (`ref.py`) pushes every live lane of every window
weighted by its neuron's spike. Dispatch follows the registry: the
ranking push is this family's accelerator implementation (XLA
operations, no Pallas kernel), the oracle runs where the registry picks
the reference.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry
from repro.kernels.event.ref import event_push_ref

Array = jax.Array

SEGMENT = 512              # entries a window (a pass gathers whole windows)


class EventTables(NamedTuple):
    """A group's fan-out table of windows (see the module docstring)."""

    win_start: Array     # (N,) int32: neuron j's first window (row)
    row_count: Array     # (N,) int32: its synapses
    seg_count: Array     # (N,) int32: its windows, ceil(row_count / width)
    windows: Array       # (ceil(entries / width) + N, 2 * width) int32


class GroupTables(NamedTuple):
    """`EventTables` with their static layout: `shift` (target bits) and
    `width` (entries a window). A pytree whose statics are aux data, so
    it rides through jit as one argument."""

    tables: EventTables
    shift: int
    width: int


jax.tree_util.register_pytree_node(
    GroupTables, lambda g: ((g.tables,), (g.shift, g.width)),
    lambda aux, ch: GroupTables(ch[0], *aux))


def target_bits(n: int) -> int:
    return max(1, (max(n, 2) - 1).bit_length())


def _source_rows(edges, shift: int):
    """One population's synapses as host arrays, row after row, each row
    its edges' live entries in edge order: (packed, weights, counts)."""
    pks, ws, lives = [], [], []
    for topo, off in edges:
        t, w, d, c = (np.asarray(a) for a in (topo.targets, topo.weights,
                                              topo.delays, topo.counts))
        lives.append(np.arange(t.shape[1])[None, :] < c[:, None])
        pks.append((d.astype(np.int32) << shift)
                   | (t.astype(np.int32) + np.int32(off)))
        ws.append(w.astype(np.float32, copy=False))
    live = np.concatenate(lives, axis=1)
    return (np.concatenate(pks, axis=1)[live],
            np.concatenate(ws, axis=1)[live],
            live.sum(axis=1, dtype=np.int32))


def build_event_tables(sources: Sequence[Tuple[int, Sequence]],
                       n_total: int, width: int = SEGMENT) -> GroupTables:
    """The table of windows of a group of `n_total` neurons.

    sources: one (rows, [(FanOut, target offset), ...]) per population in
    the group's order, its rows the population's neurons and its edges
    every FanOut leaving it (targets relative to the target population,
    placed at the offset). Built on the host from the edges' rows (read
    back once) and put on the device."""
    shift = target_bits(n_total)
    longest = max((topo.max_delay for _, edges in sources
                   for topo, _ in edges), default=1)
    if longest >= 1 << (31 - shift):
        raise ValueError(f"delay {longest} does not fit beside {shift} "
                         "target bits in an int32")
    rows = [_source_rows(edges, shift) if edges
            else (None, None, np.zeros(n, np.int32)) for n, edges in sources]
    cnt = np.concatenate([r[2] for r in rows])
    segs = -(-cnt // width)
    start = np.concatenate([[0], np.cumsum(segs, dtype=np.int64)])
    # more rows than any layout of these entries over these neurons takes
    # (each neuron pads less than one window), so the table's shape, and
    # the compiled push, follow the group's sizes, not how a draw spread
    # its synapses; a new draw of the same sizes compiles nothing anew
    n_rows = -(-int(cnt.sum(dtype=np.int64)) // width) + cnt.shape[0]
    if n_rows >= 1 << 31:
        raise ValueError(f"{n_rows} windows do not fit int32 rows")
    # padding lanes, and the spare rows a pass reads past its last
    # window, deliver nothing: weight 0 at delay 1 to neuron 0
    table = np.zeros((n_rows, 2 * width), np.int32)
    table[:, :width] = 1 << shift
    k = np.arange(width)
    lo = 0
    for pk, w, c in rows:
        hi = lo + c.shape[0]
        if pk is not None:
            a, b = int(start[lo]), int(start[hi])
            owner = np.repeat(np.arange(lo, hi), segs[lo:hi])
            nth = np.arange(a, b) - start[owner]
            live = nth[:, None] * width + k[None, :] < cnt[owner][:, None]
            table[a:b, :width][live] = pk
            table[a:b, width:][live] = w.view(np.int32)
        lo = hi
    tables = EventTables(jnp.asarray(start[:-1].astype(np.int32)),
                         jnp.asarray(cnt), jnp.asarray(segs.astype(np.int32)),
                         jnp.asarray(table))
    return GroupTables(tables, shift, width)


@functools.partial(jax.jit, static_argnames=("shift", "width", "cap"))
def event_push_xla(ring, spikes, t, tables, *, shift: int, width: int,
                   cap: int):
    """The ranking push (see the module docstring)."""
    M = spikes.shape[0]
    R = ring.shape[0] // M
    N = tables.win_start.shape[0]
    B = M // N
    spare = tables.windows.shape[0] - 1
    with jax.named_scope("event_push"):
        s = spikes > 0
        segs = jnp.where(s, jnp.tile(tables.seg_count, B), 0)
        csum = jnp.cumsum(segs, dtype=jnp.int32)
        total = csum[-1]
        events = jnp.sum(jnp.where(s, jnp.tile(tables.row_count, B), 0),
                         dtype=jnp.int32)
        k = jnp.arange(width, dtype=jnp.int32)
        lanes = jnp.arange(cap, dtype=jnp.int32)
        mask = (1 << shift) - 1

        def one_pass(carry):
            p, flat = carry
            r = p * cap + lanes                              # window ranks
            m = jnp.sum(csum[None, :] <= r[:, None], axis=1,
                        dtype=jnp.int32)
            valid = r < total
            m = jnp.where(valid, m, 0)
            j, b = m % N, m // N
            nth = r - (csum[m] - segs[m])                    # of j's windows
            first = jnp.where(valid, tables.win_start[j] + nth, spare)
            win = tables.windows[first]                      # (cap, 2 width)
            pk = win[:, :width]
            w = jax.lax.bitcast_convert_type(win[:, width:], jnp.float32)
            cut = tables.row_count[j] - nth * width      # the row's end
            live = valid[:, None] & (k[None, :] < cut[:, None])
            slot = (t + (pk >> shift)) % R
            dst = jnp.where(live, slot * M + b[:, None] * N + (pk & mask),
                            R * M)
            flat = flat.at[dst.reshape(-1)].add(w.reshape(-1), mode="drop")
            return p + 1, flat

        passes = (total + cap - 1) // cap
        _, flat = jax.lax.while_loop(lambda c: c[0] < passes, one_pass,
                                     (jnp.int32(0), ring))
        stats = jnp.stack([jnp.sum(s, dtype=jnp.int32), events,
                           (passes > 1).astype(jnp.int32)])
    return flat, stats.astype(jnp.uint32)


def _pallas_impl(ring, spikes, t, tables, *, blocks, interpret, shift,
                 width, cap):
    del blocks, interpret        # XLA operations: nothing to tile
    return event_push_xla(ring, spikes, t, tables, shift=shift, width=width,
                          cap=cap)


def _ref_impl(ring, spikes, t, tables, *, shift, width, cap):
    return event_push_ref(ring, spikes, t, tables, shift=shift, width=width,
                          cap=cap)


def event_push(ring: Array, spikes: Array, t, tables: EventTables, *,
               shift: int, width: int, cap: int = 0, force: bool = False
               ) -> Tuple[Array, Array]:
    """One step's push of `spikes` (B*N,) into the flat ring (R*B*N,):
    (the ring in the same flat layout, stats uint32[3]: spikes, synaptic
    events, 1 where the step's windows took more than one pass). R is
    `ring.size // spikes.size`. `cap` (windows a pass) 0 takes the
    registry's block (tuned, else 32)."""
    if not cap:
        spec = registry.get("event_push")
        cap = spec.resolve_blocks(spec.dims_of(ring, spikes, t, tables))[
            "cap"]
    return registry.dispatch("event_push", (ring, spikes, t, tables),
                             force_pallas=force, shift=shift, width=width,
                             cap=int(cap))


def _make_inputs(key):
    """A 3-population group of 300 neurons, batch 2, ring of 8, windows
    of 8 entries: a burst of ~40 spikes per batch row, past a cap of 16
    windows, so passes repeat."""
    from repro.core.topology import encode_fanout
    rng = np.random.default_rng(0)
    sizes = (120, 100, 80)
    base = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    sources = []
    for p, n_p in enumerate(sizes):
        edges = []
        for q, n_q in enumerate(sizes):
            m = int(rng.integers(200, 900))
            topo = encode_fanout(rng.integers(0, n_p, m),
                                 rng.integers(0, n_q, m),
                                 rng.normal(size=m).astype(np.float32),
                                 rng.integers(1, 8, m), n_p, n_q)
            edges.append((topo, int(base[q])))
        sources.append((n_p, edges))
    g = build_event_tables(sources, sum(sizes), width=8)
    N, B, R = sum(sizes), 2, 8
    k1, k2 = jax.random.split(key)
    ring = jax.random.normal(k1, (R * B * N,), jnp.float32)
    spikes = (jax.random.uniform(k2, (B * N,)) < 0.13).astype(jnp.float32)
    return ring, spikes, jnp.int32(5), g.tables, g.shift, g.width


def _apply(args, force=False):
    ring, spikes, t, tables, shift, width = args
    return event_push(ring, spikes, t, tables, shift=shift, width=width,
                      cap=16, force=force)


registry.register(registry.KernelSpec(
    name="event_push",
    ref=_ref_impl,
    pallas=_pallas_impl,
    apply=_apply,
    # the static number of windows a pass scatters: a larger cap pays for
    # more padded slots on a quiet step, a smaller one for more passes
    block_axes=(registry.BlockAxis("cap", "M", preferred=32, align=8),),
    dims_of=lambda ring, spikes, t, tables, *rest: {
        "R": ring.shape[0] // spikes.shape[0], "M": spikes.shape[0],
        "N": tables.win_start.shape[0], "RM": ring.shape[0]},
    candidates=({"cap": 16}, {"cap": 32}, {"cap": 64}),
    make_inputs=_make_inputs,
    diff_argnums=(),
    tol=1e-4,
    # not a Pallas kernel (XLA operations hold nothing in VMEM by this
    # model): the pass's gathered windows, target and weight per slot
    vmem_bytes=lambda dims, b: 4 * b["cap"] * 2 * SEGMENT,
    # one pass updates the whole flat ring in place (no grid)
    tile_model=registry.TileModel(
        out=(("RM", None),),
        tiles=lambda dims, b: {"windows": (b["cap"], 2 * SEGMENT)}),
))
