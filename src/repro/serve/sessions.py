"""Stateful streaming sessions, the device-resident state pool, and the
LRU state cache behind the engine.

A `Session` is one tenant's streaming SNN run: an input buffer of
not-yet-executed timesteps, an output trail, and — held separately — the
persistent per-session neuron state that `plan.run` threads between
windows. That state lives in a `StatePool`: one device array per state
leaf, a row per session, read and written only inside the resident step,
so a cohort crosses the jit boundary as a row vector instead of one state
tree per slot (TaiBai keeps neuron state resident in the core and moves
only spikes). A learning session's synapse entries are rows of the pool
too, so each session keeps its own learned weights.

The `StateCache` is the multi-tenant memory story over the pool: hot
sessions keep their state in a pool row; once the hot set exceeds the
byte budget, the least-recently-used sessions are *spilled* to host
memory (`numpy` copies; the row is freed) and restored bit-identically on
readmission. Spill -> restore is a pure device<->host copy of every leaf
(no re-quantization, no re-init), so a session's trajectory is exactly
the same whether it stayed resident or bounced through the cache — the
invariant the isolation property tests pin down.

Byte accounting uses `plan.state_nbytes` of a pool row (synapse entries
included where the pool holds them).
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import (Any, Callable, Dict, Iterable, List, NamedTuple,
                    Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import state_batch_axis, state_nbytes
from repro.serve.metrics import ServeMetrics


@dataclasses.dataclass
class Session:
    """One streaming tenant: buffered input, output trail, lifecycle."""

    sid: str
    n_in: int
    chunks: List[np.ndarray] = dataclasses.field(default_factory=list)
    offset: int = 0                 # consumed steps inside chunks[0]
    buffered: int = 0               # total unconsumed timesteps
    closed: bool = False            # no more submits accepted
    finished: bool = False          # closed AND buffer drained
    windows: int = 0                # cohort windows served
    steps: int = 0                  # timesteps executed
    ready_ns: int = 0               # perf_counter_ns its next window
                                    # entered the ready queue
    outputs: List[np.ndarray] = dataclasses.field(default_factory=list)

    def push(self, chunk: np.ndarray) -> None:
        if self.closed:
            raise ValueError(f"session {self.sid!r} is closed")
        if chunk.ndim != 2 or chunk.shape[1] != self.n_in:
            raise ValueError(
                f"session {self.sid!r}: chunk shape {chunk.shape} != "
                f"(T, {self.n_in})")
        if len(chunk):
            self.chunks.append(np.asarray(chunk))
            self.buffered += len(chunk)

    def pop_window(self, window: int) -> Tuple[np.ndarray, int]:
        """Next `window` timesteps, zero-padded at stream end.

        Returns (x (window, n_in), valid) where `valid` is the number of
        real (unpadded) steps. Padding only ever happens on the final
        partial window of a *closed* stream, so padded state never feeds a
        later real step.
        """
        take = min(window, self.buffered)
        parts: List[np.ndarray] = []
        got = 0
        while got < take:
            head = self.chunks[0]
            n = min(take - got, len(head) - self.offset)
            parts.append(head[self.offset:self.offset + n])
            got += n
            self.offset += n
            if self.offset == len(head):
                self.chunks.pop(0)
                self.offset = 0
        self.buffered -= take
        x = (np.concatenate(parts, axis=0) if parts
             else np.zeros((0, self.n_in), np.float32))
        if take < window:
            x = np.concatenate(
                [x, np.zeros((window - take, self.n_in), x.dtype)], axis=0)
        return x, take

    def ready(self, window: int) -> bool:
        """Schedulable: a full window buffered, or a closed partial tail."""
        if self.finished:
            return False
        return self.buffered >= window or (self.closed and self.buffered > 0)


# ---------------------------------------------------------------------------
# the state pool
# ---------------------------------------------------------------------------

SCRATCH, INIT = 0, 1        # reserved pool rows: padded slots' writes, and
                            # the initial state a fresh session reads


class Row(NamedTuple):
    """A session's place in the pool: its row, and whether it has yet to
    run (a fresh session's row holds stale data; the step reads INIT)."""

    index: int
    fresh: bool


def _map(fn: Callable, tree: Dict[str, Any], *rest: Dict[str, Any]
         ) -> Dict[str, Any]:
    """`fn(batch_axis, leaf, *same leaf of rest)` over a {node: {key: leaf}}
    state tree; the leaves of a synapse entry have batch axis None."""
    def entry(ax, v, *r):
        if ax is None:
            return jax.tree_util.tree_map(functools.partial(fn, None), v, *r)
        return fn(ax, v, *r)
    return {node: {k: entry(state_batch_axis(k), v,
                            *(r[node][k] for r in rest))
                   for k, v in nd.items()}
            for node, nd in tree.items()}


def _row_axis(ax: Optional[int]) -> int:
    """The pool's row axis for a leaf of batch axis `ax`: that axis, or a
    leading axis the pool adds to a leaf with none (synapse entries)."""
    return 0 if ax is None else ax


def _as_row(ax: Optional[int], v):
    """A one-session leaf shaped as one pool row."""
    return v[None] if ax is None else v


def gather_rows(leaves: Dict[str, Any], rows: jax.Array,
                fresh: jax.Array) -> Dict[str, Any]:
    """Inside the step: the cohort state, slot k taken from pool row
    `rows[k]`, or from INIT where `fresh[k]`."""
    src = jnp.where(fresh, INIT, rows)
    # rows come from the free-list, so always in bounds: "clip" gathers
    # with no out-of-bounds mask
    return _map(lambda ax, v: jnp.take(v, src, axis=_row_axis(ax),
                                       mode="clip"), leaves)


def scatter_rows(leaves: Dict[str, Any], rows: jax.Array,
                 state: Dict[str, Any]) -> Dict[str, Any]:
    """Inside the step: the pool with slot k of `state` written to row
    `rows[k]` (padded slots all write SCRATCH)."""
    return _map(lambda ax, v, s: (v.at[rows] if _row_axis(ax) == 0
                                  else v.at[:, rows]).set(s), leaves, state)


def vmap_slots(fn: Callable, state: Dict[str, Any], x: jax.Array):
    """Inside the step: `fn(session state, x_k)`, with a batch of 1 and
    x_k (W, 1, n_in), run once per slot k of a cohort `state` (as
    `gather_rows` returns it) and of its input x (W, C, n_in); returns
    the cohort's new state and its (W, C, n_out) output. The slots run as
    independent `vmap` lanes, so each keeps its own synapse entries."""
    axes = _map(lambda ax, v: _row_axis(ax), state)

    def one(st, x_k):
        st = _map(lambda ax, v: v if ax is None else jnp.expand_dims(v, ax),
                  st)
        ns, out = fn(st, x_k[:, None])
        return _map(lambda ax, v: v if ax is None else jnp.squeeze(v, ax),
                    ns), out[:, 0]
    return jax.vmap(one, in_axes=(axes, 1), out_axes=(axes, 1))(state, x)


class StatePool:
    """The device-resident state of every hot session (module docstring).

    Built from a one-session state tree `init` (batch axis of size 1):
    each leaf becomes one array whose row axis (`_row_axis`) holds `rows`
    rows, every row a copy of `init`. The host free-list hands rows out
    (`claim`) and takes them back (`free`); rows SCRATCH and INIT are
    never handed out. The pool starts at the power of two at or above
    4 x `capacity` rows and doubles whenever the free-list runs dry; the
    step traces anew once per size (`pool_grows` counts them).
    """

    def __init__(self, init: Dict[str, Any], capacity: int,
                 metrics: Optional[ServeMetrics] = None):
        self.metrics = metrics or ServeMetrics()
        self.row_bytes = state_nbytes(init)
        self.rows = 1 << max(2, (4 * capacity - 1).bit_length())
        self.leaves = _map(lambda ax, v: jnp.repeat(
            _as_row(ax, v), self.rows, axis=_row_axis(ax)), init)
        self._free = list(range(self.rows - 1, INIT, -1))

    def claim(self) -> int:
        if not self._free:
            self._grow()
        return self._free.pop()

    def _grow(self) -> None:
        def doubled(ax, v):
            # built on the host: an eager device concatenate would compile
            # anew for each leaf's new shape
            h = np.asarray(v)
            return jnp.asarray(np.concatenate([h, np.zeros_like(h)],
                                              axis=_row_axis(ax)))

        old = self.rows
        self.leaves = _map(doubled, self.leaves)
        self.rows = 2 * old
        self._free = list(range(self.rows - 1, old - 1, -1))
        self.metrics.bump("pool_grows")

    def read(self, row: Row) -> Dict[str, Any]:
        """The session's one-row state tree (INIT's if it is fresh)."""
        i = INIT if row.fresh else row.index
        return _map(lambda ax, v: v[i] if ax is None
                    else jax.lax.slice_in_dim(v, i, i + 1, axis=ax),
                    self.leaves)

    def write(self, index: int, state: Dict[str, Any]) -> None:
        """Put a one-row state tree (device or host) into row `index`."""
        self.leaves = _map(
            lambda ax, v, s: jax.lax.dynamic_update_slice_in_dim(
                v, _as_row(ax, jnp.asarray(s, v.dtype)), index,
                axis=_row_axis(ax)),
            self.leaves, state)

    # what the StateCache asks of its store: a hot value is a Row ---------

    def spill(self, row: Row) -> Optional[Dict[str, Any]]:
        """Copy the row to host and free it (a fresh row copies nothing)."""
        host = (None if row.fresh
                else jax.tree_util.tree_map(np.asarray, self.read(row)))
        self.free(row)
        return host

    def restore(self, host: Optional[Dict[str, Any]]) -> Row:
        index = self.claim()
        if host is None:
            return Row(index, True)
        self.write(index, host)
        return Row(index, False)

    def free(self, row: Row) -> None:
        self._free.append(row.index)


# ---------------------------------------------------------------------------
# the LRU cache
# ---------------------------------------------------------------------------


class StateCache:
    """LRU session-state cache with a hot-set byte budget.

    `put`/`get` move states in and out keyed by session id; every access
    refreshes recency. A hot state is a `Row` of the `store`, a
    `StatePool`, and costs its `row_bytes`. When hot bytes exceed
    `budget_bytes`, the least-recently-used entries spill to host
    (`numpy`) until the budget holds again — `get` of a spilled entry
    restores it to device bit-identically and counts a miss+restore.
    `budget_bytes=None` means unbounded (nothing ever spills).
    """

    def __init__(self, store: StatePool,
                 budget_bytes: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None):
        if budget_bytes is not None and budget_bytes <= 0:
            raise ValueError(f"budget_bytes must be positive or None, "
                             f"got {budget_bytes}")
        self.budget_bytes = budget_bytes
        self.metrics = metrics or ServeMetrics()
        self.store = store
        # sid -> (row or host copy, spilled?); insertion order = recency
        self._entries: "OrderedDict[str, Tuple[Any, bool]]" = OrderedDict()

    # -- introspection ------------------------------------------------------

    def __contains__(self, sid: str) -> bool:
        return sid in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def hot_bytes(self) -> int:
        return self.store.row_bytes * sum(
            not spilled for _, spilled in self._entries.values())

    @property
    def spilled(self) -> Tuple[str, ...]:
        return tuple(sid for sid, (_, sp) in self._entries.items() if sp)

    def is_spilled(self, sid: str) -> bool:
        return self._entries[sid][1]

    # -- core ---------------------------------------------------------------

    def put(self, sid: str, state: Any) -> None:
        """Insert/replace a session's state (hot) and enforce the budget."""
        self.put_many([(sid, state)])

    def put_many(self, items: Iterable[Tuple[str, Any]]) -> None:
        """Insert/replace states (hot), then enforce the budget once, the
        last one exempt."""
        sid = None
        for sid, state in items:
            self._entries.pop(sid, None)
            self._entries[sid] = (state, False)
        self._enforce(keep=(sid,))

    def get(self, sid: str) -> Any:
        """Fetch a session's state onto device, refreshing recency."""
        return self.get_many([sid])[0]

    def get_many(self, sids: Sequence[str]) -> List[Any]:
        """Fetch states onto device, refreshing recency; none of them
        spills before the next call (a pool row stays the session's)."""
        out = []
        for sid in sids:
            state, spilled = self._entries.pop(sid)
            if spilled:
                state = self.store.restore(state)
                self.metrics.bump("cache_misses")
                self.metrics.bump("cache_restores")
            else:
                self.metrics.bump("cache_hits")
            self._entries[sid] = (state, False)
            out.append(state)
        self._enforce(keep=sids)
        return out

    def drop(self, sid: str) -> None:
        entry = self._entries.pop(sid, None)
        if entry is not None and not entry[1]:
            self.store.free(entry[0])

    def _enforce(self, keep: Sequence[Optional[str]] = ()) -> None:
        """Spill LRU-first until hot bytes fit the budget. The `keep`
        entries (the sessions about to run / just scattered) are exempt so
        a budget smaller than one session still serves — it just spills
        everything else."""
        if self.budget_bytes is None:
            return
        hot = self.hot_bytes
        if hot <= self.budget_bytes:
            return
        for sid in list(self._entries):
            if hot <= self.budget_bytes:
                break
            state, spilled = self._entries[sid]
            if spilled or sid in keep:
                continue
            self._entries[sid] = (self.store.spill(state), True)
            hot -= self.store.row_bytes
            self.metrics.bump("cache_evictions")


__all__ = ["Session", "Row", "StatePool", "StateCache", "SCRATCH", "INIT",
           "gather_rows", "scatter_rows", "vmap_slots"]
