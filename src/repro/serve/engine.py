"""Multi-tenant streaming serve engine over one resident `plan.run` step.

TaiBai amortizes one resident program across many concurrent spike
streams, keeping neuron state in the core and moving only spikes; this is
the software analogue. ONE compiled plan — jitted once per (window,
capacity, pool size) shape — serves every open session. Every hot
session's state lives in a device-resident `StatePool` (one array per
state leaf, a row per session); on each window boundary the scheduler
packs whichever sessions have a runnable window into fixed cohort slots,
the engine resolves them to pool rows through the LRU cache, and the
resident step gathers those rows (fresh sessions read the initial-state
row), runs `plan.run`, and scatters the new state back into the pool,
which it takes donated and returns. Only the rows, a fresh mask, the
input block and the output cross the call. Nothing retraces unless the
pool grows: free slots point at a scratch row and their results are
discarded.

Two engines share the scheduler/cache/metrics machinery:

  * `BatchedEngine` — the continuous-batching engine. Inference cohorts
    run the *flat* path (sessions concatenated along the batch axis, the
    MXU-shaped layout). With `learn=True` on a plastic model, cohorts run
    a per-session-`vmap`ped window instead: synapse weight planes have no
    batch axis, so the flat path would batch-sum every tenant's update
    into one tile — the vmap path keeps each session's learned weights in
    its own state (entry weights come from the session's last published
    `syn:` tensors via `plasticity.apply_learned`, the chunked-online
    contract, per lane).
    That path keeps a tuple of per-session state trees through its step
    (and the cache keeps the trees); it shares no code with the pool.
  * `NaiveEngine` — the one-session-at-a-time baseline: same scheduler,
    same cache and pool, same semantics, but every served session pays
    its own one-slot window launch. `bench_serving` measures the gap.

Isolation invariant (property-tested): a session's output trajectory and
final state are bit-identical whether it runs alone, interleaved with
strangers, or is evicted to host and restored mid-stream. The flat path
earns this because every per-slot computation in the fused kernels is
row-independent and the executable is shape-fixed (solo and packed
cohorts run the *same* compiled step, whichever pool rows they hold);
the vmap path because lanes are independent by construction;
evict/restore because spill is a pure device<->host copy.

Resilience composes: kernel dispatch inside the resident step follows
the registry's failure policy (off-TPU an interpret-mode failure degrades
to the reference and is recorded; compiled kernels never degrade),
`REPRO_FAULTS` / `REPRO_GUARD` thread through
`plan.run` unchanged. The step cache keys on the ambient
faults/engine/dispatch environment, so entering a fault context retraces
instead of silently replaying a clean executable.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events, faults, plasticity
from repro.core import plan as plan_mod
from repro.serve.metrics import ServeMetrics, span
from repro.serve.scheduler import Scheduler
from repro.serve.sessions import (SCRATCH, Row, Session, StateCache,
                                  StatePool, gather_rows, scatter_rows)

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs for the streaming engines.

    window:      scheduling quantum in timesteps (the chunked-online
                 window `plan.run` state round-trips at).
    capacity:    cohort slots — max sessions per window step.
    queue_limit: admission bound, in buffered-but-unserved windows summed
                 over all sessions; a submit that would exceed it is
                 rejected (backpressure). None = unbounded.
    cache_bytes: hot-state byte budget for the LRU cache; LRU sessions
                 spill to host beyond it. None = unbounded.
    learn:       run per-session on-chip plasticity (the `learn=` path of
                 `plan.run`, vmapped per session for isolation).
    guard:       numerical guardrail policy for `plan.run` (None defers
                 to REPRO_GUARD).
    """

    window: int = 32
    capacity: int = 8
    queue_limit: Optional[int] = 256
    cache_bytes: Optional[int] = None
    learn: bool = False
    guard: Optional[str] = None

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")


# ---------------------------------------------------------------------------
# resident step cache
# ---------------------------------------------------------------------------
#
# Jitted window steps are cached per (nodes, path kind, guard, ambient
# environment). Keys hold id()s of the live node objects — the closures
# keep those objects alive, so ids cannot be recycled into a collision.
# The environment fingerprint (engine mode, dispatch pins, active fault
# spec) is part of the key because `plan.run` resolves all of those at
# TRACE time: a cached clean-world executable must not be replayed inside
# a `faults.inject(...)` context.

_STEP_CACHE: Dict[tuple, Callable] = {}


def _env_fingerprint(guard: Optional[str]) -> tuple:
    return (plan_mod.engine_mode(),
            os.environ.get("REPRO_KERNEL_IMPL"),
            os.environ.get("REPRO_PALLAS_INTERPRET"),
            os.environ.get("REPRO_SPIKEMM_SPARSE"),
            guard if guard is not None else os.environ.get("REPRO_GUARD"),
            faults.active())


def _resident_step(nodes, compiled, kind: str,
                   guard: Optional[str]) -> Callable:
    key = (tuple(id(n) for n in nodes), kind, _env_fingerprint(guard))
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    nodes = list(nodes)

    # "flat" takes the state pool (donated: updated in place) and the
    # cohort's rows; its argument count does not grow with the capacity.
    # "vmap_learn" takes a TUPLE of per-session state trees and returns a
    # tuple of per-session results, stacked and sliced inside the program.
    if kind == "flat":
        def step(params, pool, rows, fresh, x):
            with jax.named_scope("pack"):
                state = gather_rows(pool, rows, fresh)
            ns, out, _ = plan_mod.run(nodes, params, x, state=state,
                                      plan=compiled, learn=False,
                                      guard=guard)
            with jax.named_scope("unpack"):
                return scatter_rows(pool, rows, ns), out
        fn = jax.jit(step, donate_argnums=1)
    elif kind == "vmap_learn":
        def step(params, states, x):
            with jax.named_scope("pack"):
                st = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                            *states)

            def one(st_i, x_i):
                # chunked-online entry weights = the session's last
                # published learned tensors; fresh sessions carry seeds
                p = plasticity.apply_learned(nodes, params, st_i)
                ns, out, _ = plan_mod.run(nodes, p, x_i, state=st_i,
                                          plan=compiled, learn=True,
                                          guard=guard)
                return ns, out
            ns, out = jax.vmap(one)(st, x)
            with jax.named_scope("unpack"):
                return tuple(jax.tree_util.tree_map(lambda l, i=i: l[i], ns)
                             for i in range(len(states))), out
        fn = jax.jit(step)
    else:  # pragma: no cover
        raise ValueError(f"unknown step kind {kind!r}")
    _STEP_CACHE[key] = fn
    return fn


def _split_syn(state: Dict[str, Any]
               ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Split a session state into (packable core, per-session syn tree)."""
    core: Dict[str, Any] = {}
    syn: Dict[str, Any] = {}
    for node, nd in state.items():
        core[node] = {k: v for k, v in nd.items()
                      if not k.startswith("syn:")}
        s = {k: v for k, v in nd.items() if k.startswith("syn:")}
        if s:
            syn[node] = s
    return core, syn


def _merge_syn(core: Dict[str, Any], syn: Dict[str, Any]) -> Dict[str, Any]:
    out = {node: dict(nd) for node, nd in core.items()}
    for node, s in syn.items():
        out[node].update(s)
    return out


def _pool_operands(handles: List[Row], slots: int):
    """The step's row and fresh vectors: session k in slot k, the free
    slots on the scratch row, fresh (they read the initial state)."""
    rows = np.full(slots, SCRATCH, np.int32)
    fresh = np.ones(slots, bool)
    for k, r in enumerate(handles):
        rows[k], fresh[k] = r
    return rows, fresh


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------


class BatchedEngine:
    """Continuous-batching multi-tenant engine (see module docstring)."""

    kind = "batched"

    def __init__(self, nodes: List[events.LayerNode], params: Dict[str, Any],
                 cfg: EngineConfig = EngineConfig(),
                 plan: Optional[plan_mod.Plan] = None,
                 dtype=jnp.float32):
        self.nodes = list(nodes)
        self.params = params
        self.cfg = cfg
        self.plan = plan if plan is not None \
            else plan_mod.compile_program(self.nodes)
        self.dtype = events.state_dtype(dtype)
        self.n_in = self._infer_n_in()
        self.n_out = self.nodes[-1].out_dim
        self.metrics = ServeMetrics()
        self.scheduler = Scheduler(cfg.window, self.n_in,
                                   queue_limit=cfg.queue_limit,
                                   metrics=self.metrics)
        self._learn = cfg.learn and bool(self.plan.plastic)
        self._sid_counter = 0
        self._step_id = -1          # index of the current (or last) step()
        init = events.init_state(self.nodes, 1, self.dtype, params)
        if self._learn:
            # each session's tree lives in the cache; free cohort slots
            # are zero-padded (results discarded)
            self.pool = None
            self._zero_full = jax.tree_util.tree_map(jnp.zeros_like, init)
        else:
            # synapse entries never change on the flat path: held once
            core, self._syn = _split_syn(init)
            self.pool = StatePool(core, cfg.capacity, metrics=self.metrics)
        self.cache = StateCache(cfg.cache_bytes, metrics=self.metrics,
                                store=self.pool)

    def _infer_n_in(self) -> int:
        for n in self.nodes:
            for c in n.connections:
                if c.src == "input":
                    topo = events.resolve_topology(c, n.name, self.params)
                    w = (topo if topo is not None
                         else self.params[n.name][c.weight_key])
                    return int(w.shape[-2])
        raise ValueError("no node reads 'input'; cannot infer n_in")

    # -- lifecycle ----------------------------------------------------------

    def open(self, sid: Optional[str] = None) -> str:
        """Open a streaming session with fresh state; returns its id."""
        if sid is None:
            sid = f"s{self._sid_counter}"
            self._sid_counter += 1
        with span("serve.open", sid=sid):
            self.scheduler.open(sid)
            if self.pool is None:
                state = events.init_state(self.nodes, 1, self.dtype,
                                          self.params)
            else:
                state = Row(self.pool.claim(), True)
            self.cache.put(sid, state)
        return sid

    def submit(self, sid: str, chunk: np.ndarray) -> bool:
        """Buffer (T, n_in) input steps; False = backpressure (rejected)."""
        return self.scheduler.submit(sid, chunk)

    def close(self, sid: str) -> None:
        """End of stream: remaining buffered steps still run (the final
        partial window is zero-padded and its outputs trimmed)."""
        self.scheduler.close(sid)

    def finished(self, sid: str) -> bool:
        return self.scheduler.sessions[sid].finished

    def outputs(self, sid: str) -> np.ndarray:
        """All output steps produced so far, (steps, n_out)."""
        s = self.scheduler.sessions[sid]
        if not s.outputs:
            return np.zeros((0, self.n_out), np.float32)
        return np.concatenate(s.outputs, axis=0)

    def state_of(self, sid: str) -> Dict[str, Any]:
        """The session's current state tree (restored to device)."""
        state = self.cache.get(sid)
        if self.pool is None:
            return state
        return _merge_syn(self.pool.read(state), self._syn)

    def retire(self, sid: str) -> np.ndarray:
        """Drop a finished (or abandoned) session, freeing its state (its
        pool row); returns its outputs."""
        out = self.outputs(sid)
        self.cache.drop(sid)
        self.scheduler.sessions.pop(sid, None)
        return out

    # -- execution ----------------------------------------------------------

    def step(self) -> int:
        """Run one cohort window; returns the number of sessions served.

        Spanned (`repro.serve.metrics.span`) as `serve.step`, with the
        children `serve.schedule`, `serve.gather`, `serve.assemble`,
        `serve.dispatch`, `serve.readback` and `serve.scatter` in that
        order, all carrying the step's index as `step`."""
        self._step_id += 1
        i, C = self._step_id, self.cfg.capacity
        with span("serve.step", step=i) as sp:
            self.metrics.queue_depth.observe(self.scheduler.ready_count)
            with span("serve.schedule", step=i):
                cohort = self.scheduler.next_cohort(C)
            sp.ids.update(sessions=len(cohort), slots=C,
                          steps=sum(v for _, _, v in cohort))
            if cohort:
                sids = [s.sid for s, _, _ in cohort]
                with span("serve.gather", step=i):
                    states = self.cache.get_many(sids)
                new_states, outs = self._run_cohort(cohort, states)
                with span("serve.scatter", step=i):
                    self.cache.put_many(zip(sids, new_states))
                    for (s, _, valid), out in zip(cohort, outs):
                        s.outputs.append(np.asarray(out[:valid]))
                    self.metrics.bump("windows_run")
                    self.metrics.bump("session_windows", len(cohort))
                    self.metrics.bump("steps_run", sp.ids["steps"])
                    # the replaced states' device buffers (learning path)
                    # are released here, so the span times their release
                    del states, new_states, outs
        if not cohort:
            return 0
        self.metrics.window_latency_s.observe(sp.seconds)
        self.metrics.occupancy.observe(len(cohort) / C)
        return len(cohort)

    def drain(self) -> int:
        """Step until no session is schedulable; returns windows run."""
        n = 0
        while self.step():
            n += 1
        return n

    def stats(self) -> Dict[str, Any]:
        snap = self.metrics.snapshot()
        snap.update(engine=self.kind, window=self.cfg.window,
                    capacity=self.cfg.capacity,
                    cache_hot_bytes=self.cache.hot_bytes,
                    cache_spilled=len(self.cache.spilled),
                    pool_rows=self.pool.rows if self.pool else 0,
                    sessions_open=len(self.scheduler.sessions))
        return snap

    # cohort execution — the part engines differ in ------------------------

    def _run_cohort(self, cohort: List[Tuple[Session, np.ndarray, int]],
                    states: List[Any]) -> Tuple[List[Any], List[np.ndarray]]:
        """One resident-step launch for the cohort: (new states, outputs),
        one per session. A state is the session's pool `Row`, or on the
        learning path its tree."""
        if self._learn:
            return self._run_learn(cohort, states)
        C, W, i = self.cfg.capacity, self.cfg.window, self._step_id
        n_live = len(cohort)
        with span("serve.assemble", step=i):
            rows, fresh = _pool_operands(states, C)
            self.metrics.bump("fresh_resets", int(fresh[:n_live].sum()))
            # sessions concatenated along the batch axis
            x = np.zeros((W, C, self.n_in), self.dtype)
            for k, (_, xw, _) in enumerate(cohort):
                x[:, k, :] = xw
            rows, fresh, x = jnp.asarray(rows), jnp.asarray(fresh), \
                jnp.asarray(x)
        with span("serve.dispatch", step=i):
            step = _resident_step(self.nodes, self.plan, "flat",
                                  self.cfg.guard)
            self.pool.leaves, out = step(self.params, self.pool.leaves, rows,
                                         fresh, x)
        with span("serve.readback", step=i):
            out_np = np.asarray(out)            # one transfer per window
            outs = [out_np[:, k, :] for k in range(n_live)]
            news = [Row(r.index, False) for r in states]
            # the step's input and output leave the device here, so the
            # span times their release
            del x, out
        return news, outs

    def _run_learn(self, cohort, states):
        """The learning path: a tuple of per-session state trees through
        the per-session `vmap` step."""
        C, W, i = self.cfg.capacity, self.cfg.window, self._step_id
        n_live = len(cohort)
        with span("serve.assemble", step=i):
            sts = tuple(states) + (C - n_live) * (self._zero_full,)
            x = np.zeros((C, W, 1, self.n_in), self.dtype)
            for k, (_, xw, _) in enumerate(cohort):
                x[k, :, 0, :] = xw
            x = jnp.asarray(x)
        with span("serve.dispatch", step=i):
            step = _resident_step(self.nodes, self.plan, "vmap_learn",
                                  self.cfg.guard)
            ns, out = step(self.params, sts, x)
        with span("serve.readback", step=i):
            out_np = np.asarray(out)            # one transfer per window
            news = list(ns[:n_live])
            outs = [out_np[k, :, 0, :] for k in range(n_live)]
            # the input, the output and the free slots' states leave the
            # device here, so the span times their release
            del x, out, ns, sts
        return news, outs


class NaiveEngine(BatchedEngine):
    """One-session-at-a-time baseline: same scheduler, cache, and
    semantics, but each served session pays its own B=1 window launch —
    the loop `bench_serving` measures the batching win against."""

    kind = "naive"

    def _run_cohort(self, cohort, states):
        W = self.cfg.window
        news: List[Any] = []
        outs: List[np.ndarray] = []
        for (sess, xw, _), state in zip(cohort, states):
            if self._learn:
                x = jnp.asarray(xw, self.dtype).reshape(1, W, 1, self.n_in)
                step = _resident_step(self.nodes, self.plan, "vmap_learn",
                                      self.cfg.guard)
                ns, out = step(self.params, (state,), x)
                news.append(ns[0])
                outs.append(np.asarray(out)[0, :, 0, :])
            else:
                # a one-slot cohort through the pool
                rows, fresh = _pool_operands([state], 1)
                self.metrics.bump("fresh_resets", int(state.fresh))
                x = jnp.asarray(xw, self.dtype).reshape(W, 1, self.n_in)
                step = _resident_step(self.nodes, self.plan, "flat",
                                      self.cfg.guard)
                self.pool.leaves, out = step(self.params, self.pool.leaves,
                                             rows, fresh, x)
                news.append(Row(state.index, False))
                outs.append(np.asarray(out)[:, 0, :])
        return news, outs


def make_engine(nodes, params, cfg: EngineConfig = EngineConfig(),
                kind: str = "batched", **kw) -> BatchedEngine:
    """Factory: kind = "batched" (continuous batching) | "naive"."""
    cls = {"batched": BatchedEngine, "naive": NaiveEngine}.get(kind)
    if cls is None:
        raise ValueError(f"unknown engine kind {kind!r}; "
                         "expected 'batched' or 'naive'")
    return cls(nodes, params, cfg, **kw)


__all__ = ["EngineConfig", "BatchedEngine", "NaiveEngine", "make_engine"]
