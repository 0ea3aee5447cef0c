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
discarded. A one-slot engine (`capacity=1`) serves one session at a time.

Inference cohorts run *flat*: sessions side by side along the batch
axis, the MXU-shaped layout. With `learn=True` on a plastic model the
pool also holds every session's synapse entries (`syn:`, a row each) and
the step runs the window once per slot instead: a synapse weight plane
has no batch axis, so a flat cohort would batch-sum every tenant's update
into one tile. The learning step gathers the same rows, `vmap`s
`plan.run(learn=True)` over the slots at batch 1 — each lane's entry
weights are its session's last published `syn:` tensors
(`plasticity.apply_learned`, the chunked-online contract), a fresh
session's the seeded ones of the initial-state row — and scatters the
result back. Both steps take the same operands, so one cohort path
assembles, dispatches and reads back either.

Isolation invariant (property-tested): a session's output trajectory and
final state are bit-identical whether it runs alone, interleaved with
strangers, or is evicted to host and restored mid-stream. The flat step
earns this because every per-slot computation in the fused kernels is
row-independent and the executable is shape-fixed (solo and packed
cohorts run the *same* compiled step, whichever pool rows they hold);
the learning step because lanes are independent by construction;
evict/restore because spill is a pure device<->host copy.

Resilience composes: kernel dispatch inside the resident step follows
the registry's failure policy (off-TPU an interpret-mode failure degrades
to the reference and is recorded; compiled kernels never degrade),
`REPRO_FAULTS` / `REPRO_GUARD` thread through
`plan.run` unchanged. The step cache keys on the ambient faults/dispatch
environment, so entering a fault context retraces instead of silently
replaying a clean executable.
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
                                  StatePool, gather_rows, scatter_rows,
                                  vmap_slots)

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs for the streaming engine.

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
# Jitted window steps are cached per (nodes, learning or not, guard,
# ambient environment). Keys hold id()s of the live node objects — the
# closures keep those objects alive, so ids cannot be recycled into a
# collision. The environment fingerprint (dispatch pins, active fault
# spec) is part of the key because `plan.run` resolves all of those at
# TRACE time: a cached clean-world executable must not be replayed inside
# a `faults.inject(...)` context.

_STEP_CACHE: Dict[tuple, Callable] = {}


def _env_fingerprint(guard: Optional[str]) -> tuple:
    return (os.environ.get("REPRO_KERNEL_IMPL"),
            os.environ.get("REPRO_PALLAS_INTERPRET"),
            os.environ.get("REPRO_SPIKEMM_SPARSE"),
            guard if guard is not None else os.environ.get("REPRO_GUARD"),
            faults.active())


def _resident_step(nodes, compiled, learn: bool,
                   guard: Optional[str]) -> Callable:
    key = (tuple(id(n) for n in nodes), learn, _env_fingerprint(guard))
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    nodes = list(nodes)

    # Both steps take the state pool (donated: updated in place), the
    # cohort's rows and fresh mask and the (W, C, n_in) input, and return
    # the pool and the (W, C, n_out) output; the argument count does not
    # grow with the capacity.
    if not learn:
        def step(params, pool, rows, fresh, x):
            with jax.named_scope("pack"):
                state = gather_rows(pool, rows, fresh)
            ns, out, _ = plan_mod.run(nodes, params, x, state=state,
                                      plan=compiled, learn=False,
                                      guard=guard)
            with jax.named_scope("unpack"):
                return scatter_rows(pool, rows, ns), out
    else:
        def step(params, pool, rows, fresh, x):
            with jax.named_scope("pack"):
                state = gather_rows(pool, rows, fresh)

            def lane(st, x_k):
                # chunked-online entry weights = the session's last
                # published learned tensors; fresh sessions carry the
                # seeded ones
                p = plasticity.apply_learned(nodes, params, st)
                ns, out, _ = plan_mod.run(nodes, p, x_k, state=st,
                                          plan=compiled, learn=True,
                                          guard=guard)
                return ns, out
            ns, out = vmap_slots(lane, state, x)
            with jax.named_scope("unpack"):
                return scatter_rows(pool, rows, ns), out
    fn = jax.jit(step, donate_argnums=1)
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
# the engine
# ---------------------------------------------------------------------------


class BatchedEngine:
    """Continuous-batching multi-tenant engine (see module docstring)."""

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
            # every session learns its own synapses: they are pool rows
            core, self._syn = init, {}
        else:
            # synapse entries never change on the flat path: held once
            core, self._syn = _split_syn(init)
        self.pool = StatePool(core, cfg.capacity, metrics=self.metrics)
        self.cache = StateCache(self.pool, cfg.cache_bytes,
                                metrics=self.metrics)

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
            self.cache.put(sid, Row(self.pool.claim(), True))
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
        return _merge_syn(self.pool.read(self.cache.get(sid)), self._syn)

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
        snap.update(window=self.cfg.window, capacity=self.cfg.capacity,
                    cache_hot_bytes=self.cache.hot_bytes,
                    cache_spilled=len(self.cache.spilled),
                    pool_rows=self.pool.rows,
                    sessions_open=len(self.scheduler.sessions))
        return snap

    def _run_cohort(self, cohort: List[Tuple[Session, np.ndarray, int]],
                    states: List[Row]) -> Tuple[List[Row], List[np.ndarray]]:
        """One resident-step launch for the cohort: (new pool rows,
        outputs), one per session."""
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
            step = _resident_step(self.nodes, self.plan, self._learn,
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


def make_engine(nodes, params, cfg: EngineConfig = EngineConfig(),
                **kw) -> BatchedEngine:
    """The streaming engine over a program: a `BatchedEngine`."""
    return BatchedEngine(nodes, params, cfg, **kw)


__all__ = ["EngineConfig", "BatchedEngine", "make_engine"]
