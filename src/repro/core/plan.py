"""Program compiler: lower an event-driven Program to a fused execution plan.

The generic stepper (`events.run`) interprets a Program one timestep at a
time: every node pays T kernel launches and round-trips its membrane state
through HBM every step, and the INTEG matmuls run at (B, fan_in) — far too
skinny to feed the MXU. But most Program structure is static: which node
feeds which, with what delay, through which neuron dynamics, learning with
what rule. This module analyzes that structure once and emits a plan of
*segments*, each executed over the whole time axis at once.

Since the neuron API became declarative (`core/neuron.py::NeuronProgram`),
classification is *structural pattern matching on the IR* — there is no
per-class dispatch, so user-registered programs fuse whenever their shape
matches a kernel pattern:

  pattern (on the program)                          FIRE lowering
  ------------------------------------------------  -------------------
  1 state, current-driven, no threshold, membrane    `linrec` (associative
  output                                             all-T scan)
  1 state, current-driven, constant threshold, zero   `lif` (+ `lifrec`
  or subtract reset, spike output                     when self-recurrent;
                                                      subtract reset is
                                                      feed-forward only)
  2 states {membrane + spike-driven adaptation},      `alif` (+ `alifrec`
  affine threshold in the adaptation, hard reset      when self-recurrent)
  2 states {branch dendrites + sum-driven soma},      branch-integrate
  constant threshold, hard reset                      prologue (`linrec`
                                                      over the branch axis)
                                                      feeding `lif`

Synapse programs (`core/plasticity.py::SynapseProgram`, attached to a
`Connection(plastic=...)`) are matched the same way: any rule whose trace
decays are constants lowers to the generalized `stdp_seq` kernel family —
trace DIFFs hoisted through all-T `linrec`, then every outer-product
update term applied over the window with the weight tile VMEM-resident —
while unmatched rules (learned decays, oversized programs) run through the
parity-checked per-step fallback (`plasticity.synapse_step` scanned over
the realized spike trains). Either way, on-chip learning runs *inside*
`plan.run` (and, in `run_reference`, as the same pass after the
interpreted forward): within one run window the forward uses the entry
weights, and the learned weight + final traces are published in
`state[node]["syn:<conn>"]` (chunked-online semantics; merge with
`plasticity.apply_learned` between windows).

INTEG is hoisted out of the time loop for every fused segment: one
registry-dispatched `spikemm` over the (T*B, fan_in) spike matrix per feed
(block-occupancy flags = the FINDIDX bitmap at MXU granularity); the
branch convention (`snn_layers.branch_integrate`) hoists as one spikemm
against the branch-flattened weight tensor. Because that goes through the
registry, the block-sparse spikemm channel engages with no plan changes:
when the plan runs eagerly and the hoisted raster's measured occupancy is
below the tuned threshold, dispatch skips silent blocks outright
(`REPRO_SPIKEMM_SPARSE=never|auto|always` pins the choice). Everything that matches no
pattern (extra states, untagged integrates, recurrent branch programs)
runs through the stepper — per segment, with the fused neighbours'
full-time outputs (delay-shifted as needed) fed in externally.

Delayed (`Connection(delay=d)` / "src@d") reads of a *fused* source are
exact: the ring buffer the stepper would maintain is just a time-shift of
the source's full output tensor, seeded from the initial ring state.

Capability checks keep the compiler conservative: a Program where any node
reads a *later* node (previous-timestep semantics) compiles to a single
whole-program fallback segment, stepped like any other. Every Program
runs; fusable ones run fast.

`run_reference` is the fp32 reference the plan is held to: the whole
Program through the stepper (`events.run`), with the same boundary faults,
guards and learning pass as `run`.

Every fallback decision carries a stable TB2xx diagnostic code next to
its prose reason (`Segment.codes` / `PlasticLower.code`), so
`Plan.describe()` is machine-checkable and `repro.analysis` can explain
fusion without re-deriving the classifier.

Env knobs: REPRO_SNN_EXPLAIN=1 prints every compiled segment schedule
(`Plan.describe()`) as Programs are lowered. REPRO_CHECK = off | warn |
raise runs the full `repro.analysis` checker over each compiled Program:
`warn` routes warning+ findings onto the kernel incident log
(kind="check"), `raise` turns error-severity findings into
`analysis.DiagnosticError`. REPRO_FAULTS injects
deterministic faults at the run boundary and node outputs
(`core/faults.py`); REPRO_GUARD (or `run(guard=...)`) arms the numerical
guardrails (`core/guards.py`).
"""

from __future__ import annotations

import dataclasses
import os
from typing import (Any, Dict, Iterable, Iterator, List, NamedTuple,
                    Optional, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events, faults, guards, plasticity
from repro.core.neuron import Decay, NeuronProgram, decay_array
from repro.spanlog import span
# note: `repro.kernels` re-exports an `incidents()` *function*, which
# shadows the submodule on the package namespace — import names directly
from repro.kernels.incidents import FallbackEvent, record as _record_incident
from repro.kernels.alifrec.ops import alif_scan, alifrec_scan
from repro.kernels.lif.ops import lif_scan
from repro.kernels.lifrec.ops import lifrec_scan
from repro.kernels.linrec.ops import linrec
from repro.kernels.spikemm.ops import spikemm
from repro.kernels.stdp.ops import stdp_seq

Array = jax.Array

FUSED_FF = "fused_ff"
FUSED_REC = "fused_rec"
FALLBACK = "fallback"
EVENT = "event"                # populations stepped together, event INTEG

# FIRE lowering families the pattern matcher can emit
LOWER_LI = "li"
LOWER_LIF = "lif"
LOWER_ALIF = "alif"
LOWER_DHLIF = "dhlif"
LOWER_EVENT = "event_push"     # FIRE interpreted per step, INTEG pushed

# synapse-program lowerings
SYN_SEQ = "stdp_seq"
SYN_STEP = "step"

# Cross-engine agreement tolerance (fused plan vs stepper, jit vs eager).
#
# Root cause of the ~1e-6 DH-LIF membrane drift (CHANGES.md PR 7 note):
# the fused path evaluates the membrane DIFF through
# `jax.lax.associative_scan` (linrec), a fp32 *tree* reduction, while the
# stepper folds the same recurrence *sequentially*; fp32 addition is not
# associative, so the two orders accumulate different roundoff. Measured
# at T=1301 (the ECG window): 9.5e-7 max drift with a constant decay,
# 1.4e-6 with heterogeneous per-neuron decays (0.88..0.997). Reordering
# either side would cost the scan its O(log T) depth, so the bound is
# encoded here instead: ~7x margin over the worst observed drift. Use
# this constant — not ad-hoc atol literals — whenever comparing engines.
CROSS_ENGINE_ATOL = 1e-5


def check_mode() -> str:
    mode = os.environ.get("REPRO_CHECK", "off")
    if mode not in ("off", "warn", "raise"):
        raise ValueError(f"REPRO_CHECK={mode!r}: "
                         "expected 'off', 'warn', or 'raise'")
    return mode


@dataclasses.dataclass(frozen=True)
class Segment:
    """One unit of the lowered schedule, executed over the full time axis."""

    kind: str                  # fused_ff | fused_rec | fallback | event
    names: Tuple[str, ...]     # node names (fused segments hold exactly one)
    reason: str = ""           # why the planner fell back (diagnostics)
    lower: str = ""            # FIRE kernel family for fused segments
    codes: Tuple[str, ...] = ()  # TB2xx codes, one per merged fallback node


@dataclasses.dataclass(frozen=True)
class PlasticLower:
    """Lowering decision for one plastic Connection (run-granularity pass)."""

    node: str                  # destination node name
    conn: str                  # Connection.key
    lower: str                 # stdp_seq | step
    reason: str = ""           # why the fused family was refused
    code: str = ""             # TB2xx code for a refused fused lowering


@dataclasses.dataclass(frozen=True)
class Plan:
    segments: Tuple[Segment, ...]
    plastic: Tuple[PlasticLower, ...] = ()

    @property
    def fully_fallback(self) -> bool:
        return all(s.kind == FALLBACK for s in self.segments)

    def describe(self) -> str:
        """Segment schedule, with every fallback's TB-code inline — the
        machine-readable why behind each stepper segment."""
        parts = []
        for s in self.segments:
            tag = f"{s.kind}[{','.join(s.names)}]"
            if s.lower:
                tag += f":{s.lower}"
            if s.reason:
                tag += f"({s.reason})"
            parts.append(tag)
        out = " -> ".join(parts)
        if self.plastic:
            learns = []
            for p in self.plastic:
                tag = f"{p.node}.{p.conn}:{p.lower}"
                if p.reason:
                    tag += f"({p.code}: {p.reason})" if p.code \
                        else f"({p.reason})"
                learns.append(tag)
            out += " | learn " + ",".join(learns)
        return out


def _hoist_tag(node: events.LayerNode) -> Optional[str]:
    """INTEG hoist convention: "ff" = per-feed `s @ w` matmuls against each
    connection's weight key (`snn_layers.ff_integrate` = the canonical
    "w_<src>" naming), "branch" = the single-feed dendritic einsum
    (`snn_layers.branch_integrate`, fixed key "w_input"). Custom integrates
    opt in by setting `.hoist`; untagged integrates keep the stepper."""
    return getattr(node.integrate, "hoist", None)


def _match_fire_pattern(prog: NeuronProgram
                        ) -> Tuple[Optional[str], str, str]:
    """Structurally match a NeuronProgram against the fused FIRE kernels.

    Returns (lowering family, "", "") on a match, else
    (None, TB-code, reason). Driven ONLY by program structure — any user
    program with a matching shape (<= 2 coupled linear states + threshold
    + zero/subtract reset, or a pure leaky integrator) fuses, whatever
    Python class built it.
    """
    th = prog.threshold
    if not prog.states:
        return None, "TB206", "empty program"
    if prog.refractory or prog.background is not None or any(
            sv.gain != 1.0 or sv.drive.startswith("state:")
            for sv in prog.states):
        # the fused kernels know none of these; the interpreter does
        return None, "TB206", ("refractory, background, state drive or gain "
                               "(interpreted per step)")
    if th is None:
        sv = prog.states[0]
        if (len(prog.states) == 1 and not sv.branch
                and sv.drive == "current" and prog.output == sv.name):
            return LOWER_LI, "", ""
        return None, "TB206", "unfusable non-spiking program"
    if prog.output != "spikes":
        return None, "TB206", "state readout on a spiking program"
    if prog.reset not in ("zero", "subtract"):
        return None, "TB206", f"reset={prog.reset}"
    mem = next((s for s in prog.states if s.name == th.on), None)
    if mem is None or mem.branch:
        return None, "TB206", "threshold not on a plain membrane state"
    others = [s for s in prog.states if s.name != th.on]
    if mem.drive == "current" and not others and not th.adapt:
        return LOWER_LIF, "", ""
    if prog.reset != "zero":
        # the alif/dhlif kernels implement the hard reset only
        return None, "TB206", "subtract reset on a multi-state program"
    if (mem.drive == "current" and len(others) == 1
            and others[0].drive == "spikes" and not others[0].branch
            and th.adapt == others[0].name):
        return LOWER_ALIF, "", ""
    if (len(others) == 1 and others[0].branch
            and others[0].drive == "current"
            and mem.drive == f"sum:{others[0].name}" and not th.adapt):
        # the prologue feeds the soma the branches' NEW values, which is the
        # interpreter's semantics only when the branch state updates first
        names = [s.name for s in prog.states]
        if names.index(others[0].name) < names.index(mem.name):
            return LOWER_DHLIF, "", ""
        return None, "TB206", "soma declared before its branches"
    return None, "TB206", "program shape matches no fused FIRE kernel"


def _match_synapse_pattern(prog: "plasticity.SynapseProgram"
                           ) -> Tuple[str, str, str]:
    """Structurally match a SynapseProgram against the `stdp_seq` family.

    -> (SYN_SEQ, "", "") when the program is small enough for the fused
    plane stack; else (SYN_STEP, TB-code, reason) — the per-step
    interpreter over the realized spike trains, always correct. Learned
    per-synapse trace decays are fine: `linrec` takes a full decay plane,
    so a sigmoid-resolved learned decay hoists exactly like a constant.
    """
    if len(prog.traces) > 4:
        return SYN_STEP, "TB210", f"{len(prog.traces)} traces"
    if len(prog.terms) > 4:
        return SYN_STEP, "TB210", f"{len(prog.terms)} update terms"
    return SYN_SEQ, "", ""


def _classify(node: events.LayerNode, order: Dict[str, int]
              ) -> Tuple[str, str, str, str]:
    """-> (segment kind, TB-code, fallback reason, lowering family)."""
    hoist = _hoist_tag(node)
    if hoist not in ("ff", "branch"):
        return FALLBACK, "TB202", "integrate not hoistable", ""
    n_self = 0
    for c in node.connections:
        if c.src == "self":
            if c.delay:
                return FALLBACK, "TB203", "delayed self", ""
            n_self += 1
        elif c.src != "input" and order[c.src] >= order[node.name]:
            # previous-timestep read of a later node: handled by caller
            # (whole-program fallback); unreachable here, kept for safety
            return FALLBACK, "TB201", "back reference", ""
    if n_self > 1:
        return FALLBACK, "TB204", "multiple self feeds", ""
    try:
        prog = node.neuron.program
    except NotImplementedError:
        return FALLBACK, "TB205", "neuron declares no program", ""
    family, code, why = _match_fire_pattern(prog)
    if family is None:
        return FALLBACK, code, why, ""
    needs_branch = family == LOWER_DHLIF
    if needs_branch != (hoist == "branch"):
        return FALLBACK, "TB207", (
            f"{family} program needs "
            f"{'branch' if needs_branch else 'ff'} integrate, "
            f"got {hoist}"), ""
    if hoist == "branch":
        n_feeds = sum(1 for c in node.connections if c.src != "self")
        if n_feeds != 1:
            # the branch convention hoists exactly one feed through w_input;
            # extra feeds would be silently dropped
            return FALLBACK, "TB207", \
                f"branch integrate with {n_feeds} feeds", ""
    if n_self:
        if family == LOWER_LIF and prog.reset != "zero":
            return FALLBACK, "TB208", "recurrent subtract reset", ""
        if family in (LOWER_LIF, LOWER_ALIF):
            return FUSED_REC, "", "", family
        return FALLBACK, "TB208", f"recurrent {family}", ""
    return FUSED_FF, "", "", family


def _is_event_edge(c: events.Connection) -> bool:
    """A `FanOut` on the connection itself: every synapse has a delay of
    at least one step (the encoding refuses less). A topology named by a
    params key is unknown until run time and does not count here."""
    return events.event_topology(c) is not None


def _event_groups(nodes: List[events.LayerNode]
                  ) -> Tuple[List[List[str]], List[Tuple[List[str], str]]]:
    """Populations joined by event edges, as connected groups in
    declaration order: (groups that lower to one EVENT segment, groups
    that cannot with the reason). A group lowers when each member's every
    inbound edge is an event edge from a node (so a member) or itself,
    and each member declares a neuron program. (An event edge cannot be
    plastic: `Connection` refuses a topology that learns.)"""
    names = {n.name for n in nodes}
    parent = {n.name: n.name for n in nodes}

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    members = set()
    for n in nodes:
        for c in n.connections:
            if _is_event_edge(c):
                members.add(n.name)
                if c.src in names:
                    members.add(c.src)
                    parent[find(c.src)] = find(n.name)
    groups: Dict[str, List[str]] = {}
    for n in nodes:
        if n.name in members:
            groups.setdefault(find(n.name), []).append(n.name)
    by_name = {n.name: n for n in nodes}
    ok, bad = [], []
    for g in groups.values():
        why = ""
        for name in g:
            n = by_name[name]
            for c in n.connections:
                if not _is_event_edge(c):
                    why = f"{name} also reads {c.key} through no event edge"
                elif c.src not in names and c.src != "self":
                    why = f"{name} has an event edge from {c.src}, no node"
                if why:
                    break
            if not why:
                try:
                    n.neuron.program
                except NotImplementedError:
                    why = f"{name} declares no neuron program"
            if why:
                break
        if why:
            bad.append((g, why))
        else:
            ok.append(g)
    return ok, bad


def compile_program(nodes: List[events.LayerNode]) -> Plan:
    """Analyze the node DAG and emit the segment + plastic-lowering plan."""
    order = {n.name: i for i, n in enumerate(nodes)}
    plastic: List[PlasticLower] = []
    for n in nodes:
        for c in n.connections:
            if c.plastic is None:
                continue
            lower, code, why = _match_synapse_pattern(c.plastic)
            plastic.append(PlasticLower(n.name, c.key, lower, why, code))

    # Event groups: populations joined by event edges step together in one
    # EVENT segment. One that cannot lower couples the Program per step.
    groups, unlowered = _event_groups(nodes)
    plan = None
    if unlowered:
        g, why = unlowered[0]
        plan = Plan((Segment(
            FALLBACK, tuple(x.name for x in nodes),
            f"{g[0]}: TB211 event group cannot lower: {why}",
            codes=("TB211",)),), tuple(plastic))

    # Any previous-timestep read of a later node couples the whole Program
    # per-timestep: compile to one stepper segment (exactly events.run).
    # Event edges deliver at least a step later whatever the order.
    for n in ([] if plan else nodes):
        for c in n.connections:
            if _is_event_edge(c):
                continue
            if c.src not in ("input", "self") and order[c.src] >= order[n.name]:
                plan = Plan((Segment(
                    FALLBACK, tuple(x.name for x in nodes),
                    f"{n.name}: TB201 reads later node {c.src}",
                    codes=("TB201",)),), tuple(plastic))
                break
        if plan:
            break

    if plan is None:
        segments: List[Segment] = []
        pending_fallback: List[str] = []
        pending_reason = ""
        pending_codes: List[str] = []

        def flush():
            nonlocal pending_fallback, pending_reason, pending_codes
            if pending_fallback:
                segments.append(Segment(FALLBACK, tuple(pending_fallback),
                                        pending_reason,
                                        codes=tuple(pending_codes)))
                pending_fallback, pending_reason = [], ""
                pending_codes = []

        in_group = {name: g for g in groups for name in g}
        for n in nodes:
            if n.name in in_group:
                g = in_group[n.name]
                if n.name == g[0]:
                    flush()
                    segments.append(Segment(EVENT, tuple(g),
                                            lower=LOWER_EVENT))
                continue
            kind, code, reason, family = _classify(n, order)
            if kind == FALLBACK:
                pending_fallback.append(n.name)
                pending_codes.append(code)
                pending_reason = (pending_reason + "; " if pending_reason
                                  else "") + f"{n.name}: {code} {reason}"
            else:
                flush()
                segments.append(Segment(kind, (n.name,), lower=family))
        flush()
        plan = Plan(tuple(segments), tuple(plastic))

    if os.environ.get("REPRO_SNN_EXPLAIN") == "1":
        print(f"[repro.plan] {plan.describe()}")
    _run_check_hook(nodes, plan)
    return plan


# Re-entrancy latch for the REPRO_CHECK hook: `analysis.check_nodes` may
# itself call `compile_program` (it reuses the planner for TB2xx), which
# must not re-trigger the hook.
_IN_CHECK = False


def _run_check_hook(nodes: List[events.LayerNode], plan: "Plan") -> None:
    """Opt-in static checking at compile time (REPRO_CHECK=warn|raise).

    warn: warning+ findings land on the kernel incident log (kind="check")
    — observable, never fatal, and deliberately record()ed rather than
    degrade()d so REPRO_STRICT CI stays green. raise: error-severity
    findings abort compilation with `analysis.DiagnosticError`.
    """
    global _IN_CHECK
    mode = check_mode()
    if mode == "off" or _IN_CHECK:
        return
    from repro import analysis  # deferred: analysis imports this module
    _IN_CHECK = True
    try:
        diags = analysis.check_nodes(nodes, plan=plan)
    finally:
        _IN_CHECK = False
    if mode == "raise":
        analysis.raise_if(diags, "error")
    for d in analysis.at_least(diags, "warning"):
        _record_incident(FallbackEvent(
            kind="check", family="plan", stage=d.code,
            error=f"{d.site}: {d.message}"))


# ---------------------------------------------------------------------------
# plan execution
# ---------------------------------------------------------------------------


def _feed_full(outs: Dict[str, Array], state: Dict[str, Any], name: str,
               d: int, T: int) -> Array:
    """Full-time feed of source `name` delayed by `d` steps.

    feed_t = out_{t-d}; times < 0 come from the source's initial ring
    (zeros when the Program starts cold), exactly the stepper's delayed-fire
    semantics.
    """
    s_full = outs[name]
    if d == 0:
        return s_full
    ring = state.get(name, {}).get("ring")
    if ring is not None:
        prefix = ring[d - 1::-1]                     # s_{-d} ... s_{-1}
    else:
        prefix = jnp.zeros((d,) + s_full.shape[1:], s_full.dtype)
    return jnp.concatenate([prefix, s_full], axis=0)[:T]


def _advance_ring(ring: Array, out_full: Array) -> Array:
    """Ring state after the whole run: ring[k] = out_{T-1-k}, seeded from
    the initial ring for T < k."""
    stacked = jnp.concatenate([ring[::-1], out_full.astype(ring.dtype)], axis=0)
    return stacked[-ring.shape[0]:][::-1]


def _hoisted_current(node: events.LayerNode, params: Dict[str, Any],
                     outs: Dict[str, Array], state: Dict[str, Any],
                     T: int, B: int) -> Array:
    """All-T INTEG: one event-gated spikemm per inbound connection.

    The "branch" convention hoists the dendritic einsum as a single
    spikemm against the branch-flattened (n_in, K*n_out) weight view,
    yielding a (T, B, K, n_out) per-branch current block.
    """
    if _hoist_tag(node) == "branch":
        conn = next(c for c in node.connections if c.src != "self")
        s = _feed_full(outs, state, conn.src, conn.delay, T)
        w = params[node.name]["w_input"]             # (K, n_in, n_out)
        K, n_in, n_out = w.shape
        if not jnp.issubdtype(s.dtype, jnp.floating):
            s = s.astype(w.dtype)
        w2 = jnp.transpose(w, (1, 0, 2)).reshape(n_in, K * n_out)
        c = spikemm(s.reshape(T * B, -1), w2)
        return c.reshape(T, B, K, n_out)
    cur = None
    for conn in node.connections:
        if conn.src == "self":
            continue
        s = _feed_full(outs, state, conn.src, conn.delay, T)
        topo = events.resolve_topology(conn, node.name, params)
        if topo is not None:
            # compressed connectivity: hoist straight through the topology's
            # execution channel (spikemm for type-2 FC, spikemm_gather for
            # sparse/conv/pool IE tables) — dense_equivalent() never runs
            if not jnp.issubdtype(s.dtype, jnp.floating):
                s = s.astype(events.state_dtype(s.dtype))
            c = topo.apply_spikes(s.reshape(T * B, -1)).reshape(T, B, -1)
        else:
            w = params[node.name][conn.weight_key]
            if not jnp.issubdtype(s.dtype, jnp.floating):
                s = s.astype(w.dtype)                # int spikes: match locacc
            c = spikemm(s.reshape(T * B, -1), w).reshape(T, B, -1)
        cur = c if cur is None else cur + c
    if cur is None:
        cur = jnp.zeros((T, B, node.out_dim),
                        events.state_dtype(outs["input"].dtype))
    return cur


def _decay_vec(decay: Decay, nparams: Optional[Dict[str, Array]], n: int,
               n_branches: int = 0) -> Array:
    """Resolve a program Decay to the kernel-facing fp32 decay tensor:
    (N,) for per-neuron states, (K, N) for branch states."""
    shape = (n_branches, n) if n_branches else (n,)
    p = (nparams or {}).get(decay.param) if decay.kind != "const" else None
    if p is not None:
        return jnp.broadcast_to(jax.nn.sigmoid(p.astype(jnp.float32)), shape)
    return jnp.full(shape, decay.value, jnp.float32)


def _self_weight(node: events.LayerNode, params: Dict[str, Any]) -> Array:
    conn = next(c for c in node.connections if c.src == "self")
    return params[node.name][conn.weight_key]


def _is_spiking(node: events.LayerNode) -> bool:
    """Whether the node emits a spike train (rate monitors make sense) as
    opposed to a membrane/state readout. Unknown programs count as spiking
    only if they declare a threshold."""
    try:
        return node.neuron.program.threshold is not None
    except NotImplementedError:
        return False


def _fire(node: events.LayerNode, kind: str, lower: str,
          params: Dict[str, Any], state: Dict[str, Any], cur: Array,
          T: int, B: int) -> Tuple[Array, Dict[str, Array]]:
    """All-T FIRE of one fused node on its hoisted current: (output
    train, new neuron state)."""
    prog = node.neuron.program
    nparams = params.get(node.name, {}).get("neuron")
    sur, alpha = node.neuron.surrogate, node.neuron.alpha
    th = prog.threshold
    N = node.out_dim

    if lower == LOWER_LI:
        sv = prog.states[0]
        tau = _decay_vec(sv.decay, nparams, N)
        a = jnp.broadcast_to(tau.astype(cur.dtype), cur.shape)
        out, vT = linrec(a, cur, state[node.name][sv.name])
        ns = {sv.name: vT}
    elif lower == LOWER_LIF:
        tau = _decay_vec(prog.states[0].decay, nparams, N)
        v0 = state[node.name][th.on]
        if kind == FUSED_REC:
            out, vT = lifrec_scan(cur, _self_weight(node, params), tau, v0,
                                  state[node.name]["out"], th.base, sur,
                                  alpha)
        else:
            out, vT = lif_scan(cur, tau, v0, th.base, sur, alpha, False,
                               prog.reset)
        ns = {th.on: vT}
    elif lower == LOWER_ALIF:
        mem = next(s for s in prog.states if s.name == th.on)
        ad = next(s for s in prog.states if s.name == th.adapt)
        tau = _decay_vec(mem.decay, nparams, N)
        rho = _decay_vec(ad.decay, nparams, N)
        v0, a0 = state[node.name][mem.name], state[node.name][ad.name]
        if kind == FUSED_REC:
            out, vT, aT = alifrec_scan(cur, _self_weight(node, params), tau,
                                       rho, v0, a0, state[node.name]["out"],
                                       th.base, th.scale, sur, alpha)
        else:
            out, vT, aT = alif_scan(cur, tau, rho, v0, a0, th.base, th.scale,
                                    sur, alpha)
        ns = {mem.name: vT, ad.name: aT}
    elif lower == LOWER_DHLIF:
        # branch-integrate prologue: the dendrites never reset, so they are
        # a pure linear recurrence -> associative all-T linrec over the
        # branch-flattened axis, summed into the soma's LIF kernel.
        mem = next(s for s in prog.states if s.name == th.on)
        br = next(s for s in prog.states if s.branch)
        d0 = state[node.name][br.name]               # (B, K, N)
        K = d0.shape[-2]
        tau_d = _decay_vec(br.decay, nparams, N, n_branches=K)
        a = jnp.broadcast_to(tau_d.astype(cur.dtype)[None],
                             (B, K, N)).reshape(B * K, N)
        a = jnp.broadcast_to(a[None], (T, B * K, N))
        d_full, dT = linrec(a, cur.reshape(T, B * K, N),
                            d0.reshape(B * K, N))
        soma_cur = jnp.sum(d_full.reshape(T, B, K, N), axis=2)
        tau_s = _decay_vec(mem.decay, nparams, N)
        out, vT = lif_scan(soma_cur, tau_s, state[node.name][mem.name],
                           th.base, sur, alpha)
        ns = {mem.name: vT, br.name: dT.reshape(B, K, N)}
    else:  # pragma: no cover - compile_program only emits known families
        raise ValueError(f"unknown FIRE lowering {lower!r}")
    return out, ns


def _run_fused(node: events.LayerNode, kind: str, lower: str,
               params: Dict[str, Any], outs: Dict[str, Array],
               state: Dict[str, Any], new_state: Dict[str, Any],
               T: int, B: int,
               gcfg: guards.GuardConfig = guards.GuardConfig()) -> None:
    # named scopes tag each HLO op of the fused node with its stage in a
    # profiler trace and in compiled HLO metadata (op_name)
    with jax.named_scope("integ"):
        cur = _hoisted_current(node, params, outs, state, T, B)
    with jax.named_scope("fire"):
        out, ns = _fire(node, kind, lower, params, state, cur, T, B)

    # dead/stuck-row faults: the mask is time-independent, so masking the
    # full (T, B, N) train here equals the stepper's per-step masking for
    # everything downstream (feeds, rings, "out"). A fused *recurrent*
    # kernel's in-loop feedback runs pre-mask, unlike the stepper — use
    # feed-forward topologies (or the stepper engine) when exact
    # cross-engine equivalence under dead_rows matters.
    out = faults.perturb_output(node.name, out)
    out = guards.check_tensor(f"{node.name}.out", out, gcfg)
    if lower != LOWER_LI:
        guards.check_spikes(node.name, out, gcfg)
    ns = {k: guards.check_tensor(f"{node.name}.{k}", v, gcfg)
          for k, v in ns.items()}
    outs[node.name] = out
    ns["out"] = out[-1]
    if "ring" in state[node.name]:
        ns["ring"] = _advance_ring(state[node.name]["ring"], out)
    for k, v in state[node.name].items():
        if k.startswith("syn:"):
            ns[k] = v
    new_state[node.name] = ns


def _run_fallback(seg: Segment, nodes_by_name: Dict[str, events.LayerNode],
                  params: Dict[str, Any], x: Array, outs: Dict[str, Array],
                  state: Dict[str, Any], new_state: Dict[str, Any],
                  T: int,
                  gcfg: guards.GuardConfig = guards.GuardConfig()) -> None:
    seg_nodes = [nodes_by_name[name] for name in seg.names]
    seg_names = set(seg.names)
    sub_state = {name: state[name] for name in seg.names}
    ext: Dict[str, Array] = {}
    for n in seg_nodes:
        for c in n.connections:
            if c.src == "self" or c.src in seg_names or c.key in ext:
                continue
            if c.src == "input" and c.delay == 0:
                continue                 # events.step already emits x_t
            ext[c.key] = _feed_full(outs, state, c.src, c.delay, T)

    def body(st, ts):
        x_t, ext_t = ts
        st, _ = events.step(seg_nodes, params, st, x_t, ext=ext_t)
        return st, {name: st[name]["out"] for name in seg.names}

    final_sub, rec = jax.lax.scan(body, sub_state, (x, ext))
    if gcfg.active:
        for name in seg.names:
            rec[name] = guards.check_tensor(f"{name}.out", rec[name], gcfg)
            if _is_spiking(nodes_by_name[name]):
                guards.check_spikes(name, rec[name], gcfg)
        final_sub = {
            name: {k: (guards.check_tensor(f"{name}.{k}", v, gcfg)
                       if not k.startswith("syn:") else v)
                   for k, v in ns.items()}
            for name, ns in final_sub.items()}
    outs.update(rec)
    new_state.update(final_sub)


# ---------------------------------------------------------------------------
# EVENT segments: populations joined by per-synapse-delay edges
# ---------------------------------------------------------------------------
#
# The group's nodes step together inside one `lax.scan`: each step reads
# the group's arrival ring at slot t mod R as every member's current, runs
# each member's FIRE (the NeuronProgram interpreter, background drive
# included), clears the slot, and pushes the step's spikes through the
# group's flat fan-out table (`kernels/event`). The ring rides through the
# scan flat, R slots of B * N entries one after another, the layout the
# push scatters into: a step reads and clears its slot as one slice and
# never reshapes the whole ring (on a TPU the 3-D and the flat ring are
# tiled differently, so a reshape is a copy). The members' `arr` states,
# (R, B, n) each, are joined and flattened once per call and split back
# once after the scan. The tables are built once per `run` call, or once
# per stream (`prepare`), never inside a traced chunk.


def _event_members(seg: Segment, nodes_by_name: Dict[str, events.LayerNode]
                   ) -> Tuple[List[events.LayerNode], Dict[str, int], int]:
    members = [nodes_by_name[name] for name in seg.names]
    offs, pos = {}, 0
    for n in members:
        offs[n.name] = pos
        pos += n.out_dim
    return members, offs, pos


def event_tables(seg: Segment, nodes_by_name: Dict[str, events.LayerNode]):
    """(tables, shift, width) of one EVENT segment: every member's event
    edges regrouped by source population (eager; see `prepare`)."""
    from repro.kernels.event.ops import build_event_tables
    members, offs, total = _event_members(seg, nodes_by_name)
    leaving: Dict[str, List] = {n.name: [] for n in members}
    for n in members:
        for c in n.connections:
            src = n.name if c.src == "self" else c.src
            leaving[src].append((events.event_topology(c), offs[n.name]))
    return build_event_tables([(n.out_dim, leaving[n.name])
                               for n in members], total)


def prepare(plan: Plan, nodes: List[events.LayerNode]) -> Dict[int, Any]:
    """The event tables of every EVENT segment of `plan`, keyed by segment
    index: build them once and pass them to each `run` of a stream."""
    by_name = {n.name: n for n in nodes}
    return {i: event_tables(seg, by_name)
            for i, seg in enumerate(plan.segments) if seg.kind == EVENT}


def _pack_bits(s: Array) -> Array:
    """(..., n) 0/1 -> (words,) uint32, bit k of word i is entry 32 i + k
    of the flattened spikes."""
    flat = (s.reshape(-1) > 0).astype(jnp.uint32)
    pad = (-flat.shape[0]) % 32
    flat = jnp.pad(flat, (0, pad)).reshape(-1, 32)
    return jnp.sum(flat << jnp.arange(32, dtype=jnp.uint32), axis=1,
                   dtype=jnp.uint32)


def _run_event(seg: Segment, nodes_by_name: Dict[str, events.LayerNode],
               params: Dict[str, Any], state: Dict[str, Any],
               new_state: Dict[str, Any], T: int, B: int, prepared,
               packed: bool = False):
    """Step an EVENT segment T times. Returns (spikes (T, B, N) over the
    group, or (T, words) packed bits when `packed`, and the summed push
    stats uint32[3]: spikes, synaptic events delivered, steps that took
    an extra pass)."""
    from repro.kernels.event.ops import event_push
    tables, shift, width = prepared
    members, offs, N = _event_members(seg, nodes_by_name)
    rings = [state[n.name]["arr"] for n in members if "arr" in state[n.name]]
    R = rings[0].shape[0]
    dtype = rings[0].dtype
    M = B * N
    ring = jnp.concatenate(
        [state[n.name]["arr"] if "arr" in state[n.name]
         else jnp.zeros((R, B, n.out_dim), dtype) for n in members],
        axis=-1).reshape(R * M)
    fire0 = {n.name: {k: v for k, v in state[n.name].items()
                      if k not in ("out", "ring", "t", "arr")
                      and not k.startswith("syn:")} for n in members}
    last0 = jnp.concatenate([state[n.name]["out"] for n in members], -1)
    nparams = {n.name: params.get(n.name, {}).get("neuron") for n in members}
    t0 = state[members[0].name]["t"]

    def body(carry, _):
        fs, ring, t, _last, acc = carry
        at = (t % R) * M
        cur = jax.lax.dynamic_slice(ring, (at,), (M,)).reshape(B, N)
        new_fs, outs = {}, []
        with jax.named_scope("fire"):
            for n in members:
                o = offs[n.name]
                ns, so = n.neuron.fire(fs[n.name], cur[:, o:o + n.out_dim],
                                       nparams[n.name], t=t)
                new_fs[n.name] = ns
                outs.append(faults.perturb_output(n.name, so))
        spk = jnp.concatenate(outs, axis=-1).astype(dtype)
        ring = jax.lax.dynamic_update_slice(ring, jnp.zeros((M,), dtype),
                                            (at,))
        with jax.named_scope("integ"):
            ring, st = event_push(ring, spk.reshape(-1), t, tables,
                                  shift=shift, width=width)
        y = _pack_bits(spk) if packed else spk
        return (new_fs, ring, t + 1, spk, acc + st), y

    carry0 = (fire0, ring, t0, last0, jnp.zeros((3,), jnp.uint32))
    (fs, ring, tT, last, stats), ys = jax.lax.scan(body, carry0, None,
                                                   length=T)
    ring = ring.reshape(R, B, N)
    for n in members:
        o = offs[n.name]
        ns = dict(fs[n.name])
        ns["out"] = last[:, o:o + n.out_dim]
        ns["t"] = tT
        if "arr" in state[n.name]:
            ns["arr"] = ring[..., o:o + n.out_dim]
        if "ring" in state[n.name]:     # read by a delayed edge downstream
            ns["ring"] = _advance_ring(state[n.name]["ring"],
                                       ys[..., o:o + n.out_dim])
        for k, v in state[n.name].items():
            if k.startswith("syn:"):
                ns[k] = v
        new_state[n.name] = ns
    return ys, stats


# ---------------------------------------------------------------------------
# session-state layout (the serve engine's state pool builds on it)
# ---------------------------------------------------------------------------
#
# A `plan.run` state tree is {node: {key: array}} where every per-neuron
# leaf carries the batch on axis 0 — except the delay ring, whose layout is
# (depth, batch, n), and the synapse entries ("syn:<conn>", a dict each),
# whose weight plane has NO batch axis (one tile per connection,
# batch-summed updates). The serve engine (repro.serve) keeps many batch-1
# streaming sessions in one device-resident pool per leaf, a row per
# session along the batch axis; a synapse entry's leaves get a leading row
# axis of their own, so every learning session keeps its own weights.


def state_batch_axis(key: str) -> Optional[int]:
    """The batch axis of the entry `key` of a node's state; None for a
    synapse entry, which has none."""
    if key.startswith("syn:"):
        return None
    return 1 if key == "ring" else 0


def state_nbytes(state: Dict[str, Any]) -> int:
    """Total bytes of one state tree — the per-session footprint the serve
    cache budgets against (syn entries included: the learning path carries
    them per session)."""
    return sum(int(v.size) * v.dtype.itemsize if hasattr(v, "dtype") else 0
               for v in jax.tree_util.tree_leaves(state))


# ---------------------------------------------------------------------------
# the plasticity pass (run-granularity on-chip learning)
# ---------------------------------------------------------------------------


def _mod_full(mod: Optional[Array], T: int, B: int, N: int, dtype) -> Array:
    """Broadcast the run-level modulator to the (T, B, N) term plane.

    Accepts None (zeros: no reward, no update), (T,) global reward per
    step, (T, B) per-trial reward, or (T, B, N) per-neuron teaching
    signal."""
    if mod is None:
        return jnp.zeros((T, B, N), dtype)
    m = jnp.asarray(mod, dtype)
    if m.ndim == 1:
        m = m[:, None, None]
    elif m.ndim == 2:
        m = m[..., None]
    return jnp.broadcast_to(m, (T, B, N))


def _learn_fused(prog: "plasticity.SynapseProgram", syn0: Dict[str, Array],
                 pre_full: Array, post_full: Array,
                 mod_full: Optional[Array],
                 sparams: Optional[Dict[str, Array]] = None
                 ) -> Dict[str, Array]:
    """Fused `stdp_seq` lowering of one SynapseProgram window.

    Trace DIFFs are pure linear recurrences -> hoisted through all-T
    `linrec`; each term's pre/post factor products become (T*B, n) planes
    ("after" traces read the one-step-shifted trajectory); the stacked
    planes drive the serial-in-time `stdp_seq` kernel with the weight tile
    VMEM-resident across the whole window. Learned per-synapse decays
    (`sparams`, the `params[node]["syn:<conn>"]` dict) resolve through
    `decay_array` exactly like the per-step interpreter and broadcast into
    the decay plane.
    """
    T, B = pre_full.shape[:2]
    by_name = {t.name: t for t in prog.traces}
    traj: Dict[str, Array] = {}
    shifted: Dict[str, Array] = {}
    finals: Dict[str, Array] = {}
    for tr in prog.traces:
        s = pre_full if tr.source == "pre" else post_full
        h0 = syn0[tr.name].astype(s.dtype)
        a = jnp.broadcast_to(decay_array(tr.decay, sparams, s.dtype), s.shape)
        y, hT = linrec(a, tr.scale * s, h0)
        traj[tr.name] = y
        finals[tr.name] = hT.astype(syn0[tr.name].dtype)
        shifted[tr.name] = jnp.concatenate([h0[None], y[:-1]], axis=0)

    def plane(factors, spikes):
        v = None
        for f in factors:
            if f == "spikes":
                x = spikes
            elif f == "mod":
                x = mod_full
            else:
                x = traj[f] if by_name[f].update == "before" else shifted[f]
            v = x if v is None else v * x
        return v

    P = jnp.stack([plane(t.pre, pre_full).reshape(T * B, -1)
                   for t in prog.terms])
    Q = jnp.stack([plane(t.post, post_full).reshape(T * B, -1)
                   for t in prog.terms])
    w1 = stdp_seq(P, Q, syn0["w"], amps=tuple(t.amp for t in prog.terms),
                  w_min=prog.w_min, w_max=prog.w_max, batch=B)
    out = {"w": w1}
    out.update(finals)
    return out


def _learn_conn(node: events.LayerNode, conn: events.Connection, lower: str,
                params: Dict[str, Any], outs: Dict[str, Array],
                state: Dict[str, Any], new_state: Dict[str, Any],
                T: int, B: int, mod: Optional[Array],
                order: Dict[str, int],
                gcfg: guards.GuardConfig = guards.GuardConfig()) -> None:
    """Apply one plastic Connection's learning rule over the run window.

    The pre train is exactly the feed the stepper delivered: delay-shifted
    for "src@d" reads, and the *previous-step* output for "self" and for
    undelayed back-references (a source ordered at-or-after the node is
    read before it runs, i.e. at t-1, seeded from its initial "out"). The
    post train is the node's emitted output. The whole update is a weight
    write: stop_gradient keeps it out of STBP autodiff, like an optimizer
    step.
    """
    prog = conn.plastic
    key = f"syn:{conn.key}"
    syn0 = state[node.name][key]
    prev_step = conn.src == "self" or (
        conn.src != "input" and conn.delay == 0
        and order[conn.src] >= order[node.name])
    if prev_step:
        src_name = node.name if conn.src == "self" else conn.src
        s_full = outs[src_name]
        pre = jnp.concatenate([state[src_name]["out"][None], s_full[:-1]], 0)
    else:
        pre = _feed_full(outs, state, conn.src, conn.delay, T)
    post = outs[node.name]
    fdt = events.state_dtype(post.dtype)
    pre, post = pre.astype(fdt), post.astype(fdt)
    uses_mod = any("mod" in t.post for t in prog.terms)
    mod_f = _mod_full(mod, T, B, post.shape[-1], fdt) if uses_mod else None
    pre, post, syn0, mod_f = jax.lax.stop_gradient((pre, post, syn0, mod_f))
    sparams = params.get(node.name, {}).get(key)
    if lower == SYN_SEQ:
        syn1 = _learn_fused(prog, syn0, pre, post, mod_f, sparams)
    else:
        syn1 = plasticity.synapse_run(prog, syn0["w"], pre, post, mod_f,
                                      sparams, syn=syn0)
    if gcfg.active:
        # chunked-online divergence guard: a window whose learned weights
        # go nonfinite or explode is flagged (warn/raise) or rolled back
        # to the entry tensor (sanitize) before it is published
        syn1 = dict(syn1)
        syn1["w"] = guards.guard_learned(f"{node.name}.{conn.key}",
                                         syn0["w"], syn1["w"], gcfg)
    ns = dict(new_state[node.name])
    ns[key] = syn1
    new_state[node.name] = ns


def _learn_pass(plan: Plan, nodes: List[events.LayerNode],
                params: Dict[str, Any], outs: Dict[str, Array],
                state: Dict[str, Any], new_state: Dict[str, Any],
                T: int, B: int, mod: Optional[Array],
                gcfg: guards.GuardConfig = guards.GuardConfig()) -> None:
    nodes_by_name = {n.name: n for n in nodes}
    order = {n.name: i for i, n in enumerate(nodes)}
    for p in plan.plastic:
        node = nodes_by_name[p.node]
        conn = next(c for c in node.connections if c.key == p.conn)
        _learn_conn(node, conn, p.lower, params, outs, state, new_state,
                    T, B, mod, order, gcfg)


def run(nodes: List[events.LayerNode], params: Dict[str, Any], x: Array,
        state: Optional[Dict[str, Any]] = None, record: Tuple[str, ...] = (),
        plan: Optional[Plan] = None, mod: Optional[Array] = None,
        learn: bool = True,
        guard: Union[None, str, guards.GuardConfig] = None,
        prepared: Optional[Dict[int, Any]] = None):
    """Drop-in replacement for `events.run` through the compiled plan.

    x: (T, batch, n_in). Returns (final_state, outputs (T, batch, n_out),
    recorded dict) — numerically equivalent to the stepper. Plastic
    Connections learn over the window (disable with `learn=False`); `mod`
    is the optional modulator/reward signal ((T,), (T, B), or (T, B,
    n_post)) feeding the rules' "mod" factors. Learned weights + final
    traces come back in `state[node]["syn:<conn>"]`
    (`plasticity.apply_learned` merges them into params).

    Resilience hooks: active faults (`REPRO_FAULTS` / `faults.inject`)
    perturb the input raster and weight planes once at entry and node
    outputs inside every segment, as in `run_reference`. `guard` enables
    numerical guardrails (`core/guards.py`) — a policy string
    off|warn|raise|sanitize or a full `GuardConfig`; None defers to
    `REPRO_GUARD` (default off).

    EVENT segments build their fan-out tables from the connections' arrays
    on each call unless `prepared` (from `prepare`) holds them; under a
    caller's jit, pass `prepared` as an argument, or the tables become
    constants of the compiled program.
    """
    if plan is None:
        plan = compile_program(nodes)
    gcfg = guards.config(guard)
    nodes_by_name = {n.name: n for n in nodes}
    T, B = x.shape[0], x.shape[1]

    # injected faults hit the run boundary once, before init_state seeds
    # plastic synapses, exactly as in `run_reference`
    x = faults.perturb_input(x)
    params = faults.perturb_params(params)
    x = guards.check_tensor("input", x, gcfg)

    if state is None:
        state = events.init_state(nodes, B, x.dtype, params)
    outs: Dict[str, Array] = {"input": x}
    new_state = dict(state)
    for i, seg in enumerate(plan.segments):
        if seg.kind == FALLBACK:
            _run_fallback(seg, nodes_by_name, params, x, outs, state,
                          new_state, T, gcfg)
        elif seg.kind == EVENT:
            tabs = (prepared or {}).get(i) or event_tables(seg,
                                                          nodes_by_name)
            spk, _ = _run_event(seg, nodes_by_name, params, state, new_state,
                                T, B, tabs)
            members, offs, _ = _event_members(seg, nodes_by_name)
            for n in members:
                out = spk[..., offs[n.name]:offs[n.name] + n.out_dim]
                if gcfg.active:
                    out = guards.check_tensor(f"{n.name}.out", out, gcfg)
                    guards.check_spikes(n.name, out, gcfg)
                outs[n.name] = out
        else:
            _run_fused(nodes_by_name[seg.names[0]], seg.kind, seg.lower,
                       params, outs, state, new_state, T, B, gcfg)
    if learn and plan.plastic:
        _learn_pass(plan, nodes, params, outs, state, new_state,
                    T, B, mod, gcfg)
    recs = {r: outs[r] for r in record}
    return new_state, outs[nodes[-1].name], recs


def run_reference(nodes: List[events.LayerNode], params: Dict[str, Any],
                  x: Array, state: Optional[Dict[str, Any]] = None,
                  record: Tuple[str, ...] = (), mod: Optional[Array] = None,
                  learn: bool = True,
                  guard: Union[None, str, guards.GuardConfig] = None):
    """The fp32 reference `run` is held to: the whole Program through the
    stepper (`events.run`), one timestep at a time, then the same
    learning pass over the realized spike trains. Same signature and
    return as `run` (without `plan` and `prepared`), the same faults at
    the run boundary, the same guards on the last node's outputs."""
    plan = compile_program(nodes)
    gcfg = guards.config(guard)
    nodes_by_name = {n.name: n for n in nodes}
    T, B = x.shape[0], x.shape[1]
    x = faults.perturb_input(x)
    params = faults.perturb_params(params)
    x = guards.check_tensor("input", x, gcfg)
    do_learn = learn and bool(plan.plastic)
    if state is None:
        state = events.init_state(nodes, B, x.dtype, params)
    # record what the plastic connections' rules read
    needed = set(record)
    for p in plan.plastic if do_learn else ():
        needed.add(p.node)
        conn = next(c for c in nodes_by_name[p.node].connections
                    if c.key == p.conn)
        if conn.src not in ("input", "self"):
            needed.add(conn.src)
    final, out, recs = events.run(nodes, params, x, state, tuple(needed))
    out = guards.check_tensor(f"{nodes[-1].name}.out", out, gcfg)
    if gcfg.active and _is_spiking(nodes[-1]):
        guards.check_spikes(nodes[-1].name, out, gcfg)
    if not do_learn:
        return final, out, recs
    outs = dict(recs)
    outs["input"] = x
    outs[nodes[-1].name] = out
    new_state = dict(final)
    _learn_pass(plan, nodes, params, outs, state, new_state,
                T, B, mod, gcfg)
    return new_state, out, {r: outs[r] for r in record}


class StreamSpikes(NamedTuple):
    """One chunk's spikes, compacted (`run_stream(..., spikes="list")`).

    step:   absolute step index of each spike, ascending
    neuron: the spiking neuron, indexed over the program's nodes side by
            side in declaration order
    batch:  its batch row
    counts: the chunk's counters: `spikes` (counted on the device, before
            any read-back), `events` (synaptic events delivered) and
            `extra_pass_steps` (steps whose spikes took more than one
            push pass)
    """

    step: np.ndarray
    neuron: np.ndarray
    batch: np.ndarray
    counts: Dict[str, int]


def _decode_words(pos: np.ndarray, vals: np.ndarray, t0: int, W: int,
                  N: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(step, neuron, batch) of the set bits of packed words at flat
    positions `pos` of a (T, W) array."""
    bits = (vals[:, None] >> np.arange(32, dtype=np.uint32)) & 1
    r, k = np.nonzero(bits)
    word = pos[r].astype(np.int64)
    idx = (word % W) * 32 + k
    return t0 + word // W, (idx % N).astype(np.int32), \
        (idx // N).astype(np.int32)


# nonzero spike words a step that a list-mode chunk reads back compacted;
# a chunk with more reads every word
WORDS_A_STEP = 64


def stream_step(nodes: List[events.LayerNode], plan: Plan,
                spikes: str = "dense", learn: bool = True,
                guard: Union[None, str, guards.GuardConfig] = None):
    """The jitted chunk step of `run_stream`, state (argument 2) donated:
    spikes="dense": (params, prepared, state, x, mod) -> (state, outputs);
    spikes="list": (params, prepared, state, x) -> (state, (positions and
    values of the nonzero spike words, their count, every word, the push
    stats)) for a program that is one EVENT segment."""
    by_name = {n.name: n for n in nodes}

    def dense_chunk(params, prepared, state, x, m):
        st, out, _ = run(nodes, params, x, state=state, plan=plan, mod=m,
                         learn=learn, guard=guard, prepared=prepared)
        return st, out

    def list_chunk(params, prepared, state, x):
        T, B = x.shape[0], x.shape[1]
        new_state = dict(state)
        words, stats = _run_event(plan.segments[0], by_name,
                                  faults.perturb_params(params), state,
                                  new_state, T, B, prepared[0], packed=True)
        flat = words.reshape(-1)
        cap = min(flat.shape[0], WORDS_A_STEP * T)
        n_nz = jnp.sum(flat != 0, dtype=jnp.int32)
        pos = jnp.nonzero(flat, size=cap, fill_value=0)[0].astype(jnp.int32)
        return new_state, (pos, flat[pos], n_nz, words, stats)

    return jax.jit(list_chunk if spikes == "list" else dense_chunk,
                   donate_argnums=(2,))


def run_stream(nodes: List[events.LayerNode], params: Dict[str, Any],
               chunks: Iterable[Array],
               state: Optional[Dict[str, Any]] = None,
               plan: Optional[Plan] = None, mod: Optional[Array] = None,
               learn: bool = True,
               guard: Union[None, str, guards.GuardConfig] = None,
               spikes: str = "dense"
               ) -> Iterator[Tuple[Dict[str, Any], Any]]:
    """Chunked/streaming execution: constant peak memory in stream length.

    Consumes an iterable of (T_chunk, batch, n_in) spike chunks and yields
    `(state, outputs)` after each one, carrying neuron state, skip-delay
    ring buffers, arrival rings, step counters and synapse state across
    chunk boundaries. Ring-buffered delay lines make this exact: a delayed
    edge reads its prefix from the carried ring (`_feed_full`), never from
    a delay-shifted full-time tensor, and a background drive draws from
    (key, step), so concatenating the yielded outputs reproduces the
    one-shot `run` on the concatenated stream while peak host+device
    memory scales with the chunk length only — the paper's
    infinite-time-window streaming mode.

    Each chunk runs one jitted step program (compiled once per chunk
    shape) that takes the state DONATED: the state a chunk yields is
    consumed by the next one, so copy what you keep before resuming the
    stream. The stream works on its own copy of a `state` passed in. The
    plan is compiled once up front and EVENT tables built once
    (`prepare`); `mod`, when given, must be an iterable aligned with
    `chunks` (one modulator window per chunk). Guards under policy
    "raise" need concrete values, so that policy runs the chunks eagerly.

    spikes="dense" yields the last node's (T, batch, n_out) outputs, as
    `run` returns them. spikes="list" — for a program that is one EVENT
    segment — yields a `StreamSpikes` of every neuron's spikes instead:
    the chunk packs each step's spikes into bits and compacts the nonzero
    words on the device (at most 64 a step, `WORDS_A_STEP`), and the host
    reads back only those; a chunk with more reads every word, so no
    spike is lost. Both modes log the spans `sim.chunk` (the call to its
    outputs on the host, with the counters as ids in list mode) and, in
    list mode, `sim.readback` (`repro.spanlog.span`).
    """
    if plan is None:
        plan = compile_program(nodes)
    if spikes not in ("dense", "list"):
        raise ValueError(f"spikes={spikes!r}: expected 'dense' or 'list'")
    listed = spikes == "list"
    if listed and not (len(plan.segments) == 1
                       and plan.segments[0].kind == EVENT):
        raise ValueError("spikes='list' steps a program that compiles to "
                         f"one EVENT segment, not {plan.describe()}")
    gcfg = guards.config(guard)
    eager = gcfg.policy == "raise" and not listed
    prepared = prepare(plan, nodes)
    mods = iter(mod) if mod is not None else None
    if state is not None:
        state = jax.tree_util.tree_map(jnp.copy, state)

    step = stream_step(nodes, plan, spikes, learn, guard)
    t_host = None
    for k, x in enumerate(chunks):
        m = next(mods) if mods is not None else None
        if state is None:
            state = events.init_state(nodes, x.shape[1], x.dtype, params)
        with span("sim.chunk", chunk=k) as sp:
            if not listed:
                if eager:
                    state, out, _ = run(nodes, params, x, state=state,
                                        plan=plan, mod=m, learn=learn,
                                        guard=guard, prepared=prepared)
                else:
                    state, out = step(params, prepared, state, x, m)
            else:
                if t_host is None:
                    t_host = int(state[nodes[0].name]["t"])
                state, (pos, vals, n_nz, words, stats) = step(
                    params, prepared, state, x)
                with span("sim.readback", chunk=k):
                    n_nz, stats = int(n_nz), np.asarray(stats)
                    if n_nz <= pos.shape[0]:
                        pos, vals = np.asarray(pos)[:n_nz], \
                            np.asarray(vals)[:n_nz]
                    else:                        # more than the cap: all
                        w = np.asarray(words).reshape(-1)
                        pos = np.flatnonzero(w)
                        vals = w[pos]
                    W = words.shape[1]
                    n_all = sum(n.out_dim for n in nodes)
                    st_, nr, bt = _decode_words(pos, vals, t_host, W, n_all)
                counts = {"spikes": int(stats[0]), "events": int(stats[1]),
                          "extra_pass_steps": int(stats[2])}
                sp.ids.update(counts)
                out = StreamSpikes(st_, nr, bt, counts)
                t_host += x.shape[0]
        yield state, out


__all__ = ["Plan", "PlasticLower", "Segment", "compile_program",
           "check_mode", "run", "run_reference", "run_stream", "prepare",
           "stream_step",
           "event_tables", "StreamSpikes",
           "CROSS_ENGINE_ATOL",
           "state_nbytes", "state_batch_axis",
           "FUSED_FF", "FUSED_REC", "FALLBACK", "EVENT",
           "LOWER_LI", "LOWER_LIF", "LOWER_ALIF", "LOWER_DHLIF",
           "LOWER_EVENT",
           "SYN_SEQ", "SYN_STEP"]
