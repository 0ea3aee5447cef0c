"""Event-driven INTEG/FIRE execution engine (paper §IV-A, Fig. 10).

One SNN timestep on TaiBai = an INTEG phase (spike events drive current
accumulation at their destination cores; silent cores stay in RECV) followed
by a FIRE phase (membrane update, spike emission, and — for on-chip learning
— weight update). On TPU this becomes a `lax.scan` over timesteps where each
step is integrate -> fire; sparsity is exploited at block granularity by the
`spikemm` kernel instead of at word granularity by the NoC.

The engine runs a `Program`: an ordered list of `LayerNode`s whose inbound
edges are first-class `Connection` objects — source, delay (skip/delayed
delivery, Fig. 8c — implemented as a ring buffer of spike tensors, exactly
the chip's 'delayed-fire' neuron type), the weight-parameter key, and an
optional `SynapseProgram` (core/plasticity.py) making the edge learnable
on-chip. The legacy string micro-syntax ("name", "name@d", "self") still
works everywhere: `Connection.parse` is the thin back-compat adapter, and
`LayerNode` normalizes mixed string/Connection input tuples at
construction.

An edge whose topology is a `FanOut` encoding (a delay per synapse, kind
"fanout") is an *event edge*: it reads no feed. When its source fires at
step t, each synapse adds its weight into the target's ring of arriving
currents, `state[node]["arr"]` of shape (R, batch, n), at slot
(t + delay) mod R; the target reads slot t mod R as part of its current
and clears it. Every such delay is >= 1 step, so an event edge never
couples nodes within a step, whatever their order. Nodes that receive
event edges or run a background drive carry the step index,
`state[node]["t"]` (int32 scalar), across steps and chunks.

The stepper itself is forward-only; plasticity executes at run granularity
in `core/plan.py` (fused `stdp_seq` lowering or the per-step fallback over
the realized spike trains — identical trajectories), with synapse state
carried here in `state[node]["syn:<conn>"]`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core.neuron import NeuronSpec

Array = jax.Array


def _parse_src(src: str) -> Tuple[str, int]:
    if "@" in src:
        name, d = src.split("@")
        return name, int(d)
    return src, 0


@dataclasses.dataclass(frozen=True)
class Connection:
    """One inbound edge of a LayerNode, first-class.

    src:     source node name; "input" = the external spike tensor;
             "self" = this node's own output at t-1 (recurrence).
    delay:   delayed-fire depth in timesteps (ring-buffered delivery).
    weight:  params key holding this edge's weight; "" = the canonical
             convention ("w_<src>", "w_self") that `ff_integrate` /
             `branch_integrate` resolve from the feed key. Overriding it
             (weight sharing, swapping in a learned tensor) is honored
             end to end: the plan compiler and the plasticity machinery
             read `weight_key`, and the stepper aliases the canonical key
             to the override for the integrate call, so `ff_integrate`
             picks it up unchanged.
    plastic: optional `SynapseProgram` (core/plasticity.py); the edge's
             weight then learns on-chip under `plan.run` and the updated
             tensor is published in `state[node]["syn:<key>"]["w"]`.
    topology: optional compressed connectivity for this edge — an
             `EncodedTopology` instance, or a string naming one inside
             `params[node]`. The edge then executes straight from the IE
             tables (type-2 FC through the dense/sparse spikemm channels,
             sparse/conv/pool through the `spikemm_gather` channel) and no
             dense weight tensor is read. Mutually exclusive with both
             `plastic` (tables are not learnable) and a `weight` override
             (there is no dense tensor to alias).
    """

    src: str
    delay: int = 0
    weight: str = ""
    plastic: Optional["SynapseProgram"] = None  # noqa: F821
    topology: Optional[Any] = None              # EncodedTopology | params key

    def __post_init__(self):
        if not self.src:
            raise ValueError("Connection needs a source name")
        if self.delay < 0:
            raise ValueError(f"negative delay {self.delay} on connection "
                             f"from {self.src!r}")
        if self.topology is not None:
            from repro.core.topology import EncodedTopology
            if not isinstance(self.topology, (str, EncodedTopology)):
                raise TypeError(
                    f"Connection.topology must be an EncodedTopology or a "
                    f"params key, got {type(self.topology).__name__}")
            if self.plastic is not None:
                raise ValueError(
                    f"connection from {self.src!r}: topology-backed edges "
                    "cannot be plastic (IE tables are static configuration)")
            if self.weight:
                raise ValueError(
                    f"connection from {self.src!r}: topology and a weight "
                    "override are mutually exclusive")
        if self.plastic is not None:
            from repro.core.plasticity import validate_synapse_program
            validate_synapse_program(self.plastic)

    @property
    def key(self) -> str:
        """The feed-dict key — identical to the legacy string spelling, so
        integrate callables written against the old API see the same dict."""
        return f"{self.src}@{self.delay}" if self.delay else self.src

    @property
    def weight_key(self) -> str:
        if self.weight:
            return self.weight
        return "w_self" if self.src == "self" else f"w_{self.src}"

    @classmethod
    def parse(cls, spec: Union[str, "Connection"]) -> "Connection":
        """Back-compat adapter: "name" / "name@d" / "self" -> Connection."""
        if isinstance(spec, cls):
            return spec
        name, d = _parse_src(spec)
        return cls(src=name, delay=d)

    @classmethod
    def from_topology(cls, src: str, topology: Any,
                      delay: Optional[int] = None) -> "Connection":
        """Edge backed by compressed connectivity. `delay` defaults to the
        topology's own skip delay (Fig. 8c delayed-fire) when it carries
        one, else 0."""
        if delay is None:
            meta = getattr(topology, "meta", None) or {}
            delay = int(meta.get("delay", 0)) \
                if getattr(topology, "kind", "") == "skip" else 0
        return cls(src=src, delay=delay, topology=topology)


def resolve_topology(conn: Connection, node_name: str,
                     params: Dict[str, Any]):
    """The EncodedTopology a connection executes through, or None.

    A string rides as a key into `params[node]` — the topology then lives
    with the rest of the node's parameters (it is a registered pytree leaf
    with no traced children, so jit treats it as static configuration)."""
    t = conn.topology
    if t is None:
        return None
    if isinstance(t, str):
        t = params.get(node_name, {}).get(t)
        if t is None:
            raise KeyError(
                f"node {node_name!r}: connection {conn.key!r} names topology "
                f"{conn.topology!r} but params[{node_name!r}] has no such "
                "entry")
    from repro.core.topology import EncodedTopology
    if not isinstance(t, EncodedTopology):
        raise TypeError(
            f"node {node_name!r}: params[{conn.topology!r}] is "
            f"{type(t).__name__}, expected EncodedTopology")
    return t


@dataclasses.dataclass(frozen=True)
class LayerNode:
    """One population of neurons + its inbound connections.

    integrate: (params, inputs: dict[str, Array]) -> current  (INTEG stage)
    neuron:    NeuronSpec                                      (FIRE stage)
    inputs:    inbound edges — `Connection` objects or legacy strings
               ("input" = external spikes, "name@d" = skip connection
               delayed by d steps, "self" = previous timestep of this
               node); mixed tuples are fine. Normalized at construction:
               `.connections` holds the Connection view, `.inputs` the
               equivalent feed keys.
    """

    name: str
    neuron: NeuronSpec
    integrate: Callable[[Dict[str, Any], Dict[str, Array]], Array]
    inputs: Tuple[Union[str, Connection], ...] = ("input",)
    out_dim: int = 0
    connections: Tuple[Connection, ...] = dataclasses.field(init=False)

    def __post_init__(self):
        conns = tuple(Connection.parse(s) for s in self.inputs)
        keys = [c.key for c in conns]
        if len(set(keys)) != len(keys):
            raise ValueError(f"node {self.name!r}: duplicate connection "
                             f"keys {keys}")
        canon = {}
        for c in conns:
            if c.weight and canon.setdefault(
                    "w_self" if c.src == "self" else f"w_{c.src}",
                    c.weight) != c.weight:
                # the ff convention shares one weight per source, so two
                # same-source edges cannot alias it to different tensors
                raise ValueError(f"node {self.name!r}: conflicting weight "
                                 f"overrides for source {c.src!r}")
        object.__setattr__(self, "connections", conns)
        object.__setattr__(self, "inputs", tuple(keys))


def event_topology(conn: Connection, node_name: str = "",
                   params: Optional[Dict[str, Any]] = None):
    """The `FanOut` an event edge delivers through, or None. A topology
    named by a params key resolves only when `params` is given."""
    from repro.core.topology import FanOut
    t = conn.topology
    if isinstance(t, str):
        if params is None:
            return None
        t = resolve_topology(conn, node_name, params)
    return t if isinstance(t, FanOut) else None


def arrival_depth(nodes: List["LayerNode"],
                  params: Optional[Dict[str, Any]] = None) -> int:
    """Slots R of every arrival ring of the Program: the smallest power of
    two above its longest synaptic delay (0 where it has no event edge)."""
    longest = 0
    for n in nodes:
        for c in n.connections:
            topo = event_topology(c, n.name, params)
            if topo is not None:
                longest = max(longest, topo.max_delay)
    return 1 << longest.bit_length() if longest else 0


def _has_background(n: "LayerNode") -> bool:
    try:
        return n.neuron.program.background is not None
    except NotImplementedError:
        return False


def clocked_nodes(nodes: List["LayerNode"],
                  params: Optional[Dict[str, Any]] = None) -> set:
    """Names of the nodes that carry the step index: the ends of event
    edges, and nodes with a background drive."""
    out = {n.name for n in nodes if _has_background(n)}
    for n in nodes:
        for c in n.connections:
            if event_topology(c, n.name, params) is not None:
                out.add(n.name)
                out.add(n.name if c.src == "self" else c.src)
    return out


def state_dtype(dtype) -> jnp.dtype:
    """Neuron state must be float: integer spike inputs (common for encoded
    datasets) would otherwise build integer membranes that truncate every
    DIFF step. Callers pass x.dtype; ints coerce to float32."""
    dtype = jnp.dtype(dtype)
    return dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.dtype(jnp.float32)


def init_state(nodes: List[LayerNode], batch: int, dtype=jnp.float32,
               params: Optional[Dict[str, Any]] = None):
    """Neuron states, skip-delay ring buffers, and synapse (plasticity)
    state for every node. Plastic connections need `params` to seed the
    learned weight (trace shapes derive from it)."""
    dtype = state_dtype(dtype)
    state = {}
    max_delay: Dict[str, int] = {}
    for n in nodes:
        for c in n.connections:
            if c.delay:
                max_delay[c.src] = max(max_delay.get(c.src, 0), c.delay)
    depth = arrival_depth(nodes, params)
    clocked = clocked_nodes(nodes, params)
    for n in nodes:
        s = n.neuron.init_state((batch, n.out_dim), dtype)
        s["out"] = jnp.zeros((batch, n.out_dim), dtype)  # last emitted spikes
        if n.name in clocked:
            s["t"] = jnp.zeros((), jnp.int32)
            if any(event_topology(c, n.name, params) is not None
                   for c in n.connections):
                s["arr"] = jnp.zeros((depth, batch, n.out_dim), dtype)
        if n.name in max_delay:
            s["ring"] = jnp.zeros((max_delay[n.name], batch, n.out_dim), dtype)
        for c in n.connections:
            if c.plastic is None:
                continue
            if params is None:
                raise ValueError(
                    f"node {n.name!r}: connection {c.key!r} is plastic; "
                    "init_state needs params=... to seed its weight")
            from repro.core.plasticity import synapse_init
            w = params[n.name][c.weight_key]
            s[f"syn:{c.key}"] = synapse_init(c.plastic, w, batch)
        state[n.name] = s
    return state


def _node_params(n: LayerNode, params: Dict[str, Any]) -> Dict[str, Any]:
    """Node params with custom `Connection.weight` keys aliased onto the
    canonical names, so the built-in integrate conventions (`w_<src>`,
    `w_self`) transparently pick up overridden/shared weight tensors.
    Topology-backed edges alias the canonical name to the EncodedTopology
    itself — `neuron.locacc` routes it through the compressed channels."""
    p = params.get(n.name, {})
    remap = {("w_self" if c.src == "self" else f"w_{c.src}"): c.weight
             for c in n.connections if c.weight}
    topos = {("w_self" if c.src == "self" else f"w_{c.src}"):
             resolve_topology(c, n.name, params)
             for c in n.connections if c.topology is not None}
    if remap or topos:
        p = dict(p)
        for canon, key in remap.items():
            p[canon] = p[key]
        p.update(topos)
    return p


def step(nodes: List[LayerNode], params: Dict[str, Any], state: Dict[str, Any],
         x_t: Array, ext: Optional[Dict[str, Array]] = None
         ) -> Tuple[Dict[str, Any], Array]:
    """One INTEG+FIRE timestep through all nodes (in order).

    `ext` maps feed keys (e.g. "conv1", "conv1@2") to externally supplied
    per-timestep feeds — the plan compiler (`core/plan.py`) uses it to run
    a fallback *segment* of a Program whose remaining nodes were fused out
    of the time loop (their full-time outputs, delay-shifted as needed,
    arrive here one slice per step). Synapse state rides through untouched
    (plasticity is a run-granularity pass, not a stepper concern).
    """
    new_state = dict(state)
    emitted: Dict[str, Array] = {"input": x_t}
    pushes = []                 # (target node, event topology, source)
    for n in nodes:
        feeds = {}
        for c in n.connections:
            topo = event_topology(c, n.name, params)
            if topo is not None:
                pushes.append((n.name, topo, n.name if c.src == "self"
                               else c.src))
                continue
            if c.src == "self":
                feeds[c.key] = state[n.name]["out"]        # recurrent: t-1
            elif ext is not None and c.key in ext:
                feeds[c.key] = ext[c.key]                  # plan-fused source
            elif c.delay:
                feeds[c.key] = state[c.src]["ring"][c.delay - 1]  # delayed
            elif c.src in emitted:
                feeds[c.key] = emitted[c.src]              # same-timestep FF
            else:
                feeds[c.key] = state[c.src]["out"]         # not yet run: t-1
        current = n.integrate(_node_params(n, params), feeds)  # INTEG
        st = state[n.name]
        if not hasattr(current, "shape"):            # no feed at all
            current = jnp.full(st["out"].shape, current, st["out"].dtype)
        fire_state = {k: v for k, v in st.items()
                      if k not in ("out", "ring", "t", "arr")
                      and not k.startswith("syn:")}
        nparams = params.get(n.name, {}).get("neuron")
        if "t" in st:
            t = st["t"]
            if "arr" in st:                          # this step's arrivals
                current = current + st["arr"][t % st["arr"].shape[0]]
            ns, s_out = n.neuron.fire(fire_state, current, nparams,
                                      t=t)           # FIRE
        else:
            ns, s_out = n.neuron.fire(fire_state, current, nparams)
        # injected dead/stuck neuron rows (repro.core.faults): the faulty
        # rows poison everything downstream of the emission — recurrence,
        # rings, same-timestep feeds — exactly like a dead core would
        s_out = faults.perturb_output(n.name, s_out)
        ns = dict(ns)
        ns["out"] = s_out
        if "ring" in state[n.name]:
            ring = state[n.name]["ring"]
            ns["ring"] = jnp.concatenate([s_out[None], ring[:-1]], axis=0)
        for k, v in state[n.name].items():
            if k.startswith("syn:"):
                ns[k] = v
        if "t" in st:
            ns["t"] = st["t"] + 1
            if "arr" in st:                          # the slot is consumed
                ns["arr"] = st["arr"].at[st["t"] % st["arr"].shape[0]].set(0)
        new_state[n.name] = ns
        emitted[n.name] = s_out
    for name, topo, src in pushes:                   # event edges, delay >= 1
        st = new_state[name]
        new_state[name] = dict(st, arr=push_arrivals(
            st["arr"], topo, emitted[src], state[name]["t"]))
    return new_state, emitted[nodes[-1].name]


def push_arrivals(arr: Array, topo, spikes: Array, t) -> Array:
    """Every synapse of `topo` weighted by its source's spike, added into
    the arrival ring `arr` (R, batch, n_post) at slot (t + delay) mod R.
    Touches the whole table: the plain form the stepper runs; the plan's
    event segment pushes only the spiking rows (`kernels/event`)."""
    R, B = arr.shape[0], arr.shape[1]
    slot = (t + topo.delays.astype(jnp.int32)) % R          # (n_pre, F)
    w = topo.weights[None] * spikes.astype(topo.weights.dtype)[:, :, None]
    b = jnp.arange(B)[:, None, None]
    return arr.at[slot[None], b, topo.targets[None]].add(w.astype(arr.dtype))


def run(nodes: List[LayerNode], params: Dict[str, Any], x: Array,
        state: Optional[Dict[str, Any]] = None, record: Tuple[str, ...] = ()):
    """Scan the INTEG/FIRE machine over time.

    x: (T, batch, n_in) input spikes (or floats — TaiBai NCs accept both).
    Returns (final_state, outputs (T, batch, n_out), recorded dict).
    """
    if state is None:
        state = init_state(nodes, x.shape[1], x.dtype, params)

    def body(st, x_t):
        st, out = step(nodes, params, st, x_t)
        rec = {r: st[r]["out"] for r in record}
        return st, (out, rec)

    final, (outs, recs) = jax.lax.scan(body, state, x)
    return final, outs, recs
