"""The event-driven simulation path: the iaf_psc_exp neuron program, the
per-synapse-delay fan-out encoding, the event INTEG family, the plan's
EVENT segment and the jitted `run_stream`, checked on the Potjans-
Diesmann microcircuit cut to 2 % of its neurons and in-degrees (the
`scale` option) and on hand-built nets."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import events, microcircuit as mc, plan
from repro.core.events import Connection, LayerNode
from repro.core.neuron import (Background, IafPscExp, NeuronProgram,
                               ProgramNeuron, StateVar, Decay, Threshold,
                               background_counts, make_neuron,
                               poisson_table)
from repro.core.snn_layers import ff_integrate
from repro.core.topology import FanOut, encode, encode_fanout
from repro.kernels.event.ops import build_event_tables, event_push

SCALE = 0.02
SEED = 3


@pytest.fixture(scope="module")
def circuit():
    return mc.make_microcircuit(SEED, scale=SCALE)


# ---------------------------------------------------------------------------
# a plain reference: every synapse as a raw (pre, post, w, d) list
# ---------------------------------------------------------------------------


def reference_run(nodes, params, state, T):
    """NEST's iaf_psc_exp update order, stepped in numpy-free jnp f32 with
    the synapses read from the raw lists: spikes (T, N) and final V."""
    neuron = nodes[0].neuron
    p22, p21, p11 = (np.float32(x) for x in neuron.propagators)
    theta = np.float32(neuron.v_th - neuron.e_l)
    n_ref = int(round(neuron.t_ref / neuron.dt))
    sizes = [n.out_dim for n in nodes]
    base = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    off = dict(zip([n.name for n in nodes], base))
    pre, post, w, d = [], [], [], []
    for n in nodes:
        for c in n.connections:
            src = n.name if c.src == "self" else c.src
            a, b, ww, dd = c.topology.coo_delays()
            pre.append(a + off[src])
            post.append(b + off[n.name])
            w.append(ww)
            d.append(dd.astype(np.int32))
    pre, post, w, d = (np.concatenate(x) for x in (pre, post, w, d))
    cat = lambda k: jnp.concatenate([state[n.name][k][0] for n in nodes])
    v, i, ref = cat("v"), cat("i"), cat("ref")
    R = state[nodes[0].name]["arr"].shape[0]
    ring = jnp.concatenate([state[n.name]["arr"][:, 0] for n in nodes], 1)
    t0 = int(state[nodes[0].name]["t"])
    tabs = [params[n.name]["neuron"]["bg_cdf"] for n in nodes]
    keys = [params[n.name]["neuron"]["bg_key"] for n in nodes]
    w_bg = np.float32(neuron.bg_weight)
    spikes = []
    for t in range(t0, t0 + T):
        arrivals = ring[t % R]
        ring = ring.at[t % R].set(0.0)
        bg = jnp.concatenate([
            jnp.sum(jax.random.uniform(jax.random.fold_in(k, t), (m,))[None]
                    >= tab, axis=0) for k, tab, m in zip(keys, tabs, sizes)])
        held = ref > 0
        v = jnp.where(held, v, p22 * v + p21 * i)
        i = p11 * i + (arrivals + w_bg * bg.astype(jnp.float32))
        s = v >= theta
        v = jnp.where(s, 0.0, v)
        ref = jnp.where(s, n_ref, jnp.where(held, ref - 1, ref))
        fired = np.asarray(s)[pre]
        ring = ring.at[(t + d[fired]) % R, post[fired]].add(w[fired])
        spikes.append(np.asarray(s))
    return np.stack(spikes), np.asarray(v)


def test_stream_matches_reference_and_stepper(circuit):
    """3 jitted chunks of `run_stream` give the reference's spikes, step
    for step, and its V. Tolerance: both sides run the same float32
    operations in the same order except the order in which arrivals sum
    into a ring slot; a few ulps of a current of ~1e3 pA, times P21 ~4e-4
    mV/pA, decaying since, stay below 1e-6 mV — 1e-4 mV is a wide margin
    that a dropped or doubled synapse (>= 0.03 mV) cannot hide in."""
    nodes, params, st0 = circuit
    T = 30
    got, final = [], None
    for st, sp in plan.run_stream(nodes, params,
                                  [jnp.zeros((T, 1, 0))] * 3, state=st0,
                                  spikes="list"):
        got.append(sp)
        final = jax.tree_util.tree_map(jnp.copy, st)
    ref_s, ref_v = reference_run(nodes, params, st0, 3 * T)
    steps = np.concatenate([g.step for g in got])
    neurons = np.concatenate([g.neuron for g in got])
    want_t, want_n = np.nonzero(ref_s)
    np.testing.assert_array_equal(steps, want_t)
    np.testing.assert_array_equal(neurons, want_n)
    assert sum(g.counts["spikes"] for g in got) == len(steps) > 100
    v = np.concatenate([np.asarray(final[n.name]["v"][0]) for n in nodes])
    np.testing.assert_allclose(v, ref_v, atol=1e-4, rtol=0)
    # the interpreted stepper on the same stream
    _, _, rec = events.run(nodes, params, jnp.zeros((3 * T, 1, 0)), st0,
                           record=tuple(n.name for n in nodes))
    dense = np.concatenate([np.asarray(rec[n.name][:, 0]) for n in nodes], 1)
    np.testing.assert_array_equal(dense > 0, ref_s)


def test_one_shot_run_equals_stream_and_stepper(circuit):
    nodes, params, st0 = circuit
    names = tuple(n.name for n in nodes)
    s1, out1, rec1 = plan.run(nodes, params, jnp.zeros((40, 1, 0)), st0,
                              record=names)
    s2, out2, rec2 = events.run(nodes, params, jnp.zeros((40, 1, 0)), st0,
                                record=names)
    outs = [o for _, o in plan.run_stream(
        nodes, params, [jnp.zeros((25, 1, 0)), jnp.zeros((15, 1, 0))],
        state=st0)]
    for n in names:
        np.testing.assert_array_equal(np.asarray(rec1[n]),
                                      np.asarray(rec2[n]))
        np.testing.assert_allclose(np.asarray(s1[n]["v"]),
                                   np.asarray(s2[n]["v"]), atol=1e-4)
    np.testing.assert_array_equal(np.concatenate([np.asarray(o)
                                                  for o in outs]),
                                  np.asarray(out1))


def test_arrival_ring_crosses_chunk_borders_bit_for_bit(circuit):
    """The ring each chunk hands the next, split back into the members'
    (R, B, n) `arr` states: 3 chunks of `run_stream` leave every member
    the ring of one `plan.run` over the same steps, bit for bit."""
    nodes, params, st0 = circuit
    T = 20
    final = None
    for st, _ in plan.run_stream(nodes, params, [jnp.zeros((T, 1, 0))] * 3,
                                 state=st0):
        final = jax.tree_util.tree_map(jnp.copy, st)
    one, _, _ = plan.run(nodes, params, jnp.zeros((3 * T, 1, 0)), st0)
    live = 0
    for n in nodes:
        got = np.asarray(final[n.name]["arr"])
        assert got.shape == st0[n.name]["arr"].shape
        np.testing.assert_array_equal(got, np.asarray(one[n.name]["arr"]))
        live += int(np.count_nonzero(got))
    assert live > 0                       # currents in flight at the end


def test_stream_donates_its_state_and_keeps_the_callers(circuit):
    nodes, params, st0 = circuit
    it = plan.run_stream(nodes, params, [jnp.zeros((5, 1, 0))] * 2,
                         state=st0, spikes="list")
    first, _ = next(it)
    next(it)
    leaf = first[nodes[0].name]["v"]
    assert leaf.is_deleted() or jax.default_backend() == "cpu"
    assert not st0[nodes[0].name]["v"].is_deleted()


def test_microcircuit_compiles_to_one_event_segment_under_strict(
        circuit, monkeypatch):
    monkeypatch.setenv("REPRO_STRICT", "1")
    nodes, _, _ = circuit
    p = plan.compile_program(nodes)
    assert [s.kind for s in p.segments] == [plan.EVENT]
    assert p.segments[0].names == mc.POPULATIONS
    assert not p.fully_fallback
    from repro import analysis
    diags = analysis.check_nodes(nodes, params=circuit[1])
    assert not [d for d in diags if d.code in ("TB103", "TB104", "TB105",
                                               "TB201", "TB203", "TB211")]


@pytest.mark.parametrize("maker,want", [
    ("dhsnn", "fused_ff[hidden]:dhlif -> fused_ff[readout]:li"),
    ("srnn", "fused_rec[hidden]:alif -> fused_ff[readout]:li"),
    ("srnn_homogeneous", "fused_rec[hidden]:lif -> fused_ff[readout]:li"),
])
def test_shd_and_ecg_plans_are_unchanged(maker, want):
    from repro.core import snn_layers as L
    make = {"dhsnn": L.make_dhsnn_shd, "srnn": L.make_srnn_ecg,
            "srnn_homogeneous": lambda k: L.make_srnn_ecg(
                k, heterogeneous=False)}[maker]
    nodes, _ = make(jax.random.PRNGKey(0))
    assert plan.compile_program(nodes).describe() == want


# ---------------------------------------------------------------------------
# delays, refractoriness, the cap
# ---------------------------------------------------------------------------


def _pair(delay, with_bg=False):
    """Neuron a (driven by a forced V) -> neuron b through one synapse of
    `delay` steps and a large weight."""
    neuron = IafPscExp()
    syn = encode_fanout(np.array([0]), np.array([0]),
                        np.array([1000.0], np.float32), np.array([delay]),
                        1, 1)
    nodes = [LayerNode("a", neuron, ff_integrate, (), 1),
             LayerNode("b", neuron, ff_integrate,
                       (Connection("a", topology=syn),), 1)]
    return nodes, {"a": {}, "b": {}}


@pytest.mark.parametrize("d,t0", [(1, 0), (7, 5)])
def test_spike_arrives_exactly_delay_steps_later(d, t0):
    """d = 1, and d = R - 1 from step 5, whose slot (5 + 7) mod 8 wraps."""
    nodes, params = _pair(d)
    R = events.arrival_depth(nodes)
    assert d == 1 or (d == R - 1 and t0 + d >= R)
    state = events.init_state(nodes, 1, jnp.float32, params)
    state["a"]["v"] = jnp.full((1, 1), 20.0)      # fires at the first step
    for n in ("a", "b"):
        state[n]["t"] = jnp.int32(t0)
    T = d + 4
    for run in (plan.run, events.run):
        _, _, rec = run(nodes, params, jnp.zeros((T, 1, 0)), state,
                        record=("a", "b"))
        a = np.asarray(rec["a"])[:, 0, 0]
        assert a[0] == 1 and a[1:].sum() == 0
    st, cur = state, []
    for t in range(T):
        st, _ = events.step(nodes, params, st, jnp.zeros((1, 0)))
        cur.append(float(st["b"]["i"][0, 0]))
    assert cur[:d] == [0.0] * d and cur[d] == 1000.0


def test_refractory_holds_v_while_current_integrates():
    neuron = IafPscExp()
    prog = neuron.program
    assert prog.refractory == 20
    st = {"v": jnp.full((1, 1), 16.0), "i": jnp.zeros((1, 1)),
          "ref": jnp.zeros((1, 1), jnp.int32)}
    st, s = neuron.fire(st, jnp.zeros((1, 1)))
    assert float(s[0, 0]) == 1.0 and float(st["v"][0, 0]) == 0.0
    vs, is_ = [], []
    for k in range(25):
        st, s = neuron.fire(st, jnp.full((1, 1), 500.0))
        vs.append(float(st["v"][0, 0]))
        is_.append(float(st["i"][0, 0]))
    assert vs[:20] == [0.0] * 20 and vs[20] > 0.0
    assert all(b > a for a, b in zip(is_, is_[1:]))


def test_burst_past_the_cap_drops_nothing_and_counts_the_pass():
    rng = np.random.default_rng(0)
    n, m = 200, 3000
    topo = encode_fanout(rng.integers(0, n, m), rng.integers(0, n, m),
                         rng.normal(size=m).astype(np.float32),
                         rng.integers(1, 6, m), n, n)
    g = build_event_tables([(n, [(topo, 0)])], n)
    ring = jnp.zeros((8 * n,))                   # 8 slots of n, flat
    spikes = jnp.zeros((n,)).at[:50].set(1.0)    # 50 spikes, cap 8
    out, stats = event_push(ring, spikes, jnp.int32(3), g.tables,
                            shift=g.shift, width=g.width, cap=8, force=True)
    ref, rstats = event_push(ring, spikes, jnp.int32(3), g.tables,
                             shift=g.shift, width=g.width, cap=8)
    assert out.shape == ring.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    pre, post, w, d = topo.coo_delays()
    fired = pre < 50
    assert int(stats[0]) == 50 and int(stats[2]) == 1
    assert int(stats[1]) == int(fired.sum())
    np.testing.assert_allclose(float(out.sum()), float(w[fired].sum()),
                               rtol=1e-5)
    assert list(np.asarray(stats)) == list(np.asarray(rstats))
    # a quiet step takes one pass and counts none
    _, stats = event_push(ring, spikes.at[8:].set(0.0), jnp.int32(3),
                          g.tables, shift=g.shift, width=g.width, cap=8,
                          force=True)
    assert int(stats[2]) == 0


def _rows_of(counts, n_post, seed):
    """A FanOut whose row j holds counts[j] random synapses."""
    rng = np.random.default_rng(seed)
    pre = np.repeat(np.arange(len(counts)), counts)
    m = pre.shape[0]
    return encode_fanout(pre, rng.integers(0, n_post, m),
                         rng.normal(size=m).astype(np.float32),
                         rng.integers(1, 6, m), len(counts), n_post)


@pytest.mark.parametrize("count", [0, 1, 7, 8, 9])
def test_window_table_holds_each_row_whole_and_aligned(count):
    """Windows of 8 entries: neuron 2 holds `count` synapses over two
    edges (a row of 0, 1, width - 1, width, width + 1), between neighbours
    of other lengths. Its row starts on a window of its own, reads back
    the edges' live entries in edge order, and pads with weight 0. The
    table's rows follow the entries and neurons alone."""
    width, n = 8, 6
    first = [5, 0, count // 2, 8, 0, 11]
    second = [3, 0, count - count // 2, 0, 2, 9]
    edges = [(_rows_of(first, n, 1), 0), (_rows_of(second, 4, 2), n)]
    g = build_event_tables([(n, edges)], n + 4, width=width)
    tab = g.tables
    cnt = np.asarray(tab.row_count)
    segs = np.asarray(tab.seg_count)
    start = np.asarray(tab.win_start)
    windows = np.asarray(tab.windows)
    np.testing.assert_array_equal(cnt, np.add(first, second))
    np.testing.assert_array_equal(segs, -(-cnt // width))
    # consecutive rows, each from a window boundary, then spare windows
    np.testing.assert_array_equal(start, np.cumsum(segs) - segs)
    assert windows.shape == (-(-cnt.sum() // width) + n, 2 * width)
    j = 2
    rows = windows[start[j]:start[j] + segs[j]]
    pk = rows[:, :width].reshape(-1)
    w = rows[:, width:].reshape(-1).view(np.float32)
    want_pk, want_w = [], []
    for topo, off in edges:
        c = int(np.asarray(topo.counts)[j])
        d = np.asarray(topo.delays)[j, :c].astype(np.int32)
        t = np.asarray(topo.targets)[j, :c].astype(np.int32) + off
        want_pk.append((d << g.shift) | t)
        want_w.append(np.asarray(topo.weights)[j, :c])
    np.testing.assert_array_equal(pk[:count], np.concatenate(want_pk))
    np.testing.assert_array_equal(w[:count], np.concatenate(want_w))
    # padding lanes, and the spare windows, deliver nothing
    spare = windows[segs.sum():]
    assert not w[count:].any() and not spare[:, width:].any()
    assert (pk[count:] == 1 << g.shift).all()
    assert (spare[:, :width] == 1 << g.shift).all()


@pytest.mark.parametrize("cap", [8, 16])
def test_push_matches_the_oracle_over_passes(cap):
    """A burst of 50 spikes at windows of 8 takes several passes at `cap`
    8 and 16; the flat ring and the stats equal the oracle's."""
    rng = np.random.default_rng(4)
    n, m = 200, 3000
    topo = encode_fanout(rng.integers(0, n, m), rng.integers(0, n, m),
                         rng.normal(size=m).astype(np.float32),
                         rng.integers(1, 6, m), n, n)
    g = build_event_tables([(n, [(topo, 0)])], n, width=8)
    ring = jax.random.normal(jax.random.PRNGKey(0), (8 * n,))
    spikes = jnp.zeros((n,)).at[::4].set(1.0)
    segs = int(np.asarray(g.tables.seg_count)[::4].sum())
    assert segs > 2 * cap                          # three passes or more
    got = event_push(ring, spikes, jnp.int32(6), g.tables, shift=g.shift,
                     width=g.width, cap=cap, force=True)
    want = event_push(ring, spikes, jnp.int32(6), g.tables, shift=g.shift,
                      width=g.width, cap=cap)
    assert got[0].shape == want[0].shape == ring.shape
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-5)
    assert list(np.asarray(got[1])) == list(np.asarray(want[1]))


# ---------------------------------------------------------------------------
# the IR and the encoding
# ---------------------------------------------------------------------------


def test_same_seed_and_step_give_the_same_poisson_counts():
    """The program's background draws against an independent inverse of
    the Poisson CDF, computed here from the float64 pmf."""
    lam = 2100 * 8.0 * 0.1e-3
    key = jax.random.PRNGKey(11)
    pmf = [math.exp(-lam) * lam ** k / math.factorial(k) for k in range(40)]
    cdf = np.float32(np.cumsum(pmf))
    params = {"bg_cdf": jnp.asarray(poisson_table(np.full(300, lam))),
              "bg_key": key}
    for t in (0, 7, 100_000):
        got = np.asarray(background_counts(Background(1.0), params, t,
                                           (1, 300)))[0]
        u = np.asarray(jax.random.uniform(jax.random.fold_in(key, t),
                                          (300,)))
        want = np.searchsorted(cdf, u, side="right")
        np.testing.assert_array_equal(got, want)
    assert abs(np.mean(np.asarray(background_counts(
        Background(1.0), params, 3, (400, 300)))) - lam) < 0.02


def test_state_drive_and_gain_follow_declaration_order():
    prog = NeuronProgram(
        states=(StateVar("v", Decay("const", 0.5), drive="state:i",
                         gain=2.0),
                StateVar("i", Decay("const", 0.25))),
        threshold=Threshold(base=100.0))
    n = ProgramNeuron(prog=prog)
    st = {"v": jnp.ones((1, 1)), "i": jnp.full((1, 1), 4.0)}
    st, _ = n.fire(st, jnp.full((1, 1), 8.0))
    assert float(st["v"][0, 0]) == 0.5 + 2.0 * 4.0      # the old i
    assert float(st["i"][0, 0]) == 0.25 * 4.0 + 8.0


@pytest.mark.parametrize("bad", ["own", "unknown", "refractory"])
def test_ir_validation(bad):
    kw = {"own": dict(states=(StateVar("v", Decay(), drive="state:v"),),
                      threshold=Threshold()),
          "unknown": dict(states=(StateVar("v", Decay(), drive="state:x"),),
                          threshold=Threshold()),
          "refractory": dict(states=(StateVar("v", Decay()),),
                             output="v", refractory=3)}[bad]
    with pytest.raises(ValueError):
        ProgramNeuron(prog=NeuronProgram(**kw))


def test_iaf_psc_exp_is_registered_with_nest_propagators():
    n = make_neuron("iaf_psc_exp")
    p22, p21, p11 = n.propagators
    assert p22 == pytest.approx(math.exp(-0.01))
    assert p11 == pytest.approx(math.exp(-0.2))
    assert p21 == pytest.approx(10 * 0.5 / (250 * 9.5)
                                * (math.exp(-0.01) - math.exp(-0.2)))
    assert mc.psc_per_psp(250.0, 10.0, 0.5) * 0.15 == pytest.approx(87.81,
                                                                    abs=0.01)


def test_fanout_encoding_round_trips():
    rng = np.random.default_rng(1)
    pre, post = rng.integers(0, 30, 200), rng.integers(0, 40, 200)
    w, d = rng.normal(size=200).astype(np.float32), rng.integers(1, 9, 200)
    topo = encode((pre, post, w, d), kind="fanout", n_pre=30, n_post=40)
    assert isinstance(topo, FanOut) and topo.meta["n_connections"] == 200
    assert (topo.min_delay, topo.max_delay) == (int(d.min()), int(d.max()))
    a, b, ww, dd = topo.coo_delays()
    got = sorted(zip(a.tolist(), b.tolist(), ww.tolist(), dd.tolist()))
    assert got == sorted(zip(pre.tolist(), post.tolist(), w.tolist(),
                             d.tolist()))
    dense = np.zeros((30, 40), np.float32)
    np.add.at(dense, (pre, post), w)
    np.testing.assert_allclose(topo.dense_equivalent(), dense, atol=1e-6)
    rows = FanOut.from_rows(topo.targets, topo.weights, topo.delays,
                            topo.counts, 40)
    assert (rows.min_delay, rows.max_delay, rows.meta["n_connections"]) == \
        (topo.min_delay, topo.max_delay, 200)
    with pytest.raises(TypeError):
        topo.apply_spikes(jnp.zeros((1, 30)))
    with pytest.raises(ValueError):
        encode_fanout(pre, post, w, np.zeros(200, int), 30, 40)
    from repro import analysis
    assert not analysis.check_topology(topo)


def test_event_group_that_cannot_lower_steps_the_whole_program():
    """A member that also reads a feed through no event edge: the group
    cannot lower and the program runs the stepper (TB211)."""
    syn = encode_fanout(np.array([0]), np.array([0]),
                        np.array([1.0], np.float32), np.array([2]), 1, 1)
    nodes = [LayerNode("a", IafPscExp(), ff_integrate,
                       ("input", Connection("b", topology=syn)), 1),
             LayerNode("b", IafPscExp(), ff_integrate,
                       (Connection("a", topology=syn),), 1)]
    p = plan.compile_program(nodes)
    assert p.fully_fallback and p.segments[0].codes == ("TB211",)
    params = {"a": {"w_input": jnp.ones((3, 1))}, "b": {}}
    x = jnp.ones((6, 1, 3)) * 10.0
    s1, o1, _ = plan.run(nodes, params, x)
    s2, o2, _ = events.run(nodes, params, x)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_stream_chunks_are_spanned_with_their_counters(circuit, tmp_path):
    """Under a profiler trace each chunk logs `sim.chunk` with the chunk's
    counters as ids, and `sim.readback` inside it."""
    from repro.serve import metrics
    nodes, params, st0 = circuit
    metrics.clear_spans()
    with jax.profiler.trace(str(tmp_path)):
        got = [sp for _, sp in plan.run_stream(
            nodes, params, [jnp.zeros((10, 1, 0))] * 2, state=st0,
            spikes="list")]
    recs = metrics.spans()
    chunks = [r for r in recs if r.name == "sim.chunk"]
    backs = [r for r in recs if r.name == "sim.readback"]
    assert [r.ids["chunk"] for r in chunks] == [0, 1] and len(backs) == 2
    for r, b, sp in zip(chunks, backs, got):
        assert r.start_ns <= b.start_ns <= b.end_ns <= r.end_ns
        assert {k: r.ids[k] for k in sp.counts} == sp.counts
        assert sp.counts["spikes"] == len(sp.step)


def test_fused_patterns_refuse_the_new_ir_features():
    """A LIF-shaped program with a refractory period, a background, a
    state drive or a gain is no `lif` kernel's: outside an EVENT group it
    steps through the interpreter (TB206) and agrees with the stepper."""
    base = dict(states=(StateVar("v", Decay("const", 0.9)),),
                threshold=Threshold(base=1.0))
    progs = [NeuronProgram(**base, refractory=3),
             NeuronProgram(states=(StateVar("v", Decay("const", 0.9),
                                            gain=2.0),),
                           threshold=Threshold(base=1.0))]
    for prog in progs:
        assert plan._match_fire_pattern(prog)[1] == "TB206"
        nodes = [LayerNode("h", ProgramNeuron(prog=prog), ff_integrate,
                           ("input",), 6)]
        params = {"h": {"w_input": 0.4 * jnp.ones((3, 6))}}
        x = (jax.random.uniform(jax.random.PRNGKey(1), (12, 2, 3)) < 0.6
             ).astype(jnp.float32)
        assert plan.compile_program(nodes).fully_fallback
        _, o1, _ = plan.run(nodes, params, x)
        _, o2, _ = events.run(nodes, params, x)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert plan._match_fire_pattern(IafPscExp(bg_weight=1.0).program)[1] \
        == "TB206"
