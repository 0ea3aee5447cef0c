"""Plan-compiler parity: fused execution plans vs the stepper, plus the
block-occupancy helper the hoisted INTEG relies on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import events, plan
from repro.core.neuron import (ALIF, LI, LIF, PLIF, Decay, NeuronProgram,
                               ProgramNeuron, StateVar, Threshold)
from repro.core.snn_layers import (branch_integrate, ff_integrate,
                                   make_dhsnn_shd, make_plastic_ff,
                                   make_srnn_ecg)
from repro.kernels.spikemm.ops import block_occupancy, occupancy_fraction

KEY = jax.random.PRNGKey(0)


def _w(key, n_in, n_out, scale=0.6):
    return scale * jax.random.normal(key, (n_in, n_out), jnp.float32)


def _spikes(key, shape, rate=0.3):
    return (jax.random.uniform(key, shape) < rate).astype(jnp.float32)


def _assert_equiv(nodes, params, x, record=(), state=None, tol=1e-5):
    st1, o1, r1 = events.run(nodes, params, x, state=state, record=record)
    st2, o2, r2 = plan.run(nodes, params, x, state=state, record=record)
    np.testing.assert_allclose(o1, o2, atol=tol, rtol=tol)
    for r in record:
        np.testing.assert_allclose(r1[r], r2[r], atol=tol, rtol=tol)
    for name in st1:
        assert set(st1[name]) == set(st2[name]), name
        for k in st1[name]:
            np.testing.assert_allclose(st1[name][k], st2[name][k],
                                       atol=tol, rtol=tol,
                                       err_msg=f"{name}.{k}")
    return st1, o1


# ---------------------------------------------------------------------------
# occupancy helper (the hoisted INTEG's FINDIDX bitmap)
# ---------------------------------------------------------------------------


def test_block_occupancy_flags():
    s = jnp.zeros((4, 6))
    s = s.at[0, 1].set(1.0).at[3, 5].set(1.0)
    flags = block_occupancy(s, bm=2, bk=3)          # (2, 2) blocks
    np.testing.assert_array_equal(np.asarray(flags),
                                  [[1, 0], [0, 1]])
    # negative values count as events too (currents, not just 0/1 spikes)
    flags2 = block_occupancy(s.at[1, 4].set(-2.0), bm=2, bk=3)
    np.testing.assert_array_equal(np.asarray(flags2), [[1, 1], [0, 1]])


def test_occupancy_fraction_pads_to_blocks():
    # 5x7 with one event pads to one (128, 512) block: fraction 1.0
    s = jnp.zeros((5, 7)).at[2, 3].set(1.0)
    assert float(occupancy_fraction(s)) == 1.0
    assert float(occupancy_fraction(jnp.zeros((5, 7)))) == 0.0
    # two row-blocks, events only in the first
    s = jnp.zeros((200, 16)).at[0, 0].set(1.0)
    assert float(occupancy_fraction(s, bm=128, bk=512)) == 0.5


# ---------------------------------------------------------------------------
# plan structure
# ---------------------------------------------------------------------------


def test_compile_segments_and_lowerings():
    nodes = [
        events.LayerNode("a", LIF(), ff_integrate, ("input",), 8),
        events.LayerNode("b", ALIF(), ff_integrate, ("a",), 8),
        events.LayerNode("c", LIF(), ff_integrate, ("b", "self"), 8),
        events.LayerNode("d", LI(), ff_integrate, ("c",), 4),
    ]
    p = plan.compile_program(nodes)
    kinds = [s.kind for s in p.segments]
    assert kinds == [plan.FUSED_FF, plan.FUSED_FF, plan.FUSED_REC,
                     plan.FUSED_FF]
    assert [s.lower for s in p.segments] == [
        plan.LOWER_LIF, plan.LOWER_ALIF, plan.LOWER_LIF, plan.LOWER_LI]


def test_compile_is_structural_not_nominal():
    """Classification is driven by NeuronProgram structure alone: a
    user-space ProgramNeuron whose program matches a kernel pattern fuses;
    an extra state breaks the pattern and falls back — and the compiler
    itself never dispatches on neuron classes."""
    import inspect

    src = inspect.getsource(plan)
    assert "isinstance" not in src and "type(neuron)" not in src

    lif_like = ProgramNeuron(prog=NeuronProgram(
        states=(StateVar("m", Decay("const", 0.8)),),
        threshold=Threshold(base=0.7, on="m")))
    alif_like = ProgramNeuron(prog=NeuronProgram(
        states=(StateVar("m", Decay("const", 0.85)),
                StateVar("trace", Decay("const", 0.9), drive="spikes")),
        threshold=Threshold(base=0.9, on="m", adapt="trace", scale=0.4)))
    three_state = ProgramNeuron(prog=NeuronProgram(
        states=(StateVar("m", Decay("const", 0.85)),
                StateVar("t1", Decay("const", 0.9), drive="spikes"),
                StateVar("t2", Decay("const", 0.5), drive="spikes")),
        threshold=Threshold(base=0.9, on="m", adapt="t1", scale=0.4)))
    nodes = [
        events.LayerNode("a", lif_like, ff_integrate, ("input",), 8),
        events.LayerNode("b", alif_like, ff_integrate, ("a", "self"), 8),
        events.LayerNode("c", three_state, ff_integrate, ("b",), 4),
    ]
    p = plan.compile_program(nodes)
    assert [(s.kind, s.lower) for s in p.segments] == [
        (plan.FUSED_FF, plan.LOWER_LIF), (plan.FUSED_REC, plan.LOWER_ALIF),
        (plan.FALLBACK, "")]
    assert "no fused FIRE kernel" in p.segments[2].reason
    ks = jax.random.split(KEY, 4)
    params = {"a": {"w_input": _w(ks[0], 5, 8)},
              "b": {"w_a": _w(ks[1], 8, 8), "w_self": _w(ks[2], 8, 8, 0.3)},
              "c": {"w_b": _w(ks[3], 8, 4)}}
    _assert_equiv(nodes, params, _spikes(KEY, (14, 2, 5), rate=0.4),
                  record=("a", "b"))


def test_compile_backref_forces_whole_program_fallback():
    nodes = [
        events.LayerNode("a", LIF(), ff_integrate, ("input", "b"), 8),
        events.LayerNode("b", LIF(), ff_integrate, ("a",), 8),
    ]
    p = plan.compile_program(nodes)
    assert p.fully_fallback and len(p.segments) == 1
    ks = jax.random.split(KEY, 3)
    params = {"a": {"w_input": _w(ks[0], 5, 8), "w_b": _w(ks[1], 8, 8)},
              "b": {"w_a": _w(ks[2], 8, 8)}}
    _assert_equiv(nodes, params, _spikes(KEY, (12, 3, 5)))


def test_run_reference_is_the_stepper():
    """`run_reference` is `events.run`, bit for bit, on a program that
    does not learn; on a plastic one the forward is still the stepper's
    and the learning pass then publishes new weights (none with
    learn=False)."""
    ks = jax.random.split(KEY, 2)
    nodes = [events.LayerNode("h", LIF(), ff_integrate, ("input",), 8),
             events.LayerNode("ro", LI(), ff_integrate, ("h",), 3)]
    params = {"h": {"w_input": _w(ks[0], 5, 8)},
              "ro": {"w_h": _w(ks[1], 8, 3)}}
    x = _spikes(KEY, (12, 3, 5))
    want = events.run(nodes, params, x, record=("h",))
    got = plan.run_reference(nodes, params, x, record=("h",))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    nodes, params = make_plastic_ff(jax.random.PRNGKey(7), n_in=6,
                                    n_hidden=10)
    x = _spikes(KEY, (9, 2, 6))
    seed = np.asarray(params["hidden"]["w_input"])
    st, out, _ = plan.run_reference(nodes, params, x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(events.run(nodes, params, x)[1]))
    assert not np.array_equal(np.asarray(st["hidden"]["syn:input"]["w"]),
                              seed)
    frozen, _, _ = plan.run_reference(nodes, params, x, learn=False)
    np.testing.assert_array_equal(
        np.asarray(frozen["hidden"]["syn:input"]["w"]), seed)


# ---------------------------------------------------------------------------
# numerical parity vs the stepper
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_plan_ff_stack_matches_stepper(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    nodes = [
        events.LayerNode("h1", LIF(tau=0.85, v_th=0.7), ff_integrate,
                         ("input",), 24),
        events.LayerNode("h2", LIF(tau=0.9), ff_integrate, ("h1", "input"),
                         16),
        events.LayerNode("ro", LI(tau=0.95), ff_integrate, ("h2",), 6),
    ]
    params = {"h1": {"w_input": _w(ks[0], 10, 24)},
              "h2": {"w_h1": _w(ks[1], 24, 16), "w_input": _w(ks[2], 10, 16)},
              "ro": {"w_h2": _w(ks[3], 16, 6)}}
    x = _spikes(ks[4], (17, 3, 10))
    _assert_equiv(nodes, params, x, record=("h1", "h2"))


def test_plan_recurrent_uses_lifrec():
    ks = jax.random.split(KEY, 4)
    nodes = [
        events.LayerNode("h", LIF(tau=0.8, v_th=0.8), ff_integrate,
                         ("input", "self"), 20),
        events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 4),
    ]
    params = {"h": {"w_input": _w(ks[0], 7, 20),
                    "w_self": _w(ks[1], 20, 20, scale=0.3)},
              "ro": {"w_h": _w(ks[2], 20, 4)}}
    p = plan.compile_program(nodes)
    assert p.segments[0].kind == plan.FUSED_REC
    _assert_equiv(nodes, params, _spikes(ks[3], (19, 2, 7), rate=0.4))


def test_plan_delayed_feeds_fused_and_fallback():
    """'@d' reads of fused sources must match the stepper's ring buffers —
    both when the reader is fused and when it sits in a fallback segment."""
    ks = jax.random.split(KEY, 6)
    nodes = [
        events.LayerNode("a", LIF(tau=0.5, v_th=0.6), ff_integrate,
                         ("input",), 12),
        events.LayerNode("b", LIF(tau=0.7), ff_integrate, ("a@2",), 10),
        events.LayerNode("c", ALIF(), ff_integrate, ("a@3", "b@1"), 8),
        events.LayerNode("ro", LI(), ff_integrate, ("c", "b"), 4),
    ]
    params = {"a": {"w_input": _w(ks[0], 6, 12)},
              "b": {"w_a": _w(ks[1], 12, 10)},
              "c": {"w_a": _w(ks[2], 12, 8), "w_b": _w(ks[3], 10, 8)},
              "ro": {"w_c": _w(ks[4], 8, 4), "w_b": _w(ks[5], 10, 4)}}
    x = _spikes(KEY, (15, 2, 6), rate=0.5)
    st, _ = _assert_equiv(nodes, params, x, record=("a", "b", "c"))
    # delay shorter than ring depth and T shorter than delays still agree
    _assert_equiv(nodes, params, x[:2])
    # resuming from a mid-run state must thread ring contents through
    _assert_equiv(nodes, params, x, state=st)


def test_plan_heterogeneous_taus_plif():
    ks = jax.random.split(KEY, 3)
    neuron = PLIF()
    nodes = [
        events.LayerNode("h", neuron, ff_integrate, ("input",), 16),
        events.LayerNode("ro", LI(), ff_integrate, ("h",), 4),
    ]
    params = {"h": {"w_input": _w(ks[0], 5, 16),
                    "neuron": {"w_tau": 2.0 + jax.random.normal(ks[1], (16,))}},
              "ro": {"w_h": _w(ks[2], 16, 4)}}
    p = plan.compile_program(nodes)
    assert p.segments[0].kind == plan.FUSED_FF
    _assert_equiv(nodes, params, _spikes(KEY, (14, 3, 5), rate=0.4))


def test_plan_app_models_parity_and_zero_fallback():
    """All Program-based application-model variants agree with the stepper
    AND compile with zero fallback segments (acceptance criterion: the ECG
    SRNN's ALIF hidden layer and the SHD DHSNN's DH-LIF hidden layer now
    pattern-lower to fused kernels; BCI is not a Program — its fused LIF is
    exercised by test_events_and_apps)."""
    cases = [
        make_srnn_ecg(jax.random.PRNGKey(0), heterogeneous=True, n_hidden=24),
        make_srnn_ecg(jax.random.PRNGKey(1), heterogeneous=False, n_hidden=24),
        make_dhsnn_shd(jax.random.PRNGKey(2), n_hidden=16),
        make_dhsnn_shd(jax.random.PRNGKey(3), n_hidden=16, dendritic=False),
    ]
    for i, (nodes, params) in enumerate(cases):
        p = plan.compile_program(nodes)
        assert not any(s.kind == plan.FALLBACK for s in p.segments), \
            p.describe()
        n_in = 4 if i < 2 else 700
        x = _spikes(jax.random.PRNGKey(10 + i), (12, 2, n_in), rate=0.25)
        _assert_equiv(nodes, params, x, record=("hidden",))
    ecg = plan.compile_program(cases[0][0])
    assert ecg.segments[0] == plan.Segment(plan.FUSED_REC, ("hidden",),
                                           lower=plan.LOWER_ALIF)
    shd = plan.compile_program(cases[2][0])
    assert shd.segments[0] == plan.Segment(plan.FUSED_FF, ("hidden",),
                                           lower=plan.LOWER_DHLIF)


def test_plan_gradients_match_stepper():
    """Training through the plan path (spikemm/lif/lifrec/linrec custom
    VJPs) must give the stepper's STBP gradients."""
    nodes, params = make_srnn_ecg(jax.random.PRNGKey(4), heterogeneous=False,
                                  n_hidden=20)
    x = _spikes(KEY, (15, 3, 4), rate=0.4)

    def make_loss(run_fn):
        def loss(p):
            _, o, _ = run_fn(nodes, p, x)
            return jnp.sum(jnp.sin(o * 1.3))
        return loss

    g1 = jax.grad(make_loss(events.run))(params)
    g2 = jax.grad(make_loss(plan.run))(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4,
                                                         rtol=2e-4), g1, g2)


@pytest.mark.parametrize("variant", ["alif", "dhlif"])
def test_plan_gradients_match_stepper_alif_dhlif(variant):
    """The newly fused FIRE lowerings (alifrec kernel, DH-LIF branch
    prologue) must reproduce the stepper's STBP gradients — including the
    heterogeneous tau/rho/tau_d logits trained through sigmoid."""
    if variant == "alif":
        nodes, params = make_srnn_ecg(jax.random.PRNGKey(6),
                                      heterogeneous=True, n_hidden=20)
        x = _spikes(KEY, (15, 3, 4), rate=0.4)
    else:
        nodes, params = make_dhsnn_shd(jax.random.PRNGKey(7), n_hidden=12)
        x = _spikes(KEY, (15, 3, 700), rate=0.1)
    assert not any(s.kind == plan.FALLBACK
                   for s in plan.compile_program(nodes).segments)

    def make_loss(run_fn):
        def loss(p):
            _, o, _ = run_fn(nodes, p, x)
            return jnp.sum(jnp.sin(o * 1.3))
        return loss

    g1 = jax.grad(make_loss(events.run))(params)
    g2 = jax.grad(make_loss(plan.run))(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=3e-4,
                                                         rtol=3e-4), g1, g2)


# ---------------------------------------------------------------------------
# property test: any valid random program, plan == stepper
# ---------------------------------------------------------------------------


def _random_program(variant: int, tau: float, rho: float, beta: float,
                    with_threshold: bool) -> NeuronProgram:
    """Enumerate structurally distinct valid programs: fusable LIF/ALIF
    shapes, a non-spiking integrator, and shapes the matcher must refuse
    (subtractive-like extra traces, membrane readout of a spiking model)."""
    if not with_threshold:
        return NeuronProgram(states=(StateVar("m", Decay("const", tau)),),
                             threshold=None, reset="none", output="m")
    states = [StateVar("m", Decay("const", tau))]
    th = Threshold(base=0.8, on="m")
    output = "spikes"
    if variant == 1:          # adaptive threshold (fuses via alif)
        states.append(StateVar("tr", Decay("const", rho), drive="spikes"))
        th = Threshold(base=0.8, on="m", adapt="tr", scale=beta)
    elif variant == 2:        # spike trace NOT in the threshold (fallback)
        states.append(StateVar("tr", Decay("const", rho), drive="spikes"))
        output = "tr"
    elif variant == 3:        # membrane readout of a spiking model (fallback)
        output = "m"
    return NeuronProgram(states=tuple(states), threshold=th, output=output)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 3), st.floats(0.3, 0.95), st.floats(0.5, 0.95),
       st.floats(0.1, 1.5), st.booleans(), st.booleans())
def test_plan_matches_stepper_on_random_programs(variant, tau, rho, beta,
                                                 with_threshold, recurrent):
    """For ANY valid NeuronProgram — fused or fallback, recurrent or not —
    the compiled plan must equal the stepper bit-for-tolerance."""
    neuron = ProgramNeuron(prog=_random_program(variant, tau, rho, beta,
                                                with_threshold))
    inputs = ("input", "self") if recurrent else ("input",)
    nodes = [events.LayerNode("h", neuron, ff_integrate, inputs, 12),
             events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 4)]
    ks = jax.random.split(jax.random.PRNGKey(variant + int(tau * 997)), 3)
    params = {"h": {"w_input": _w(ks[0], 6, 12)},
              "ro": {"w_h": _w(ks[1], 12, 4)}}
    if recurrent:
        params["h"]["w_self"] = _w(ks[2], 12, 12, scale=0.3)
    x = _spikes(jax.random.fold_in(KEY, variant), (11, 2, 6), rate=0.4)
    _assert_equiv(nodes, params, x, record=("h",))


def test_plan_soma_before_branches_falls_back():
    """Regression: a dendritic program declaring the sum-driven soma BEFORE
    its branch state means the soma integrates the branches' previous-step
    values — the fused prologue always feeds the NEW values, so the matcher
    must refuse and the stepper must carry it (and agree with the plan)."""
    soma_first = ProgramNeuron(prog=NeuronProgram(
        states=(StateVar("v", Decay("const", 0.85), drive="sum:d"),
                StateVar("d", Decay("const", 0.7), branch=True)),
        threshold=Threshold(base=0.8, on="v"), n_branches=2))
    nodes = [events.LayerNode("h", soma_first, branch_integrate, ("input",),
                              10),
             events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 3)]
    p = plan.compile_program(nodes)
    assert p.segments[0].kind == plan.FALLBACK
    assert "soma declared before its branches" in p.segments[0].reason
    ks = jax.random.split(KEY, 2)
    params = {"h": {"w_input": 0.5 * jax.random.normal(ks[0], (2, 6, 10))},
              "ro": {"w_h": _w(ks[1], 10, 3)}}
    _assert_equiv(nodes, params, _spikes(KEY, (11, 2, 6), rate=0.4))


def test_plan_multi_feed_branch_integrate_falls_back():
    """Regression: the branch-hoist convention carries exactly one feed
    through w_input; a branch-tagged integrate with two inbound feeds must
    fall back instead of silently dropping the second feed."""
    def two_feed_branch(params, feeds):
        cur = 0.0
        for s in feeds.values():
            cur = cur + jnp.einsum("bi,kio->bko", s, params["w_input"])
        return cur
    two_feed_branch.hoist = "branch"

    from repro.core.neuron import DHLIF
    neuron = DHLIF(n_branches=2)
    nodes = [events.LayerNode("a", LIF(tau=0.8, v_th=0.7), ff_integrate,
                              ("input",), 6),
             events.LayerNode("h", neuron, two_feed_branch, ("input", "a"),
                              8),
             events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 3)]
    p = plan.compile_program(nodes)
    assert p.segments[1].kind == plan.FALLBACK
    assert "branch integrate with 2 feeds" in p.segments[1].reason
    ks = jax.random.split(KEY, 3)
    params = {"a": {"w_input": _w(ks[0], 6, 6)},
              "h": {"w_input": 0.4 * jax.random.normal(ks[1], (2, 6, 8)),
                    "neuron": neuron.param_init(ks[1], (8,))},
              "ro": {"w_h": _w(ks[2], 8, 3)}}
    _assert_equiv(nodes, params, _spikes(KEY, (10, 2, 6), rate=0.4))


# ---------------------------------------------------------------------------
# subtract reset: the newest structural pattern
# ---------------------------------------------------------------------------


def test_plan_subtract_reset_fuses_ff_and_matches_stepper():
    """A feed-forward LIF with reset="subtract" must pattern-lower to the
    `lif` kernel (no fallback) and agree with the stepper — forward AND
    STBP gradients (the soft-reset adjoint differs from the hard reset)."""
    ks = jax.random.split(KEY, 3)
    nodes = [
        events.LayerNode("h", LIF(tau=0.85, v_th=0.7, reset="subtract",
                                  surrogate="sigmoid", alpha=3.0),
                         ff_integrate, ("input",), 16),
        events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 4),
    ]
    p = plan.compile_program(nodes)
    assert p.segments[0] == plan.Segment(plan.FUSED_FF, ("h",),
                                         lower=plan.LOWER_LIF)
    params = {"h": {"w_input": _w(ks[0], 5, 16)},
              "ro": {"w_h": _w(ks[1], 16, 4)}}
    x = _spikes(ks[2], (14, 3, 5), rate=0.5)
    _assert_equiv(nodes, params, x, record=("h",))

    def make_loss(run_fn):
        def loss(pp):
            _, o, _ = run_fn(nodes, pp, x)
            return jnp.sum(jnp.sin(o * 1.3))
        return loss

    g1 = jax.grad(make_loss(events.run))(params)
    g2 = jax.grad(make_loss(plan.run))(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4,
                                                         rtol=2e-4), g1, g2)


def test_plan_recurrent_subtract_reset_falls_back():
    """The lifrec kernel implements the hard reset only: a self-recurrent
    subtract-reset LIF must take the stepper (and still agree)."""
    ks = jax.random.split(KEY, 3)
    nodes = [
        events.LayerNode("h", LIF(tau=0.8, v_th=0.7, reset="subtract"),
                         ff_integrate, ("input", "self"), 10),
        events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 3),
    ]
    p = plan.compile_program(nodes)
    assert p.segments[0].kind == plan.FALLBACK
    assert "recurrent subtract reset" in p.segments[0].reason
    params = {"h": {"w_input": _w(ks[0], 5, 10),
                    "w_self": _w(ks[1], 10, 10, 0.3)},
              "ro": {"w_h": _w(ks[2], 10, 3)}}
    _assert_equiv(nodes, params, _spikes(KEY, (12, 2, 5), rate=0.5))


# ---------------------------------------------------------------------------
# dtype hygiene: integer spike inputs must not build integer membranes
# ---------------------------------------------------------------------------


def test_integer_spike_input_keeps_float_state():
    """Regression: init_state(nodes, B, x.dtype) used to inherit int dtypes
    from integer spike tensors, truncating every DIFF step to zero. Both
    engines must coerce neuron state to float and agree with the float run."""
    nodes = [events.LayerNode("h", LIF(tau=0.85, v_th=0.7), ff_integrate,
                              ("input",), 10),
             events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 3)]
    ks = jax.random.split(KEY, 2)
    params = {"h": {"w_input": _w(ks[0], 5, 10)},
              "ro": {"w_h": _w(ks[1], 10, 3)}}
    x_int = (jax.random.uniform(KEY, (9, 2, 5)) < 0.4).astype(jnp.int32)
    st = events.init_state(nodes, 2, x_int.dtype)
    assert all(v.dtype == jnp.float32 for s in st.values()
               for v in s.values())
    _, o_float, _ = events.run(nodes, params, x_int.astype(jnp.float32))
    for run_fn in (events.run, plan.run):
        _, o_int, _ = run_fn(nodes, params, x_int)
        assert jnp.issubdtype(o_int.dtype, jnp.floating)
        np.testing.assert_allclose(o_int, o_float,
                                   atol=plan.CROSS_ENGINE_ATOL, rtol=1e-5)


def test_plan_runs_under_jit():
    nodes, params = make_dhsnn_shd(jax.random.PRNGKey(5), n_hidden=16,
                                   dendritic=False)
    x = _spikes(KEY, (10, 2, 700), rate=0.1)

    @jax.jit
    def f(p, xx):
        _, o, _ = plan.run(nodes, p, xx)
        return o

    _, o_ref, _ = events.run(nodes, params, x)
    np.testing.assert_allclose(f(params, x), o_ref,
                               atol=plan.CROSS_ENGINE_ATOL, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 3), st.floats(0.3, 0.95), st.booleans(),
       st.floats(0.02, 0.6))
def test_plan_outputs_identical_under_sparse_dispatch(variant, tau,
                                                      recurrent, rate):
    """Property: pinning the spikemm channel (never vs always) must not
    change ANY plan output bit — the block-sparse path only skips blocks
    that are exactly zero, so eager plan.run is bit-identical either way
    on arbitrary random programs and input densities."""
    import os

    neuron = ProgramNeuron(prog=_random_program(variant, tau, 0.8, 0.5,
                                                True))
    inputs = ("input", "self") if recurrent else ("input",)
    nodes = [events.LayerNode("h", neuron, ff_integrate, inputs, 12),
             events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 4)]
    ks = jax.random.split(jax.random.PRNGKey(variant + int(rate * 991)), 3)
    params = {"h": {"w_input": _w(ks[0], 6, 12)},
              "ro": {"w_h": _w(ks[1], 12, 4)}}
    if recurrent:
        params["h"]["w_self"] = _w(ks[2], 12, 12, scale=0.3)
    x = _spikes(jax.random.fold_in(KEY, variant), (11, 2, 6), rate=rate)
    env, prev = "REPRO_SPIKEMM_SPARSE", os.environ.get("REPRO_SPIKEMM_SPARSE")
    try:
        os.environ[env] = "never"
        _, o1, r1 = plan.run(nodes, params, x, record=("h",))
        os.environ[env] = "always"
        _, o2, r2 = plan.run(nodes, params, x, record=("h",))
    finally:
        if prev is None:
            os.environ.pop(env, None)
        else:
            os.environ[env] = prev
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(r1["h"]), np.asarray(r2["h"]))
