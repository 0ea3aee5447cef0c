"""Connection API + synapse-program plan lowering.

Covers the string->Connection back-compat adapter (old "name@d"/"self"
micro-syntax parses to identical Connections; mixed old/new Programs run
bit-identically), the plastic-connection learning pass under `plan.run`
(fused `stdp_seq` lowering vs the per-step `synapse_step` reference —
weights AND traces), modulator plumbing, and a hypothesis property test
over random valid SynapsePrograms (fused vs fallback weight-trajectory
parity)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import events, plan, plasticity
from repro.core.events import Connection
from repro.core.neuron import LI, LIF, Decay
from repro.core.plasticity import (SynapseProgram, TraceVar, UpdateTerm,
                                   pair_stdp, synapse_run, triplet_stdp)
from repro.core.snn_layers import ff_integrate, make_plastic_ff

KEY = jax.random.PRNGKey(0)


def _w(key, n_in, n_out, scale=0.6):
    return scale * jax.random.normal(key, (n_in, n_out), jnp.float32)


def _spikes(key, shape, rate=0.35):
    return (jax.random.uniform(key, shape) < rate).astype(jnp.float32)


# ---------------------------------------------------------------------------
# the back-compat adapter: strings are a thin spelling of Connections
# ---------------------------------------------------------------------------


def test_connection_parse_equals_explicit():
    assert Connection.parse("x@2") == Connection("x", delay=2)
    assert Connection.parse("self") == Connection("self")
    assert Connection.parse("input") == Connection("input")
    # parse is idempotent on Connections
    c = Connection("a", delay=3)
    assert Connection.parse(c) is c
    # key round-trips the legacy spelling
    assert Connection("x", 2).key == "x@2"
    assert Connection("self").key == "self"
    assert Connection("hidden").key == "hidden"
    # canonical weight keys
    assert Connection("hidden").weight_key == "w_hidden"
    assert Connection("self").weight_key == "w_self"
    assert Connection("x", weight="w_shared").weight_key == "w_shared"


def test_connection_validation():
    with pytest.raises(ValueError, match="source"):
        Connection("")
    with pytest.raises(ValueError, match="delay"):
        Connection("x", delay=-1)
    with pytest.raises(ValueError, match="at least one update term"):
        Connection("x", plastic=SynapseProgram(traces=(), terms=()))


def test_layernode_normalizes_mixed_inputs():
    node = events.LayerNode("h", LIF(), ff_integrate,
                            ("x@2", Connection("y", delay=1), "self"), 8)
    assert node.connections == (Connection("x", 2), Connection("y", 1),
                                Connection("self"))
    assert node.inputs == ("x@2", "y@1", "self")
    with pytest.raises(ValueError, match="duplicate"):
        events.LayerNode("h", LIF(), ff_integrate,
                         ("x@2", Connection("x", delay=2)), 8)


def test_mixed_string_and_connection_programs_run_bit_identically():
    """The same topology spelled as strings vs Connection objects must be
    indistinguishable: identical plans, identical outputs, identical state
    — under both engines."""
    ks = jax.random.split(KEY, 6)
    old = [
        events.LayerNode("a", LIF(tau=0.5, v_th=0.6), ff_integrate,
                         ("input",), 12),
        events.LayerNode("b", LIF(tau=0.7), ff_integrate, ("a@2", "self"),
                         10),
        events.LayerNode("ro", LI(), ff_integrate, ("b", "a@1"), 4),
    ]
    new = [
        events.LayerNode("a", LIF(tau=0.5, v_th=0.6), ff_integrate,
                         (Connection("input"),), 12),
        events.LayerNode("b", LIF(tau=0.7), ff_integrate,
                         (Connection("a", delay=2), Connection("self")), 10),
        events.LayerNode("ro", LI(), ff_integrate,
                         (Connection("b"), Connection("a", delay=1)), 4),
    ]
    params = {"a": {"w_input": _w(ks[0], 6, 12)},
              "b": {"w_a": _w(ks[1], 12, 10), "w_self": _w(ks[2], 10, 10, 0.3)},
              "ro": {"w_b": _w(ks[3], 10, 4), "w_a": _w(ks[4], 12, 4)}}
    x = _spikes(ks[5], (13, 2, 6), rate=0.5)
    assert (plan.compile_program(old).describe()
            == plan.compile_program(new).describe())
    for run_fn in (events.run, plan.run):
        st1, o1, r1 = run_fn(old, params, x, record=("a", "b"))
        st2, o2, r2 = run_fn(new, params, x, record=("a", "b"))
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        for r in r1:
            np.testing.assert_array_equal(np.asarray(r1[r]),
                                          np.asarray(r2[r]))
        for name in st1:
            for k in st1[name]:
                np.testing.assert_array_equal(np.asarray(st1[name][k]),
                                              np.asarray(st2[name][k]))


# ---------------------------------------------------------------------------
# plastic connections under plan.run
# ---------------------------------------------------------------------------


def _reference_syn(nodes, params, x, conn_node, rule, mod=None, pre_src=None):
    """Per-step reference: realized spike trains through the stepper, then
    synapse_run (scan of synapse_step)."""
    record = tuple({conn_node} | ({pre_src} if pre_src else set()))
    _, out, recs = events.run(nodes, params, x, record=record)
    pre = x if pre_src is None else recs[pre_src]
    return synapse_run(rule, params[conn_node]["w_input"], pre,
                       recs[conn_node], mod=mod)


def _force_step(compiled: plan.Plan) -> plan.Plan:
    """Force every plastic lowering through the per-step fallback."""
    return dataclasses.replace(compiled, plastic=tuple(
        dataclasses.replace(p, lower=plan.SYN_STEP, reason="forced")
        for p in compiled.plastic))


@pytest.mark.parametrize("rule_name", ["pair_stdp", "triplet_stdp",
                                       "reward_stdp", "accumulated_spike"])
def test_builtin_rules_plan_lowered_match_reference(rule_name):
    """Acceptance: all four built-in rules lower to the fused stdp_seq
    family, run under plan.run WITHOUT falling back to the full stepper,
    and match the per-step reference on weights + traces."""
    rule = plasticity.make_synapse(rule_name)
    nodes, params = make_plastic_ff(jax.random.PRNGKey(3), n_in=9,
                                    n_hidden=14, rule=rule)
    x = _spikes(jax.random.PRNGKey(4), (11, 3, 9))
    T, B = x.shape[:2]
    compiled = plan.compile_program(nodes)
    assert not any(s.kind == plan.FALLBACK for s in compiled.segments), \
        compiled.describe()
    assert compiled.plastic == (plan.PlasticLower("hidden", "input",
                                                  plan.SYN_SEQ),)
    mod = None
    if rule_name == "reward_stdp":
        mod = jax.random.uniform(jax.random.PRNGKey(5), (T,))
    elif rule_name == "accumulated_spike":
        mod = jnp.zeros((T, B, 14)).at[-1].set(
            jax.random.normal(jax.random.PRNGKey(6), (B, 14)))
    st, _, _ = plan.run(nodes, params, x, plan=compiled, mod=mod)
    ref = _reference_syn(nodes, params, x, "hidden", rule, mod=mod)
    syn = st["hidden"]["syn:input"]
    assert set(syn) == set(ref)
    for k in ref:
        np.testing.assert_allclose(np.asarray(syn[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5, err_msg=k)
    if rule_name == "pair_stdp":
        assert float(jnp.linalg.norm(syn["w"] - params["hidden"]["w_input"])
                     ) > 1e-3                     # actually learned


def test_plastic_on_inter_layer_and_delayed_connection():
    """Plasticity on a node-to-node delayed edge: the pre train the rule
    sees must be the delay-shifted feed the stepper delivered."""
    rule = pair_stdp()
    ks = jax.random.split(KEY, 4)
    nodes = [
        events.LayerNode("a", LIF(tau=0.6, v_th=0.6), ff_integrate,
                         ("input",), 10),
        events.LayerNode("h", LIF(tau=0.8, v_th=0.7), ff_integrate,
                         (Connection("a", delay=2, plastic=rule),), 8),
        events.LayerNode("ro", LI(), ff_integrate, ("h",), 3),
    ]
    params = {"a": {"w_input": _w(ks[0], 5, 10)},
              "h": {"w_a": _w(ks[1], 10, 8)},
              "ro": {"w_h": _w(ks[2], 8, 3)}}
    x = _spikes(ks[3], (12, 2, 5), rate=0.5)
    compiled = plan.compile_program(nodes)
    assert compiled.plastic == (plan.PlasticLower("h", "a@2", plan.SYN_SEQ),)
    st, _, _ = plan.run(nodes, params, x, plan=compiled)
    # reference: shift the realized 'a' train by the delay (cold start)
    _, _, recs = events.run(nodes, params, x, record=("a", "h"))
    pre = jnp.concatenate([jnp.zeros((2,) + recs["a"].shape[1:]),
                           recs["a"][:-2]], axis=0)
    ref = synapse_run(rule, params["h"]["w_a"], pre, recs["h"])
    for k in ref:
        np.testing.assert_allclose(np.asarray(st["h"]["syn:a@2"][k]),
                                   np.asarray(ref[k]), atol=1e-5, rtol=1e-5)


def test_plastic_learning_identical_under_stepper_engine():
    """The stepper reference (`plan.run_reference`) learns the same
    trajectories as the plan (the learning pass is the same code)."""
    nodes, params = make_plastic_ff(jax.random.PRNGKey(7), n_in=6,
                                    n_hidden=10)
    x = _spikes(KEY, (9, 2, 6))
    st_plan, o_plan, _ = plan.run(nodes, params, x)
    st_step, o_step, _ = plan.run_reference(nodes, params, x)
    np.testing.assert_allclose(np.asarray(o_plan), np.asarray(o_step),
                               atol=1e-5)
    for k in st_plan["hidden"]["syn:input"]:
        np.testing.assert_allclose(
            np.asarray(st_plan["hidden"]["syn:input"][k]),
            np.asarray(st_step["hidden"]["syn:input"][k]),
            atol=1e-5, rtol=1e-5)


def test_learn_false_freezes_and_apply_learned_merges():
    nodes, params = make_plastic_ff(jax.random.PRNGKey(8), n_in=6,
                                    n_hidden=10)
    x = _spikes(KEY, (9, 2, 6))
    st_frozen, _, _ = plan.run(nodes, params, x, learn=False)
    np.testing.assert_array_equal(
        np.asarray(st_frozen["hidden"]["syn:input"]["w"]),
        np.asarray(params["hidden"]["w_input"]))
    st, _, _ = plan.run(nodes, params, x)
    learned = plasticity.apply_learned(nodes, params, st)
    np.testing.assert_array_equal(
        np.asarray(learned["hidden"]["w_input"]),
        np.asarray(st["hidden"]["syn:input"]["w"]))
    # untouched entries survive the merge
    assert learned["readout"]["w_hidden"] is params["readout"]["w_hidden"]
    # chunked-online: the next window's forward sees the learned weight
    o1 = plan.run(nodes, learned, x, learn=False)[1]
    o0 = plan.run(nodes, params, x, learn=False)[1]
    assert float(jnp.max(jnp.abs(o1 - o0))) > 0


def test_learning_does_not_perturb_stbp_gradients():
    """The weight update is an optimizer-like write (stop_gradient): grads
    of the forward loss must be identical with learning on, off, and under
    the stepper."""
    nodes, params = make_plastic_ff(jax.random.PRNGKey(9), n_in=6,
                                    n_hidden=10)
    x = _spikes(KEY, (9, 2, 6))

    def loss(p, learn):
        _, o, _ = plan.run(nodes, p, x, learn=learn)
        return jnp.sum(jnp.sin(o * 1.3))

    g_on = jax.grad(lambda p: loss(p, True))(params)
    g_off = jax.grad(lambda p: loss(p, False))(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 g_on, g_off)

    def stepper_loss(p):
        _, o, _ = events.run(nodes, p, x)
        return jnp.sum(jnp.sin(o * 1.3))

    g_ref = jax.grad(stepper_loss)(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4,
                                                         rtol=2e-4),
                 g_on, g_ref)


def test_plastic_run_under_jit():
    nodes, params = make_plastic_ff(jax.random.PRNGKey(10), n_in=6,
                                    n_hidden=10)
    compiled = plan.compile_program(nodes)
    x = _spikes(KEY, (8, 2, 6))

    @jax.jit
    def f(p, xx):
        st, o, _ = plan.run(nodes, p, xx, plan=compiled)
        return o, st["hidden"]["syn:input"]["w"]

    o_jit, w_jit = f(params, x)
    st, o, _ = plan.run(nodes, params, x, plan=compiled)
    np.testing.assert_allclose(np.asarray(o_jit), np.asarray(o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(w_jit),
                               np.asarray(st["hidden"]["syn:input"]["w"]),
                               atol=1e-5)


def test_learned_decay_rule_hoists_fused_and_matches_interpreter():
    """Learned per-synapse trace decays no longer force the per-step
    fallback: a sigmoid-resolved decay plane hoists through linrec exactly
    like a constant, so the matcher keeps the rule on the fused stdp_seq
    path — and the fused weight trajectory matches the per-step
    interpreter bit-for-bit (within cross-engine tolerance)."""
    rule = SynapseProgram(
        traces=(TraceVar("x", "pre", Decay("learned", 0.9, "tau_x")),),
        terms=(UpdateTerm(0.02, pre=("x",), post=("spikes",)),))
    nodes, params = make_plastic_ff(jax.random.PRNGKey(11), n_in=6,
                                    n_hidden=10, rule=rule)
    # heterogeneous decay logits: each presynaptic trace gets its own tau
    params["hidden"]["syn:input"] = {
        "tau_x": jnp.linspace(-1.5, 2.0, 6, dtype=jnp.float32)}
    compiled = plan.compile_program(nodes)
    assert compiled.plastic[0].lower == plan.SYN_SEQ
    x = _spikes(KEY, (9, 2, 6))
    st, _, _ = plan.run(nodes, params, x, plan=compiled)
    # interpreter reference with the same learned-decay params
    _, _, recs = events.run(nodes, params, x, record=("hidden",))
    ref = plasticity.synapse_run(rule, params["hidden"]["w_input"], x,
                                 recs["hidden"],
                                 params=params["hidden"]["syn:input"])
    for k in ref:
        np.testing.assert_allclose(np.asarray(st["hidden"]["syn:input"][k]),
                                   np.asarray(ref[k]), atol=1e-5, rtol=1e-5,
                                   err_msg=k)
    # and the forced per-step fallback agrees with the fused path
    st2, _, _ = plan.run(nodes, params, x, plan=_force_step(compiled))
    for k in ref:
        np.testing.assert_allclose(np.asarray(st["hidden"]["syn:input"][k]),
                                   np.asarray(st2["hidden"]["syn:input"][k]),
                                   atol=1e-5, rtol=1e-5, err_msg=k)


def test_custom_weight_key_honored_by_both_engines():
    """Regression: Connection(weight=...) overrides used to work in the
    fused plan but crash (or silently diverge) in the stepper, whose
    ff_integrate hard-codes w_<src>. The stepper now aliases the canonical
    key to the override, so both engines read the same tensor — and
    apply_learned round-trips through it."""
    ks = jax.random.split(KEY, 4)
    rule = pair_stdp()
    nodes = [
        events.LayerNode("h", LIF(tau=0.8, v_th=0.6), ff_integrate,
                         (Connection("input", weight="w_shared",
                                     plastic=rule),), 10),
        events.LayerNode("ro", LI(tau=0.9), ff_integrate, ("h",), 3),
    ]
    params = {"h": {"w_shared": _w(ks[0], 6, 10)},
              "ro": {"w_h": _w(ks[1], 10, 3)}}
    x = _spikes(ks[2], (11, 2, 6))
    st1, o1, _ = events.run(nodes, params, x)
    st2, o2, _ = plan.run(nodes, params, x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    # the learning pass reads and writes the override tensor
    learned = plasticity.apply_learned(nodes, params, st2)
    np.testing.assert_array_equal(
        np.asarray(learned["h"]["w_shared"]),
        np.asarray(st2["h"]["syn:input"]["w"]))
    assert float(jnp.linalg.norm(learned["h"]["w_shared"]
                                 - params["h"]["w_shared"])) > 1e-3
    with pytest.raises(ValueError, match="conflicting weight"):
        events.LayerNode("h", LIF(), ff_integrate,
                         (Connection("a", weight="w_one"),
                          Connection("a", delay=1, weight="w_two")), 4)


def test_plastic_backref_learns_from_delivered_train():
    """Regression: a plastic back-reference (source ordered after the node,
    read at t-1 by the stepper) used to learn from the source's same-step
    train. The learned weight must match synapse_run on the actually
    delivered (one-step-shifted) pre spikes."""
    rule = pair_stdp()
    ks = jax.random.split(KEY, 4)
    nodes = [
        events.LayerNode("a", LIF(tau=0.6, v_th=0.6), ff_integrate,
                         ("input", Connection("b", plastic=rule)), 10),
        events.LayerNode("b", LIF(tau=0.8, v_th=0.7), ff_integrate,
                         ("a",), 8),
    ]
    params = {"a": {"w_input": _w(ks[0], 5, 10), "w_b": _w(ks[1], 8, 10)},
              "b": {"w_a": _w(ks[2], 10, 8)}}
    x = _spikes(ks[3], (12, 2, 5), rate=0.5)
    compiled = plan.compile_program(nodes)
    assert compiled.fully_fallback          # backref -> whole-program stepper
    st, _, _ = plan.run(nodes, params, x, plan=compiled)
    _, _, recs = events.run(nodes, params, x, record=("a", "b"))
    pre = jnp.concatenate([jnp.zeros((1,) + recs["b"].shape[1:]),
                           recs["b"][:-1]], axis=0)      # delivered: t-1
    ref = synapse_run(rule, params["a"]["w_b"], pre, recs["a"])
    for k in ref:
        np.testing.assert_allclose(np.asarray(st["a"]["syn:b"][k]),
                                   np.asarray(ref[k]), atol=1e-5, rtol=1e-5,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# property test: random valid SynapsePrograms, fused == per-step fallback
# ---------------------------------------------------------------------------


def _random_rule(n_traces, n_terms, tau_a, tau_b, amp, variant):
    """Enumerate structurally diverse valid programs: pre/post traces with
    mixed before/after reads, multi-factor terms, optional mod gating."""
    sources = ["pre", "post"]
    traces = tuple(
        TraceVar(f"t{i}", sources[(i + variant) % 2],
                 Decay("const", tau_a if i % 2 == 0 else tau_b),
                 scale=1.0 if i % 2 == 0 else 0.7,
                 update="before" if (i + variant) % 3 else "after")
        for i in range(n_traces))
    pre_traces = [t.name for t in traces if t.source == "pre"]
    post_traces = [t.name for t in traces if t.source == "post"]
    terms = []
    for j in range(n_terms):
        pre = ("spikes",) if not pre_traces or j % 2 == 0 else \
            (pre_traces[j % len(pre_traces)],)
        post = ("spikes",) if not post_traces else \
            ("spikes", post_traces[j % len(post_traces)]) if j % 3 == 2 \
            else (post_traces[j % len(post_traces)],)
        if variant == 2 and j == 0:
            post = post + ("mod",)
        terms.append(UpdateTerm(amp * (-1.0 if j % 2 else 1.0),
                                pre=pre, post=post))
    return SynapseProgram(traces=traces, terms=tuple(terms),
                          w_min=-0.8, w_max=0.8)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 3), st.integers(1, 4), st.floats(0.3, 0.95),
       st.floats(0.5, 0.99), st.floats(0.005, 0.05), st.integers(0, 2))
def test_random_synapse_programs_fused_matches_fallback(
        n_traces, n_terms, tau_a, tau_b, amp, variant):
    """For ANY valid SynapseProgram the fused stdp_seq lowering and the
    per-step fallback must produce the same weight trajectory endpoint and
    final traces."""
    rule = plasticity.validate_synapse_program(
        _random_rule(n_traces, n_terms, tau_a, tau_b, amp, variant))
    nodes, params = make_plastic_ff(
        jax.random.PRNGKey(n_traces * 7 + n_terms), n_in=7, n_hidden=9,
        rule=rule)
    x = _spikes(jax.random.fold_in(KEY, variant + n_terms), (10, 2, 7))
    mod = (jax.random.uniform(jax.random.PRNGKey(variant), (10,))
           if variant == 2 else None)
    compiled = plan.compile_program(nodes)
    assert compiled.plastic[0].lower == plan.SYN_SEQ, compiled.describe()
    st_seq, _, _ = plan.run(nodes, params, x, plan=compiled, mod=mod)
    st_step, _, _ = plan.run(nodes, params, x, plan=_force_step(compiled),
                             mod=mod)
    a, b = st_seq["hidden"]["syn:input"], st_step["hidden"]["syn:input"]
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   atol=1e-5, rtol=1e-5, err_msg=k)


def test_oversized_program_refused_by_matcher():
    rule = _random_rule(4, 4, 0.9, 0.8, 0.01, 0)
    big = dataclasses.replace(rule, terms=rule.terms + (
        UpdateTerm(0.001, pre=("spikes",), post=("spikes",)),))
    lower, code, why = plan._match_synapse_pattern(big)
    assert lower == plan.SYN_STEP and "update terms" in why
    assert code == "TB210"


def test_describe_names_plastic_lowerings():
    nodes, _ = make_plastic_ff(jax.random.PRNGKey(12), rule=triplet_stdp())
    desc = plan.compile_program(nodes).describe()
    assert "learn hidden.input:stdp_seq" in desc
