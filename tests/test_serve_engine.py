"""Streaming serve engine: session isolation (property-tested), the LRU
state cache, ragged scheduling/backpressure, fault degradation without
cross-session contamination, long-prompt admission, and TB5xx checks.

The load-bearing invariant: a session's output trajectory and final state
are bit-identical whether it runs alone, interleaved with strangers, is
evicted to host and restored mid-stream, or reuses a retired session's
pool row — because the engine always executes the SAME fixed-shape
resident step (free slots on the scratch row, fresh sessions reading the
initial-state row) and spill/restore is a pure device<->host copy.
"""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import analysis
from repro.core import events, faults
from repro.core.neuron import ALIF, LI, LIF
from repro.core.snn_layers import (ff_integrate, make_dhsnn_shd,
                                   make_plastic_ff)
from repro.kernels.incidents import clear, incidents
from repro.serve import (EngineConfig, Histogram, Scheduler, ServeMetrics,
                         Session, StateCache, make_engine)
from repro.core.plan import state_nbytes
from repro.serve.engine import _resident_step
from repro.serve.sessions import (SCRATCH, Row, StatePool,
                                  gather_rows)
from repro.serve.loop import Request, ServeConfig, _admit, generate
from tests._faults import forced_pallas

W, C = 8, 4        # one cohort shape for the whole module: jit once
# the engine's slots per cohort: C sessions side by side, or one at a time
CAPACITY = {"batched": C, "one_slot": 1}


@functools.lru_cache(maxsize=None)
def _model():
    return make_dhsnn_shd(jax.random.PRNGKey(0), n_in=12, n_hidden=16,
                          n_out=5, dendritic=False)


@functools.lru_cache(maxsize=None)
def _plastic_model():
    return make_plastic_ff(jax.random.PRNGKey(1), n_in=10, n_hidden=12,
                           n_out=3)


@functools.lru_cache(maxsize=None)
def _delay_model():
    """Delay rings (pool axis 1) and an ALIF beside the plain leaves."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)

    def w(k, i, o):
        return jax.random.normal(k, (i, o)) / np.sqrt(i)

    nodes = [
        events.LayerNode("a", LIF(tau=0.5, v_th=0.6), ff_integrate,
                         ("input",), 10),
        events.LayerNode("c", ALIF(), ff_integrate, ("a@3",), 8),
        events.LayerNode("ro", LI(), ff_integrate, ("c", "a@1"), 5),
    ]
    params = {"a": {"w_input": w(ks[0], 12, 10)},
              "c": {"w_a": w(ks[1], 10, 8)},
              "ro": {"w_c": w(ks[2], 8, 5), "w_a": w(ks[3], 10, 5)}}
    return nodes, params


MODELS = {"dhsnn": _model, "delays": _delay_model}


def _streams(n, T, seed, n_in=12):
    rng = np.random.default_rng(seed)
    return {f"s{i}": (rng.random((T, n_in)) < 0.25).astype(np.float32)
            for i in range(n)}


def _run(kind, data, cache_bytes=None, learn=False, model=None, drip=0,
         window=W):
    nodes, params = model if model is not None else _model()
    eng = make_engine(nodes, params,
                      EngineConfig(window=window, capacity=CAPACITY[kind],
                                   cache_bytes=cache_bytes, learn=learn))
    for sid in data:
        eng.open(sid)
    if drip:     # ragged arrival: submit in drip-sized chunks, stepping
        offs = {sid: 0 for sid in data}
        while any(offs[s] < len(data[s]) for s in data):
            for sid, x in data.items():
                if offs[sid] < len(x):
                    eng.submit(sid, x[offs[sid]:offs[sid] + drip])
                    offs[sid] += drip
                    if offs[sid] >= len(x):
                        eng.close(sid)
            eng.step()
    else:
        for sid, x in data.items():
            assert eng.submit(sid, x)
            eng.close(sid)
    eng.drain()
    return eng


def _leaves(state):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(state)]


# ---------------------------------------------------------------------------
# isolation property
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)    # the first example compiles
@given(st.integers(min_value=1, max_value=3),
       st.integers(min_value=3, max_value=40),
       st.integers(min_value=0, max_value=10_000))
def test_isolation_solo_interleaved_evict_restore(n_extra, T, seed):
    """Session s0's outputs and final state: solo == interleaved with
    strangers == interleaved under a 1-byte cache (every window evicts
    and restores) — exact equality, in cohorts of C slots and of one."""
    data = _streams(1 + n_extra, T, seed)
    for kind in CAPACITY:
        solo = _run(kind, {"s0": data["s0"]})
        inter = _run(kind, data)
        evict = _run(kind, data, cache_bytes=1)
        if len(data) > 1:
            assert evict.metrics.cache_evictions > 0
        np.testing.assert_array_equal(solo.outputs("s0"),
                                      inter.outputs("s0"))
        np.testing.assert_array_equal(solo.outputs("s0"),
                                      evict.outputs("s0"))
        for a, b in zip(_leaves(solo.state_of("s0")),
                        _leaves(inter.state_of("s0"))):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(_leaves(solo.state_of("s0")),
                        _leaves(evict.state_of("s0"))):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kind", sorted(CAPACITY))
def test_ragged_arrival_matches_bulk(kind):
    """Dripping uneven chunks through interleaved steps produces exactly
    the bulk-submitted trajectory (scheduling never changes numerics)."""
    data = _streams(5, 37, seed=7)
    bulk = _run(kind, data)
    drip = _run(kind, data, drip=5)
    for sid in data:
        np.testing.assert_array_equal(bulk.outputs(sid), drip.outputs(sid))
        assert bulk.outputs(sid).shape == (37, 5)
        assert bulk.finished(sid)


def test_learned_weights_stay_per_session():
    """With learn=True each session owns its synapse weights: s0's learned
    tensors are bit-identical solo vs interleaved, and differ from a
    stranger fed different spikes (no batch-summed contamination)."""
    model = _plastic_model()
    data = _streams(3, 20, seed=3, n_in=10)
    solo = _run("batched", {"s0": data["s0"]}, learn=True, model=model)
    inter = _run("batched", data, learn=True, model=model)

    def syn_w(eng, sid):
        st_ = eng.state_of(sid)
        return {(n, k): np.asarray(v["w"]) for n, d in st_.items()
                for k, v in d.items() if k.startswith("syn:")}

    ws, wi = syn_w(solo, "s0"), syn_w(inter, "s0")
    assert ws, "plastic model produced no syn entries"
    for k in ws:
        np.testing.assert_array_equal(ws[k], wi[k])
    k = next(iter(ws))
    assert not np.array_equal(syn_w(inter, "s1")[k], wi[k])
    np.testing.assert_array_equal(solo.outputs("s0"), inter.outputs("s0"))


def test_compile_fail_degrades_without_contamination():
    """Under a forced-pallas compile_fail world the engine serves through
    the dispatch fallback chain (incidents recorded, nothing raises) and
    the isolation invariant still holds inside that world."""
    data = _streams(3, 19, seed=11)
    clear()
    with forced_pallas(), faults.inject("compile_fail:kernels=*"):
        solo = _run("batched", {"s0": data["s0"]})
        inter = _run("batched", data)
    assert incidents(kind="dispatch"), "fallback chain never engaged"
    np.testing.assert_array_equal(solo.outputs("s0"), inter.outputs("s0"))
    assert inter.outputs("s1").shape == (19, 5)


def test_fault_world_retraces_resident_step():
    """The resident-step cache keys on the ambient fault spec: a clean
    run, then the same shapes inside faults.inject, must not replay the
    clean executable (the fault world traces fresh and records dispatch
    incidents). window=5 is unique to this test so neither world's step
    was traced by an earlier test."""
    data = _streams(2, 16, seed=5)
    _run("batched", data, window=5)             # populate clean-world cache
    clear()
    with forced_pallas(), faults.inject("compile_fail:kernels=*"):
        _run("batched", data, window=5)
    assert incidents(kind="dispatch")


# ---------------------------------------------------------------------------
# state pool
# ---------------------------------------------------------------------------


def _row_of(eng, sid):
    return eng.cache.get(sid).index


@pytest.mark.parametrize("kind", sorted(CAPACITY))
def test_retired_row_reused_matches_solo(kind):
    """A row freed at retire goes to the next open; that session starts
    from the initial state, not the stale row, and its outputs and final
    state equal a solo run's bit for bit."""
    data = _streams(2, 29, seed=13)
    solo = _run(kind, {"s1": data["s1"]})
    nodes, params = _model()
    eng = make_engine(nodes, params,
                      EngineConfig(window=W, capacity=CAPACITY[kind]))
    eng.open("s0")
    row = _row_of(eng, "s0")
    eng.submit("s0", data["s0"])
    eng.close("s0")
    eng.drain()
    eng.retire("s0")
    eng.open("s1")
    assert _row_of(eng, "s1") == row
    eng.submit("s1", data["s1"])
    eng.close("s1")
    eng.drain()
    np.testing.assert_array_equal(solo.outputs("s1"), eng.outputs("s1"))
    for a, b in zip(_leaves(solo.state_of("s1")), _leaves(eng.state_of("s1"))):
        np.testing.assert_array_equal(a, b)
    assert eng.stats()["fresh_resets"] == 2


def _syn_w(state):
    return {(n, k): np.asarray(v["w"]) for n, d in state.items()
            for k, v in d.items() if k.startswith("syn:")}


def test_retired_learning_row_reused_starts_from_seeded_weights():
    """With learn=True a session's synapse entries are pool rows too: a
    session that takes over a retired learner's row starts from the
    seeded weights, not its predecessor's learned ones, and its outputs
    and learned weights equal a solo run's bit for bit."""
    model = _plastic_model()
    nodes, params = model
    data = _streams(2, 20, seed=19, n_in=10)
    solo = _run("batched", {"s1": data["s1"]}, learn=True, model=model)
    eng = make_engine(nodes, params,
                      EngineConfig(window=W, capacity=C, learn=True))
    eng.open("s0")
    row = _row_of(eng, "s0")
    eng.submit("s0", data["s0"])
    eng.close("s0")
    eng.drain()
    learned = _syn_w(eng.state_of("s0"))
    seeded = _syn_w(events.init_state(nodes, 1, jnp.float32, params))
    assert learned.keys() == seeded.keys() and seeded
    assert any(not np.array_equal(learned[k], seeded[k]) for k in seeded)
    eng.retire("s0")
    eng.open("s1")
    assert _row_of(eng, "s1") == row
    for k, w in _syn_w(eng.state_of("s1")).items():
        np.testing.assert_array_equal(w, seeded[k])
    eng.submit("s1", data["s1"])
    eng.close("s1")
    eng.drain()
    np.testing.assert_array_equal(solo.outputs("s1"), eng.outputs("s1"))
    for a, b in zip(_leaves(solo.state_of("s1")), _leaves(eng.state_of("s1"))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_fresh_reset_equals_init_state(model):
    """The step's gather reads INIT for a fresh slot, whatever the row
    holds: leaf for leaf `events.init_state`; a slot that is not fresh
    reads its row."""
    nodes, params = MODELS[model]()
    eng = make_engine(nodes, params, EngineConfig(window=W, capacity=C))
    pool = eng.pool
    r = pool.claim()
    rng = np.random.default_rng(0)
    junk = jax.tree_util.tree_map(
        lambda v: rng.standard_normal(v.shape).astype(v.dtype),
        pool.read(Row(r, True)))
    pool.write(r, junk)
    got = gather_rows(pool.leaves, jnp.array([r, r, SCRATCH]),
                      jnp.array([True, False, True]))
    init = events.init_state(nodes, 1, jnp.float32, params)
    for node, nd in init.items():
        for k, v in nd.items():
            ax = 1 if k == "ring" else 0
            slot = [jax.lax.index_in_dim(got[node][k], j, axis=ax)
                    for j in range(3)]
            np.testing.assert_array_equal(np.asarray(slot[0]), np.asarray(v))
            np.testing.assert_array_equal(np.asarray(slot[2]), np.asarray(v))
            np.testing.assert_array_equal(np.asarray(slot[1]),
                                          junk[node][k])


@pytest.mark.parametrize("model", sorted(MODELS))
def test_pool_growth_keeps_isolation(model):
    """Past the first 4 x capacity rows the pool doubles, once per
    doubling, and a session's trajectory is the solo one across it."""
    data = _streams(40, 19, seed=17)
    m = MODELS[model]()
    solo = _run("batched", {"s0": data["s0"]}, model=m)
    many = _run("batched", data, model=m)
    assert solo.stats()["pool_rows"] == 4 * C
    assert solo.metrics.pool_grows == 0
    assert many.stats()["pool_rows"] == 16 * C     # 16 -> 32 -> 64 rows
    assert many.metrics.pool_grows == 2
    np.testing.assert_array_equal(solo.outputs("s0"), many.outputs("s0"))
    for a, b in zip(_leaves(solo.state_of("s0")),
                    _leaves(many.state_of("s0"))):
        np.testing.assert_array_equal(a, b)


def test_flat_step_argument_count_is_independent_of_capacity():
    """The flat step's operands flatten to the same number of arrays at 8
    and 64 slots: the pool, rows and fresh vectors, not a tree a slot."""
    nodes, params = _model()
    counts = []
    for cap in (8, 64):
        eng = make_engine(nodes, params, EngineConfig(window=W, capacity=cap))
        step = _resident_step(eng.nodes, eng.plan, False, None)
        rows, fresh = np.zeros(cap, np.int32), np.ones(cap, bool)
        x = jnp.zeros((W, cap, 12), jnp.float32)
        lowered = step.lower(params, eng.pool.leaves, rows, fresh, x)
        counts.append(len(jax.tree_util.tree_leaves(lowered.args_info)))
    n_pool = len(jax.tree_util.tree_leaves(eng.pool.leaves))
    assert counts[0] == counts[1] == \
        len(jax.tree_util.tree_leaves(params)) + n_pool + 3


# ---------------------------------------------------------------------------
# state cache (over pool rows, with and without a learning session's
# synapse entry)
# ---------------------------------------------------------------------------


def _toy_state(v, syn=False, n=4):
    """A one-session state; with `syn`, a synapse entry beside it whose
    weight has no batch axis (the pool gives it a row axis of its own)."""
    st = {"node": {"mem": jnp.full((1, n), float(v), jnp.float32),
                   "out": jnp.zeros((1, n), jnp.float32)}}
    if syn:
        st["node"]["syn:input"] = {
            "w": jnp.full((n, 3), float(v), jnp.float32),
            "pre": jnp.full((1, n), -float(v), jnp.float32)}
    return st


def _toy_cache(syn, budget, metrics=None):
    pool = StatePool(_toy_state(0, syn), capacity=1, metrics=metrics)
    return StateCache(pool, budget_bytes=budget, metrics=metrics)


def _put(cache, sid, v, syn):
    i = cache.store.claim()
    cache.store.write(i, _toy_state(v, syn))
    cache.put(sid, Row(i, False))


def _assert_row(cache, row, v, syn):
    """The row reads back as `_toy_state(v, syn)`, leaf for leaf, shapes
    included, on the device."""
    got = cache.store.read(row)
    want = _toy_state(v, syn)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert isinstance(g, jax.Array)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


ROWS = {"plain": False, "syn": True}


@pytest.mark.parametrize("row", sorted(ROWS))
def test_cache_lru_spills_and_restores_bit_identical(row):
    syn = ROWS[row]
    m = ServeMetrics()
    nbytes = state_nbytes(_toy_state(0, syn))
    assert nbytes == (2 * 4 * 4 if not syn else 2 * 4 * 4 + 4 * 12 + 4 * 4)
    cache = _toy_cache(syn, 2 * nbytes, m)
    for i in range(3):
        _put(cache, f"s{i}", i + 1, syn)
    assert cache.hot_bytes <= 2 * nbytes
    assert cache.spilled == ("s0",)             # LRU spilled first
    assert m.cache_evictions == 1
    _assert_row(cache, cache.get("s0"), 1, syn)  # restore refreshes recency
    assert not cache.is_spilled("s0")
    assert m.cache_misses == 1 and m.cache_restores == 1
    assert "s0" not in cache.spilled and len(cache.spilled) == 1


@pytest.mark.parametrize("row", sorted(ROWS))
def test_cache_unbounded_never_spills(row):
    syn = ROWS[row]
    cache = _toy_cache(syn, None)
    for i in range(20):
        _put(cache, f"s{i}", i, syn)
    assert cache.spilled == ()
    assert cache.hot_bytes == 20 * state_nbytes(_toy_state(0, syn))
    for i in range(20):
        _assert_row(cache, cache.get(f"s{i}"), i, syn)


@pytest.mark.parametrize("row", sorted(ROWS))
def test_cache_budget_smaller_than_one_session_still_serves(row):
    syn = ROWS[row]
    m = ServeMetrics()
    cache = _toy_cache(syn, 1, m)
    _put(cache, "a", 1, syn)
    _put(cache, "b", 2, syn)
    got = cache.get("a")                        # the active session stays hot
    assert not cache.is_spilled("a") and cache.is_spilled("b")
    _assert_row(cache, got, 1, syn)
    _assert_row(cache, cache.get("b"), 2, syn)  # b restored, a spilled
    assert cache.is_spilled("a") and m.cache_restores == 2


def test_cache_rejects_bad_budget():
    with pytest.raises(ValueError, match="budget_bytes"):
        StateCache(StatePool(_toy_state(0), capacity=1), budget_bytes=0)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def test_scheduler_round_robin_fairness():
    """A firehose session cannot starve a trickle session: with one slot
    per cohort, service alternates between two ready sessions."""
    sch = Scheduler(window=4, n_in=2)
    for sid in ("hog", "meek"):
        sch.open(sid)
        sch.submit(sid, np.ones((40, 2), np.float32))
    order = [sch.next_cohort(1)[0][0].sid for _ in range(6)]
    assert order == ["hog", "meek", "hog", "meek", "hog", "meek"]


def test_scheduler_backpressure_rejects_and_records():
    clear()
    m = ServeMetrics()
    sch = Scheduler(window=4, n_in=2, queue_limit=2, metrics=m)
    sch.open("a")
    assert sch.submit("a", np.ones((8, 2), np.float32))      # 2 windows
    assert not sch.submit("a", np.ones((8, 2), np.float32))  # would be 4
    assert m.chunks_rejected == 1 and m.chunks_admitted == 1
    evs = incidents(kind="serve")
    assert evs and evs[-1].stage == "admission"
    # draining frees budget (one window per session per cohort — fair
    # round-robin — so two cohorts empty the queue); the submit now fits
    sch.next_cohort(4)
    sch.next_cohort(4)
    assert sch.pending_windows == 0
    assert sch.submit("a", np.ones((8, 2), np.float32))


def test_scheduler_partial_tail_only_after_close():
    sch = Scheduler(window=8, n_in=3)
    s = sch.open("a")
    sch.submit("a", np.ones((5, 3), np.float32))
    assert not s.ready(8)                       # open partial: not runnable
    assert sch.next_cohort(4) == []
    sch.close("a")
    cohort = sch.next_cohort(4)
    assert len(cohort) == 1
    _, x, valid = cohort[0]
    assert x.shape == (8, 3) and valid == 5
    np.testing.assert_array_equal(x[5:], np.zeros((3, 3)))
    assert s.finished


def test_session_rejects_bad_chunks():
    s = Session(sid="x", n_in=4)
    with pytest.raises(ValueError, match="chunk shape"):
        s.push(np.ones((3, 5), np.float32))
    s.closed = True
    with pytest.raises(ValueError, match="closed"):
        s.push(np.ones((3, 4), np.float32))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_exact_quantiles():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    assert h.quantile(0.50) == 50.0
    assert h.quantile(0.99) == 99.0
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["max"] == 100.0
    assert snap["mean"] == pytest.approx(50.5)


def test_engine_stats_snapshot():
    data = _streams(2, 16, seed=2)
    eng = _run("batched", data)
    snap = eng.stats()
    assert snap["steps_run"] == 32 and snap["sessions_finished"] == 2
    assert 0.0 < snap["occupancy"]["mean"] <= 1.0
    assert snap["cache_hit_rate"] == 1.0        # unbounded cache: all hot


# ---------------------------------------------------------------------------
# long-prompt admission (loop.py)
# ---------------------------------------------------------------------------


def test_admit_truncates_to_most_recent_tokens():
    scfg = ServeConfig(max_seq=16)
    r = Request(np.arange(30, dtype=np.int32), max_new=4)
    clear()
    admitted, notes = _admit([r], scfg)
    assert len(admitted[0].prompt) == 12        # max_seq - max_new
    np.testing.assert_array_equal(admitted[0].prompt,
                                  np.arange(18, 30, dtype=np.int32))
    assert "truncated" in notes[0]
    assert any(e.stage == "admission" for e in incidents(kind="serve"))


def test_admit_reject_policy_refuses():
    scfg = ServeConfig(max_seq=16, long_prompt="reject")
    r = Request(np.arange(30, dtype=np.int32), max_new=4)
    admitted, notes = _admit([r], scfg)
    assert admitted == [None] and "rejected" in notes[0]
    short = Request(np.arange(5, dtype=np.int32), max_new=4)
    admitted, notes = _admit([short], scfg)
    assert admitted[0] is short and notes[0] is None


def test_generate_raises_on_rejected_prompt_before_model_runs():
    scfg = ServeConfig(max_seq=8, long_prompt="reject")
    reqs = [Request(np.arange(30, dtype=np.int32), max_new=4)]
    with pytest.raises(ValueError, match="refused at admission"):
        generate(None, SimpleNamespace(family="dense"), reqs, scfg)


def test_admit_unknown_policy_raises():
    scfg = ServeConfig(max_seq=8, long_prompt="shrug")
    with pytest.raises(ValueError, match="long_prompt"):
        _admit([Request(np.arange(30, dtype=np.int32))], scfg)


# ---------------------------------------------------------------------------
# TB5xx serve checks
# ---------------------------------------------------------------------------


def test_check_serve_clean_for_sane_deployment():
    nodes, params = _model()
    fp = analysis.session_footprint(nodes, params)
    cfg = EngineConfig(window=W, capacity=C, queue_limit=32,
                       cache_bytes=C * fp)
    assert analysis.check_serve(nodes, params, cfg) == []


def test_check_serve_flags_budget_and_queue():
    nodes, params = _model()
    fp = analysis.session_footprint(nodes, params)
    cfg = SimpleNamespace(window=W, capacity=C, queue_limit=C - 1,
                          cache_bytes=fp - 1)
    codes = {d.code for d in analysis.check_serve(nodes, params, cfg)}
    assert {"TB501", "TB504"} <= codes
    cfg = SimpleNamespace(window=W, capacity=C, queue_limit=None,
                          cache_bytes=C * fp - 1)
    codes = {d.code for d in analysis.check_serve(nodes, params, cfg)}
    assert "TB502" in codes and "TB501" not in codes


def test_check_serve_flags_invalid_config():
    nodes, params = _model()
    cfg = SimpleNamespace(window=0, capacity=-1, queue_limit=0,
                          cache_bytes=0)
    diags = analysis.check_serve(nodes, params, cfg)
    assert {d.code for d in diags} == {"TB505"}
    assert len(diags) == 4 and all(d.severity == "error" for d in diags)
