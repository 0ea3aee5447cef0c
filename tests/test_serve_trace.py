"""Spans inside the serve engine: kept only while a profiler trace runs,
nested and ordered as `BatchedEngine.step` runs its phases, the queue
waits the scheduler records, their twins in the profiler's own trace, and
the log's bound."""

import functools
import glob
import os

import jax
import numpy as np
import pytest

from repro import spanlog
from repro.core.snn_layers import make_dhsnn_shd
from repro.serve import EngineConfig, make_engine, metrics

W, C, N_IN = 8, 4, 12
CHILDREN = ["serve.schedule", "serve.gather", "serve.assemble",
            "serve.dispatch", "serve.readback", "serve.scatter"]


@functools.lru_cache(maxsize=None)
def _model():
    return make_dhsnn_shd(jax.random.PRNGKey(0), n_in=N_IN, n_hidden=16,
                          n_out=5, dendritic=False)


def _engine():
    nodes, params = _model()
    return make_engine(nodes, params, EngineConfig(window=W, capacity=C))


def _chunk(steps, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((steps, N_IN)) < 0.25).astype(np.float32)


@pytest.fixture
def traced(tmp_path):
    """Run the body under a profiler trace; yields the trace directory."""
    metrics.clear_spans()
    with jax.profiler.trace(str(tmp_path)):
        yield str(tmp_path)


def _by_name(recs, name):
    return [r for r in recs if r.name == name]


def _children(recs, step):
    """The spans other than `step` that carry its `step` id and lie
    inside its bounds, in order of start."""
    return sorted((r for r in recs if r.name != "serve.step"
                   and r.ids.get("step") == step.ids["step"]
                   and step.start_ns <= r.start_ns <= r.end_ns
                   <= step.end_ns), key=lambda r: r.start_ns)


def test_no_profiler_keeps_no_spans_and_still_times_the_step():
    eng = _engine()
    eng.step()                                  # compile outside the count
    metrics.clear_spans()
    sid = eng.open()
    eng.submit(sid, _chunk(W))
    assert eng.step() == 1
    assert metrics.spans() == () and metrics.dropped() == 0
    h = eng.metrics.window_latency_s
    assert h.count == 1 and h.total > 0.0


def test_cohort_step_spans_nest_in_order(traced):
    eng = _engine()
    sid = eng.open("a")
    eng.submit(sid, _chunk(W))
    assert eng.step() == 1
    recs = metrics.spans()
    (opened,) = _by_name(recs, "serve.open")
    assert opened.ids == {"sid": "a"}
    (step,) = _by_name(recs, "serve.step")
    assert opened.end_ns <= step.start_ns
    assert step.ids == {"step": 0, "sessions": 1, "slots": C, "steps": W}
    kids = _children(recs, step)
    assert [r.name for r in kids] == CHILDREN
    assert all(r.ids == {"step": 0} for r in kids)
    # every span of the step's phases lies inside it
    assert len([r for r in recs if r.ids.get("step") == 0]) == 7
    for a, b in zip(kids, kids[1:]):
        assert a.end_ns <= b.start_ns
    assert eng.metrics.window_latency_s.total == pytest.approx(
        (step.end_ns - step.start_ns) / 1e9)


def test_empty_step_is_spanned_without_children(traced):
    eng = _engine()
    assert eng.step() == 0
    recs = metrics.spans()
    (step,) = _by_name(recs, "serve.step")
    assert step.ids == {"step": 0, "sessions": 0, "slots": C, "steps": 0}
    assert [r.name for r in _children(recs, step)] == ["serve.schedule"]
    assert len(recs) == 2
    assert eng.metrics.window_latency_s.count == 0


def test_second_buffered_window_waits_from_the_first_pop(traced):
    eng = _engine()
    sid = eng.open("a")
    eng.submit(sid, _chunk(2 * W))
    assert eng.step() == 1 and eng.step() == 1
    q = _by_name(metrics.spans(), "serve.queue")
    assert [r.ids for r in q] == [{"sid": "a", "window": 0},
                                  {"sid": "a", "window": 1}]
    assert q[0].start_ns < q[0].end_ns
    assert q[1].start_ns == q[0].end_ns < q[1].end_ns
    steps = _by_name(metrics.spans(), "serve.step")
    # each pop happens inside the step that served the window
    for r, s in zip(q, steps):
        assert s.start_ns <= r.end_ns <= s.end_ns


def test_partial_tail_waits_from_the_close(traced):
    eng = _engine()
    sid = eng.open("a")
    eng.submit(sid, _chunk(3))
    assert eng.scheduler.ready_count == 0
    eng.close(sid)
    closed_at = eng.scheduler.sessions[sid].ready_ns
    assert eng.step() == 1
    (q,) = _by_name(metrics.spans(), "serve.queue")
    assert q.start_ns == closed_at and q.ids["window"] == 0


def test_every_span_has_its_twin_in_the_profiler_trace(tmp_path):
    eng = _engine()
    eng.step()
    metrics.clear_spans()
    with jax.profiler.trace(str(tmp_path)):
        for k in range(3):
            sid = eng.open()
            eng.submit(sid, _chunk(W, seed=k))
        eng.close(sid)
        while eng.step():
            pass
    recs = [r for r in metrics.spans() if r.name != "serve.queue"]
    assert len(recs) > 3
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    traced_ns = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    traced_ns.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns))
    for name in {r.name for r in recs}:
        mine = sorted((r for r in recs if r.name == name),
                      key=lambda r: r.start_ns)
        theirs = sorted(traced_ns.get(name, []))
        assert len(theirs) == len(mine), name
        for r, (_, dur) in zip(mine, theirs):
            assert abs((r.end_ns - r.start_ns) - dur) <= 50_000, name


def test_bound_drops_the_oldest_and_counts_them(traced, monkeypatch):
    monkeypatch.setattr(spanlog, "_MAX_SPANS", 3)   # the log serve re-exports
    for k in range(5):
        metrics.record("serve.queue", k, k + 1, window=k)
    assert [r.ids["window"] for r in metrics.spans()] == [2, 3, 4]
    assert metrics.dropped() == 2
    metrics.clear_spans()
    assert metrics.spans() == () and metrics.dropped() == 0
