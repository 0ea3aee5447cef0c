"""The trace reduction on events written by hand, with every number
worked out by hand, and on a small trace recorded on a TPU v5e
(`record_trace.py`)."""

import os

import pytest

import tracing

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "small.xplane.pb")


def hand_events():
    # device: A [0, 10], B [5, 20], C [30, 40], the kernel [50, 60]
    # host:   window [0, 100]; step [0, 25], wait [25, 45], step [45, 65]
    return [
        (DEV, OPS, "A", 0, 10), (DEV, OPS, "B", 5, 20),
        (DEV, OPS, "C", 30, 40), (DEV, OPS, "_gather_kernel", 50, 60),
        (DEV, "XLA Modules", "jit_step", 0, 60),
        (HOST, "t1", "bench.window", 0, 100),
        (HOST, "t1", "bench.step", 0, 25), (HOST, "t1", "bench.wait", 25, 45),
        (HOST, "t1", "bench.step", 45, 65),
        (HOST, "t1", "PjitFunction", 1, 2),
    ]


def test_union_merges_overlaps():
    assert tracing.union([(5, 20), (0, 10), (30, 40), (40, 41)]) == \
        [(0, 20), (30, 41)]


def test_busy_and_idle_by_hand():
    r = tracing.Reduced(hand_events())
    assert (r.lo, r.hi) == (0, 100)
    # busy: [0, 20] + [30, 40] + [50, 60] = 40 of 100 (modules line ignored)
    assert r.busy_s() == pytest.approx(40e-9)
    # inside the steps: [0, 20] of [0, 25] and [50, 60] of [45, 65]
    steps = r.span_windows("step")
    assert steps == [(0, 25), (45, 65)]
    assert r.busy_s(steps) == pytest.approx(30e-9)


def test_kernel_time_by_pattern():
    r = tracing.Reduced(hand_events())
    assert r.kernel_s([r"gather_kernel"]) == (pytest.approx(10e-9), 1)
    assert r.kernel_s([r"^[AB]$"]) == (pytest.approx(25e-9), 2)
    assert r.kernel_s([r"nothing"]) == (0.0, 0)


def test_breakdown_by_hand():
    b = tracing.Reduced(hand_events()).breakdown()
    assert b["device_ops"] == [["B", 15e-9], ["A", 10e-9], ["C", 10e-9],
                               ["_gather_kernel", 10e-9]]
    # gaps [20, 30]: step and wait overlap 5 each, the shorter (wait)
    # takes it; [40, 50]: wait and step2 5 each, equal length, the
    # earlier (wait); [60, 100]: only step2 overlaps
    assert b["idle_gaps"] == [["bench.step", 40e-9], ["bench.wait", 20e-9]]


def test_idle_gap_without_a_span_is_host_other():
    evs = [(DEV, OPS, "A", 0, 10), (HOST, "t", "bench.window", 0, 30)]
    assert tracing.Reduced(evs).breakdown()["idle_gaps"] == \
        [["host.other", 20e-9]]


def test_recorded_trace_by_hand():
    """`data/small.xplane.pb` (its recorded source paths rewritten to
    `<checkout>/`): three rounds of a jitted step (an XLA fusion
    and a Pallas custom call, which the trace names after the jitted
    function: `%step.1 = ... custom-call`) and a 2 ms wait. On this trace
    the device clock runs ~0.04-0.12 ms ahead of the host's, so the first
    step's operations fall just before the window span opens."""
    r = tracing.Reduced(tracing.load(FIXTURE))
    assert r.devices() == [DEV]
    assert (r.lo, r.hi) == (43229070, 53382211)
    assert len(r.span_windows("step")) == 3
    # steps 2 and 3, each copy-start, copy-done, the fusion and step.1 with
    # gaps of 1-2 ns between them: 13 + 3 + 1503 + 1788 and
    # 13 + 3 + 1503 + 1787
    assert r.busy_s() == pytest.approx(6613e-9)
    assert r.kernel_s([r"^%step[.\d]* = "]) == (pytest.approx(3575e-9), 2)
    b = r.breakdown()
    assert b["device_ops"][:2] == [
        ["step.1", pytest.approx(3575e-9)],
        ["convolution_tanh_fusion", pytest.approx(3006e-9)]]
    # every gap overlaps a wait span more than a step span
    assert b["idle_gaps"] == [["bench.wait",
                               pytest.approx(10153141e-9 - 6613e-9)]]
