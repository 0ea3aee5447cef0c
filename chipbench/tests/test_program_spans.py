"""The program's spans on the trace's clock, and the nine readers that
use them, on spans and `bench.step` events written by hand with every
number worked out by hand; then one CPU-size traced run of the harness in
which all nine are read."""

import json
from types import SimpleNamespace

import pytest

import harness
import program_spans
import tracing
from conftest import tiny_cell

HOST = "/host:CPU"
SERVE = "shd_dhsnn.utterances"
P = 10 ** 9          # the program's clock runs P + 3 ns ahead of the trace's
READERS = ["open_ms.serve", "schedule_ms.serve", "sched_wait_p99_ms.serve",
           "slot_fill.serve", "gather_ms.serve", "assemble_ms.serve",
           "dispatch_ms.serve", "readback_ms.serve", "scatter_ms.serve"]


def rec(name, lo, hi, **ids):
    """A program record given on the trace's clock."""
    from repro.serve.metrics import SpanRecord
    return SpanRecord(name, lo + P + 3, hi + P + 3, ids)


def kids(step, t0, *durations):
    names = ["serve.schedule", "serve.gather", "serve.assemble",
             "serve.dispatch", "serve.readback", "serve.scatter"]
    out, t = [], t0
    for name, d in zip(names, durations):
        out.append(rec(name, t, t + d, step=step))
        t += d
    return out


def hand_records():
    # window [900, 2000); served steps 0 and 1, step 2 empty, step 3 after
    # the window; opens at 950 (3 ns), 1200 (6), 2050 (after); queue waits
    # popped at 1005 (205 ns), 1006 (16), 2105 (after)
    return ([rec("serve.step", 999, 1099, step=0, sessions=2, slots=2,
                 steps=7)]
            + kids(0, 1000, 10, 10, 20, 30, 20, 8)
            + [rec("serve.step", 1502, 1602, step=1, sessions=1, slots=2,
                   steps=4)]
            + kids(1, 1503, 20, 5, 20, 30, 12, 10)
            + [rec("serve.step", 1700, 1710, step=2, sessions=0, slots=2,
                   steps=0),
               rec("serve.schedule", 1701, 1709, step=2),
               rec("serve.step", 2100, 2200, step=3, sessions=1, slots=2,
                   steps=4)]
            + kids(3, 2101, 10, 10, 10, 10, 10, 10)
            + [rec("serve.open", 950, 953, sid="a"),
               rec("serve.open", 1200, 1206, sid="b"),
               rec("serve.open", 2050, 2060, sid="c"),
               rec("serve.queue", 800, 1005, sid="a", window=0),
               rec("serve.queue", 990, 1006, sid="b", window=0),
               rec("serve.queue", 1990, 2105, sid="a", window=1)])


def bench_events(steps=((1000, 1100), (1500, 1600), (1700, 1710),
                        (2100, 2200))):
    return ([(HOST, "t1", "bench.window", 900, 2000)]
            + [(HOST, "t1", "bench.step", s, e) for s, e in steps])


def view_of(events):
    return SimpleNamespace(trace=tracing.Reduced(events),
                           cfg={"serve": {"window": 4}})


def read(name, view):
    mod = harness.load_module(f"{harness.BENCH_DIR}/metrics/{name}.py")
    return mod.read(view)


@pytest.fixture
def log(monkeypatch):
    state = {"records": hand_records(), "dropped": 0}
    monkeypatch.setattr(program_spans, "program_log",
                        lambda: (state["records"], state["dropped"]))
    return state


def test_offset_fit_by_hand(log):
    # program - trace: 999, 1502, 1700, 2100 against 1000, 1500, 1700, 2100
    # gives differences -P-2, -P-5, -P-3, -P-3: median -P-3, residuals
    # 1, 2, 0, 0, their median 0.5
    recs = log["records"]
    evs, off, res = program_spans.align(
        recs, [ev for ev in bench_events() if ev[2] == "bench.step"])
    assert (off, res) == (-P - 3, 0.5)
    assert evs[0] == (program_spans.PLANE, program_spans.LINE, "serve.step",
                      999, 1099, {"step": 0, "sessions": 2, "slots": 2,
                                  "steps": 7})
    # in the window by start: 4 steps of 3 (step 3 at 2100 is out), ...
    v = view_of(bench_events())
    names = [ev[2] for ev in program_spans.events(v)]
    assert names.count("serve.step") == 3 and names.count("serve.open") == 2
    assert names.count("serve.queue") == 2          # starts 990, 1990
    ends = [ev for ev in program_spans.events(v, at="end")
            if ev[2] == "serve.queue"]
    assert [(ev[3], ev[4]) for ev in ends] == [(800, 1005), (990, 1006)]


def test_readers_by_hand(log):
    v = view_of(bench_events())
    ns = 1e-6                                    # ms per ns
    want = {
        "open_ms.serve": 4.5 * ns,                 # (3 + 6) / 2
        "schedule_ms.serve": 15 * ns,              # (10 + 20) / 2; step 2
        "gather_ms.serve": 7.5 * ns,               # (10 + 5) / 2
        "assemble_ms.serve": 20 * ns,
        "dispatch_ms.serve": 30 * ns,
        "readback_ms.serve": 16 * ns,              # (20 + 12) / 2
        "scatter_ms.serve": 9 * ns,                # (8 + 10) / 2
        "sched_wait_p99_ms.serve": 203.11 * ns,    # 16 + 0.99 x (205 - 16)
        "slot_fill.serve": 68.75,                  # (7 + 4) / (2x4 + 2x4)
    }
    assert sorted(want) == sorted(READERS)
    for name, value in want.items():
        assert read(name, v) == pytest.approx(value), name


@pytest.mark.parametrize("fault", ["count", "dropped", "residual", "none"])
def test_no_spans_where_the_clocks_cannot_be_joined(fault, log,
                                                     monkeypatch):
    steps = [(1000, 1100), (1500, 1600), (1700, 1710), (2100, 2200)]
    if fault == "count":
        steps = steps[:3]
    elif fault == "dropped":
        log["dropped"] = 1
    elif fault == "residual":
        # two of four pairs 300 us off: the median residual is 150 us
        steps[1] = (1500 + 300_000, 1600 + 300_000)
        steps[3] = (2100 + 300_000, 2200 + 300_000)
    else:                                   # a program that keeps no log
        monkeypatch.setattr(program_spans, "program_log", lambda: None)
    v = view_of(bench_events(tuple(steps)))
    assert program_spans.events(v) is None
    assert all(read(name, v) is None for name in READERS)


def test_traced_cpu_run_reads_all_nine(capsys):
    import run
    from repro.serve import metrics
    metrics.clear_spans()
    rc = run.main(["--workload", SERVE, "--seed", str(2 ** 33 + 5),
                   "--seconds", "1.5", "--trace", "1"], require_chip=False,
                  cell=tiny_cell(SERVE))
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = res["metrics"]
    assert all(name in m for name in READERS), sorted(m)
    assert 0.0 < m["slot_fill.serve"]["value"] <= 100.0
    assert m["dispatch_ms.serve"]["unit"] == "ms"
