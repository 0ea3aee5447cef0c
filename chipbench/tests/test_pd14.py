"""The microcircuit cell at a size a CPU test can hold: every population
cut by one factor (so is every in-degree, the connection probabilities
being kept), shorter chunks and warm-up. A sound run is `correct`; runs
with the program broken underneath are not; the control (weights rounded
to bfloat16) reads beyond the limits."""

import json

import numpy as np
import pytest

import harness

CELL = "pd14_microcircuit.spontaneous"
SCALE = 0.02


def tiny_cell(**traffic):
    cell = harness.Cell(CELL)
    cell.cfg["n"] = [max(1, round(n * SCALE)) for n in cell.cfg["n"]]
    cell.traffic.update(chunk_steps=50, warmup_steps=100, trace_seconds=2.0,
                        **traffic)
    return cell


def run_tiny(capsys, seed=2 ** 31 + 5, seconds=1.5, control=0, trace=0,
             **traffic):
    import run
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--control",
                   str(control)], require_chip=False,
                  cell=tiny_cell(**traffic))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    res = run_tiny(capsys)
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["spikes_dropped"]["value"] == 0
    assert res["info"]["sample"] == 4
    assert set(res["metrics"]) == {"session_steps_per_s", "setup_s"}
    rates = res["info"]["rates_hz"]
    assert all(r >= 0 for r in rates.values()) and sum(rates.values()) > 0


def test_traced_run_reads_every_new_metric(capsys):
    res = run_tiny(capsys, trace=1, seconds=2.0)
    want = {"chunk_ms.sim", "readback_ms.sim", "device_idle_share.sim",
            "syn_events_per_s.sim"}
    assert want <= set(res["metrics"]), res["metrics"]
    assert res["correct"] is True, res["compared"]


def test_mix_sets_the_background_rate(capsys):
    """A mix's `bg_rate_hz` replaces the configuration's on both sides: a
    quieter background, fewer spikes, and still `correct`."""
    full = run_tiny(capsys, seed=17)
    quiet = run_tiny(capsys, seed=17, bg_rate_hz=2.0)
    assert full["correct"] and quiet["correct"], quiet["compared"]
    assert quiet["info"]["spikes_per_step"] < full["info"]["spikes_per_step"]


def _broken_stream(kind, monkeypatch):
    """Wrap `plan.run_stream` so every chunk after the first few comes back
    broken."""
    from repro.core import plan
    orig = plan.run_stream

    def run_stream(*a, **kw):
        prev = None
        for k, (state, sp) in enumerate(orig(*a, **kw)):
            if k >= 3:
                if kind == "dropped_spike" and len(sp.step):
                    sp = sp._replace(step=sp.step[1:], neuron=sp.neuron[1:],
                                     batch=sp.batch[1:])
                elif kind == "state_unadvanced":
                    state = prev
                elif kind == "v_altered":
                    name = next(iter(state))
                    state = dict(state)
                    state[name] = dict(state[name],
                                       v=state[name]["v"] + 0.5)
            import jax
            import jax.numpy as jnp
            prev = jax.tree_util.tree_map(jnp.copy, state)
            yield state, sp

    monkeypatch.setattr(plan, "run_stream", run_stream)


@pytest.mark.parametrize("kind", ["dropped_spike", "state_unadvanced",
                                  "v_altered"])
def test_fault_is_caught(kind, capsys, monkeypatch):
    _broken_stream(kind, monkeypatch)
    res = run_tiny(capsys)
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_control_is_not_correct(seed, capsys):
    res = run_tiny(capsys, seed=seed, control=1)
    limits = harness.Cell(CELL).cfg["correct"]["limits"]
    checked = harness.check(res["control"], limits)
    assert not all(c["ok"] for c in checked.values()), checked


def test_push_roofline_reads_the_loops_around_the_ring_scatter():
    """The push's device time is the innermost `while` around each scatter
    into the flat ring: the scan around it, the gather loops beside it and
    loops with no such scatter do not count."""
    metric = harness.load_module(harness.BENCH_DIR + "/metrics/"
                                 "push_roofline.sim.py")
    ring, dev = 4938816, "/device:TPU:0"

    def ev(name, a, b):
        return (dev, "XLA Ops", name, a, b)

    scan = "%while.96 = (u32[], f32[1,64,77169]) while(%t), body=%b"
    push = "%while.97 = (s32[], f32[4938816]{0}) while(%t), body=%c"
    gather = "%while.100 = (s32[], s32[32,512]) while(%t), body=%d"
    scatter = f"%fusion.128 = f32[{ring}]{{0:T(1024)}} fusion(%a), kind=kCustom"
    other = "%fusion.7 = f32[64,1,77169]{2,1,0} fusion(%a), kind=kLoop"
    ops = [ev(scan, 0, 1000), ev(push, 10, 50), ev(gather, 12, 20),
           ev(scatter, 30, 40), ev(scatter, 41, 45), ev(push, 60, 90),
           ev(gather, 61, 65), ev(scatter, 70, 80), ev(other, 91, 95),
           ev(push, 100, 120)]
    assert metric.push_loops_ns(ops, ring, 0, 1000) == 40 + 30
    assert metric.push_loops_ns(ops, ring, 55, 1000) == 30
    assert metric.push_loops_ns(ops, ring + 1, 0, 1000) == 0


def test_same_poisson_counts_as_the_program():
    """The reference's background counts equal the program's for the same
    (key, step)."""
    import jax
    import jax.numpy as jnp
    from repro.core.neuron import Background, background_counts, \
        poisson_table
    ref = harness.Cell(CELL).module("reference")
    cfg = harness.Cell(CELL).cfg
    lam = ref.background_lambda(cfg)[3]
    key = jax.random.PRNGKey(7)
    tab = ref.poisson_table(lam)
    np.testing.assert_array_equal(tab, poisson_table(lam)[:, 0])
    for t in (0, 1, 12345):
        a = ref.poisson_counts(key, t, jnp.asarray(tab), 500)
        b = background_counts(Background(1.0), {
            "bg_cdf": jnp.asarray(poisson_table(np.full(500, lam))),
            "bg_key": key}, t, (1, 500))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[0])
