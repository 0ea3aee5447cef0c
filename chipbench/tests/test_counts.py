"""Operation and least-byte counts on shapes small enough to count by
hand."""

import numpy as np

import counts
import harness

serve_engine = harness.load_module(
    f"{harness.BENCH_DIR}/entries/serve_engine.py")


def test_integ_counts_by_hand():
    # 3 spikes fanning out to 10 weights, 6 of them distinct; 4 rows of
    # 16 inputs (8 bytes as bits < 12 as indices); output 4 x 8 f32
    c = counts.integ(events=10, weights_touched=6, rows=4, n_pre=16,
                     n_post=8, spikes=3)
    assert c == {"ops": 20.0, "bytes": 24 + 8 + 128}


def test_dense_integ_counts_by_hand():
    # 3 spikes on 2 distinct inputs, each input fans out to all 8 outputs
    c = counts.dense_integ(spikes=3, active_inputs=2, fan_out=8, rows=4,
                           n_pre=16)
    assert c == {"ops": 48.0, "bytes": 4 * 16 + 8 + 128}


def test_input_bytes_take_indices_when_fewer():
    # one spike in 4 x 1024 inputs: a 4-byte index beats 512 bytes of bits
    c = counts.integ(events=5, weights_touched=5, rows=4, n_pre=1024,
                     n_post=5, spikes=1)
    assert c["bytes"] == 4 * 5 + 4 + 4 * 4 * 5


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time({"ops": 48.0, "bytes": 200.0}, peaks) == 20.0
    assert counts.least_time({"ops": 5000.0, "bytes": 200.0}, peaks) == 50.0


def test_serve_step_counts_by_hand():
    # one session (pool row 0) of 3 steps in windows of 2 over 4 inputs;
    # INTEG fans each input out to 1 branch x 2 hidden units
    pool = np.zeros((1, 4, 4), np.float32)
    pool[0, 0, [0, 3]] = 1.0                  # step 0: inputs 0 and 3
    pool[0, 1, 0] = 1.0                       # step 1: input 0
    pool[0, 2, 1] = 1.0                       # step 2: input 1
    rec = {"sched": {"row": np.array([0])}, "W": 2, "t_open": 0.0,
           "t_close": 5.0,
           # (session, window, valid steps, step start, step end)
           "wins": [(0, 0, 2, 1.0, 1.1), (0, 1, 1, 2.0, 2.1),
                    (0, 1, 1, 9.0, 9.1)]}    # the last after the window
    cfg = {"n_in": 4, "n_branches": 1, "n_hidden": 2, "n_out": 1}
    c = serve_engine.step_counts(rec, pool, cfg, rows_per_call=4)
    # first step: 3 spikes on 2 inputs -> 6 events, 4 weights, bits of
    # 4 x 4 entries (2 bytes) < 3 indices, output 4 x 2 f32; the readout
    # counts its 4 x 1 f32 output alone
    ro = {"ops": 0.0, "bytes": 16.0}
    assert c == [{"start": 1.0, "input": {"ops": 12.0, "bytes": 16 + 2 + 32},
                  "readout": ro},
                 {"start": 2.0, "input": {"ops": 4.0, "bytes": 8 + 2 + 32},
                  "readout": ro}]
