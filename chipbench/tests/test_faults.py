"""A run with the timed path broken underneath must come out not
correct: once for each fault the cell can have. The harness's look for a
chip is skipped; the rest of the run is the benchmark's own, at a size a
CPU can hold (`conftest.tiny_cell`)."""

import numpy as np
import pytest

from conftest import run_tiny

SERVE = "shd_dhsnn.utterances"


def test_sound_run_is_correct(capsys):
    res = run_tiny(SERVE, capsys)
    assert res["correct"] is True, res["compared"]
    assert list(res["compared"])[-1] == "sample_missing"


def _broken_cohort(kind):
    from repro.serve.engine import BatchedEngine
    orig = BatchedEngine._run_cohort

    def run_cohort(self, cohort, states):
        news, outs = orig(self, cohort, states)
        if kind == "state_unchanged":
            news = list(states)
        elif kind == "answer_altered":
            outs = [o.copy() for o in outs]
            outs[0][0, 0] += 1.0
        elif kind == "half_batch":
            # the second half of the cohort is left out: its sessions keep
            # their state and get the mean of the others' outputs
            half = max(1, len(outs) // 2)
            mean = np.mean(outs[:half], axis=0)
            news = list(news[:half]) + list(states[half:])
            outs = list(outs[:half]) + [mean] * (len(outs) - half)
        return news, outs

    return BatchedEngine, run_cohort


@pytest.mark.parametrize("kind", ["state_unchanged", "answer_altered",
                                  "half_batch"])
def test_serve_fault_is_caught(kind, capsys, monkeypatch):
    cls, fn = _broken_cohort(kind)
    monkeypatch.setattr(cls, "_run_cohort", fn)
    res = run_tiny(SERVE, capsys)
    assert res["correct"] is False, res["compared"]
