"""The control, the plain reference at the next lower precision (weights
at what three MXU passes keep) in the program's place, must come out not
correct under the cell's limits, on three seeds."""

import pytest

import harness
from conftest import run_tiny

SERVE = "shd_dhsnn.utterances"


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 977])
def test_control_is_not_correct(seed, capsys):
    res = run_tiny(SERVE, capsys, seed=seed, control=1)
    limits = harness.Cell(SERVE).cfg["correct"]["limits"]
    checked = harness.check(res["control"], limits)
    assert not all(c["ok"] for c in checked.values()), checked
