"""The benchmark's own tests: `python -m pytest chipbench/tests` from the
root of a checkout. They run on the CPU at small sizes."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def tiny_cell(workload: str):
    """The cell at a size a CPU test can hold: the same model widths and
    traffic shapes, fewer slots and sessions."""
    import harness
    cell = harness.Cell(workload)
    cell.cfg["serve"]["capacity"] = 8
    cell.traffic.update(rate_per_s=20.0, warmup_s=1.0, pool=32)
    return cell


def run_tiny(workload: str, capsys, seconds=1.5, seed=2 ** 31 + 7,
             control=0):
    """One run of the harness on the CPU; returns the result line."""
    import json
    import run
    cell = tiny_cell(workload)
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0",
                   "--control", str(control)], require_chip=False, cell=cell)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
