"""Test fixtures and harness policy.

NOTE: no XLA_FLAGS here — tests run on the single real CPU device (the
512-device override is dryrun.py-only, per the assignment).

Tier policy (mirrored in .github/workflows/ci.yml):
  fast tier    pytest -m "not slow"   — kernels, registry parity, topology,
               routing, plasticity; target well under 2 minutes
  full tier    pytest                 — adds model smoke / sharding /
               training-learns tests (the `slow` marker)

Tests run on the CPU (Pallas kernels in interpret mode). The chip's
compiler is exercised here ahead of time (tests/test_chip_compile.py);
running on a TPU is `python chip_smoke.py`, not a pytest tier.

If `hypothesis` is not installed (the baked container has no dev extras),
a minimal deterministic stub (tests/_hypothesis_stub.py) is registered
BEFORE collection so the property-test modules import and run; CI installs
the real engine via requirements-dev.txt.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

if importlib.util.find_spec("hypothesis") is None:
    _stub_path = os.path.join(os.path.dirname(__file__),
                              "_hypothesis_stub.py")
    _spec = importlib.util.spec_from_file_location("hypothesis", _stub_path)
    _stub = importlib.util.module_from_spec(_spec)
    sys.modules["hypothesis"] = _stub
    _spec.loader.exec_module(_stub)
    sys.modules["hypothesis.strategies"] = _stub.strategies


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _clear_incidents():
    """Isolate the per-process incident log (repro.kernels.incidents)
    between tests, so one test's recorded degradations cannot satisfy or
    pollute another's assertions."""
    from repro.kernels.incidents import clear
    clear()
    yield
    clear()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration/smoke test; excluded "
                   "from the fast CI tier")
