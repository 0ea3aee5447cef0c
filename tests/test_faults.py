"""Fault-injection harness tests: spec parsing, determinism, jit-safety,
and the behavioral signature of every fault kind (repro.core.faults)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults, plan
from tests._faults import dh_net, spikes


def run_net(x=None, **kw):
    nodes, params = dh_net()
    if x is None:
        x = spikes(jax.random.PRNGKey(1))
    return plan.run(nodes, params, x, **kw)


# ---------------------------------------------------------------------------
# spec grammar + resolution
# ---------------------------------------------------------------------------


def test_parse_spec():
    fs = faults.parse("drop_blocks:p=0.1,seed=3; dead_rows:frac=0.2,mode=stuck")
    assert [f.kind for f in fs] == ["drop_blocks", "dead_rows"]
    assert fs[0].getf("p", 0.0) == pytest.approx(0.1)
    assert fs[0].geti("seed", 0) == 3
    assert fs[1].get("mode") == "stuck"


def test_parse_rejects_unknown_kind_and_bad_param():
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.parse("cosmic_ray:p=1")
    with pytest.raises(ValueError, match="not key=value"):
        faults.parse("drop_blocks:p")


def test_env_spec_activates(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "bitflip:frac=0.5,seed=1")
    assert [f.kind for f in faults.active()] == ["bitflip"]
    monkeypatch.setenv("REPRO_FAULTS", "")
    assert faults.active() == ()


def test_inject_overrides_env(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "bitflip:frac=0.5")
    with faults.inject("dead_rows:frac=0.1"):
        assert [f.kind for f in faults.active()] == ["dead_rows"]
        with faults.inject(""):        # chaos-CI escape hatch: clean world
            assert faults.active() == ()
    assert [f.kind for f in faults.active()] == ["bitflip"]


# ---------------------------------------------------------------------------
# data faults: determinism + signatures
# ---------------------------------------------------------------------------


def test_drop_blocks_zeroes_tiles_deterministically():
    x = jnp.ones((16, 2, 64))
    with faults.inject("drop_blocks:p=0.5,bt=4,bn=16,seed=7"):
        a = faults.perturb_input(x)
        b = faults.perturb_input(x)
    np.testing.assert_array_equal(a, b)
    assert float(a.sum()) < float(x.sum())          # something was dropped
    # drops are whole (bt x bn) tiles: each tile is all-kept or all-zero
    tiles = np.asarray(a).reshape(4, 4, 2, 4, 16).transpose(0, 3, 2, 1, 4)
    per_tile = tiles.reshape(16, -1).sum(axis=1)
    assert set(np.unique(per_tile)).issubset({0.0, 2 * 4 * 16})


def test_dead_rows_masks_only_named_node():
    out = jnp.ones((5, 3, 40))
    with faults.inject("dead_rows:frac=0.4,node=hidden,seed=2"):
        hit = faults.perturb_output("hidden", out)
        other = faults.perturb_output("readout", out)
    assert float(hit.sum()) < float(out.sum())
    np.testing.assert_array_equal(other, out)
    # the mask is per-neuron and time-independent: dead columns are dead
    # at every timestep (the property that makes engines bit-identical)
    col_sums = np.asarray(hit).sum(axis=(0, 1))
    assert set(np.unique(col_sums)).issubset({0.0, 15.0})


def test_stuck_rows_force_ones():
    out = jnp.zeros((5, 3, 40))
    with faults.inject("dead_rows:frac=0.4,mode=stuck,seed=2"):
        hit = faults.perturb_output("hidden", out)
    col = np.asarray(hit).sum(axis=(0, 1))
    assert set(np.unique(col)).issubset({0.0, 15.0})
    assert float(hit.sum()) > 0


def test_weight_poisoning_targets_w_planes_only():
    params = {"hidden": {"w_input": jnp.ones((8, 8)),
                         "neuron": jnp.ones((8,)),
                         "bias": jnp.ones((8,))},
              "readout": {"w_hidden": jnp.ones((8, 4))}}
    with faults.inject("nan_weights:frac=0.3,seed=5"):
        p = faults.perturb_params(params)
    assert bool(jnp.isnan(p["hidden"]["w_input"]).any())
    assert bool(jnp.isnan(p["readout"]["w_hidden"]).any())
    assert not bool(jnp.isnan(p["hidden"]["neuron"]).any())
    assert not bool(jnp.isnan(p["hidden"]["bias"]).any())
    with faults.inject("bitflip:frac=0.3,seed=5"):
        q = faults.perturb_params(params)
    flipped = np.asarray(q["hidden"]["w_input"])
    assert set(np.unique(flipped)) == {-1.0, 1.0}    # sign flips only


def test_identity_when_inactive():
    x = jnp.ones((4, 2, 8))
    with faults.inject(""):
        assert faults.perturb_input(x) is x
        assert faults.perturb_output("n", x) is x
        p = {"n": {"w_x": x}}
        assert faults.perturb_params(p) is p


# ---------------------------------------------------------------------------
# through the engines: determinism, jit == eager, engine equivalence
# ---------------------------------------------------------------------------

SPEC = "drop_blocks:p=0.3,seed=3;dead_rows:frac=0.2,seed=5;bitflip:frac=0.01,seed=7"


def test_faults_change_the_run_and_are_deterministic():
    _, clean, _ = run_net()
    with faults.inject(SPEC):
        _, a, _ = run_net()
        _, b, _ = run_net()
    assert not np.array_equal(np.asarray(a), np.asarray(clean))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_faults_jit_matches_eager():
    nodes, params = dh_net()
    x = spikes(jax.random.PRNGKey(1))
    with faults.inject(SPEC):
        _, eager, _ = plan.run(nodes, params, x)
        jitted = jax.jit(lambda p, xx: plan.run(nodes, p, xx)[1])(params, x)
    # same masks, same math; tolerance covers associative-scan vs
    # sequential-fold fp32 reordering (see plan.CROSS_ENGINE_ATOL)
    np.testing.assert_allclose(np.asarray(jitted), np.asarray(eager),
                               atol=plan.CROSS_ENGINE_ATOL)


def test_faults_identical_across_engines():
    """The fused plan and the per-step stepper reference must see the SAME
    injected world: masks depend only on (seed, site), never on engine
    internals."""
    nodes, params = dh_net()
    x = spikes(jax.random.PRNGKey(1))
    with faults.inject(SPEC):
        _, fused, _ = plan.run(nodes, params, x)
        _, stepped, _ = plan.run_reference(nodes, params, x)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(stepped),
                               atol=plan.CROSS_ENGINE_ATOL)


def test_compile_fail_is_deterministic_per_kernel():
    f = faults.parse("compile_fail:kernels=*,p=0.5,seed=1")[0]
    names = ("linrec", "lif", "spikemm", "attention", "stdp_seq")
    picks = {k: faults._fails(f, k) for k in names}
    assert picks == {k: faults._fails(f, k) for k in names}   # stable
    sure = faults.parse("compile_fail:kernels=*,p=1")[0]
    never = faults.parse("compile_fail:kernels=*,p=0")[0]
    assert all(faults._fails(sure, k) for k in names)
    assert not any(faults._fails(never, k) for k in names)


def test_compile_fail_targets_named_kernels():
    with faults.inject("compile_fail:kernels=lif|linrec"):
        with pytest.raises(faults.FaultInjectedError):
            faults.maybe_fail_compile("lif")
        faults.maybe_fail_compile("spikemm")      # untargeted: no raise


def test_vmem_limit_override_takes_min():
    with faults.inject("vmem_limit:mb=2;vmem_limit:mb=1"):
        assert faults.vmem_limit_override_bytes() == 1 * 2 ** 20
    with faults.inject(""):
        assert faults.vmem_limit_override_bytes() is None
