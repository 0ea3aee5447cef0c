"""Kernel-registry tests: parity harness over every registered kernel,
block resolution, dispatch policy, and the tuning-cache round trip."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import parity, registry, tuning

registry.ensure_registered()
ALL_KERNELS = registry.names()


# ---------------------------------------------------------------------------
# registration + parity (the CI backbone: every kernel, forward AND VJP)
# ---------------------------------------------------------------------------


def test_all_families_registered():
    assert set(ALL_KERNELS) == {"linrec", "lif", "lifrec", "alif", "alifrec",
                                "spikemm", "spikemm_gather", "attention",
                                "stdp", "stdp_seq", "event_push"}
    for name in ALL_KERNELS:
        spec = registry.get(name)
        assert spec.make_inputs is not None, name
        assert spec.block_axes, name
        assert spec.candidates, name


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_parity_forward_and_vjp(name):
    report = parity.check_kernel(name)
    assert report["forward_max_err"] <= registry.get(name).tol
    if registry.get(name).diff_argnums:
        assert "vjp_max_err" in report


def test_parity_check_all_covers_every_kernel():
    reports = parity.check_all()
    assert set(reports) == set(ALL_KERNELS)


def test_interpret_mode_policy(monkeypatch):
    """Off-TPU, interpret mode is on unless REPRO_PALLAS_INTERPRET=0 asks
    for the compiled kernels (to compile them for a described chip)."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert registry.interpret_mode()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert not registry.interpret_mode()


def _lif_args():
    return registry.get("lif").make_inputs(jax.random.PRNGKey(0))


def test_compiled_dispatch_failure_is_fatal(monkeypatch):
    """With compiled kernels (as on a chip) a failing Pallas call raises:
    it never reaches the reference, even without REPRO_STRICT. On the CPU
    backend the compiled Mosaic call itself is what fails."""
    from repro.kernels.incidents import incidents
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.delenv("REPRO_STRICT", raising=False)
    with pytest.raises(registry.FallbackError, match="lif"):
        registry.dispatch("lif", _lif_args())
    assert [e.stage for e in incidents(kind="dispatch")] == ["pallas"]


def test_compiled_dispatch_vmem_rejection_is_fatal(monkeypatch):
    from repro.kernels.incidents import incidents
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("REPRO_VMEM_LIMIT_MB", "0.001")
    monkeypatch.delenv("REPRO_STRICT", raising=False)
    with pytest.raises(registry.FallbackError, match="exceeds budget"):
        registry.dispatch("lif", _lif_args())
    assert len(incidents(kind="vmem")) == 1


def test_interpret_dispatch_failure_still_degrades(monkeypatch):
    """Interpret mode (off-TPU) keeps the degrade-to-reference chain."""
    from repro.core import faults
    from repro.kernels.incidents import incidents
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_STRICT", raising=False)
    args = _lif_args()
    with faults.inject("compile_fail:kernels=lif,p=1"):
        s, vT = registry.dispatch("lif", args)
    s_ref, v_ref = registry.get("lif").ref(*args)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    assert [e.stage for e in incidents(kind="dispatch")] == ["pallas"]


def test_gather_refuses_lane_misaligned_tables():
    """Compiled gather calls refuse table blocks the TPU's 128-lane tiling
    cannot take; interpret mode still runs them."""
    from repro.kernels.spikemm import gather
    rng = np.random.default_rng(0)
    pre = rng.integers(0, 300, 500)
    post = rng.integers(0, 200, 500)
    tables = gather.build_gather_tables(pre, post, np.ones(500), 300, 200,
                                        bk=32, bn=32)
    spikes = jnp.ones((8, 300), jnp.float32)
    with pytest.raises(ValueError, match="128-lane"):
        gather._pallas_impl(spikes, tables, blocks={"bm": 8},
                            interpret=False)
    out = gather._pallas_impl(spikes, tables, blocks={"bm": 8},
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gather._ref_impl(spikes, tables)),
                               rtol=1e-5, atol=1e-5)


def test_ops_files_have_no_direct_dispatch_logic():
    """Acceptance guard: block sizing + interpret policy live ONLY in the
    registry; a new kernel must not reintroduce per-family copies."""
    import repro.kernels as kpkg

    root = os.path.dirname(kpkg.__file__)
    offenders = []
    for fam in os.listdir(root):
        ops = os.path.join(root, fam, "ops.py")
        if not os.path.isfile(ops):
            continue
        src = open(ops).read()
        for banned in ("pick_block", "interpret_mode"):
            if banned in src:
                offenders.append((fam, banned))
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# block resolution
# ---------------------------------------------------------------------------


def test_fit_block_alignment_and_cap():
    assert registry.fit_block(100, 256, 8) == 104    # round up to align
    assert registry.fit_block(1000, 256, 8) == 256   # capped at preferred
    assert registry.fit_block(3, 256, 128) == 128    # floor at align


def test_exact_block_divides():
    assert registry.exact_block(20, 256) == 20       # whole axis fits
    assert registry.exact_block(1000, 256) == 250    # largest divisor <= pref
    assert registry.exact_block(97, 64) == 1         # prime: serial fallback
    for n, pref in [(20, 8), (256, 256), (1000, 256), (7, 512)]:
        b = registry.exact_block(n, pref)
        assert n % b == 0 and 1 <= b <= max(n, 1)


def test_lif_time_axis_never_padded():
    """Regression for the bug the parity harness caught: zero-padding the
    LIF time axis runs extra decay steps and corrupts v_final. The ct axis
    is `exact`, so any T (incl. primes) must agree with the reference."""
    from repro.kernels.lif.ops import lif_scan
    from repro.kernels.lif.ref import lif_scan_ref

    for T in (20, 23, 37):
        k = jax.random.PRNGKey(T)
        cur = 0.6 * jax.random.normal(k, (T, 2, 130))
        tau = jnp.full((130,), 0.9)
        v0 = jnp.zeros((2, 130))
        s_ref, v_ref = lif_scan_ref(cur, tau, v0)
        s_k, v_k = lif_scan(cur, tau, v0, 1.0, "rectangle", 1.0, True)
        np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_ref))
        np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref),
                                   rtol=1e-5, atol=1e-5)


def test_dispatch_policy_env(monkeypatch):
    from repro.kernels.common import on_tpu

    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    assert registry.use_pallas(False)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    assert not registry.use_pallas(False)
    assert registry.use_pallas(True)          # explicit force always wins
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    assert registry.use_pallas(False) == on_tpu()  # auto: pallas on TPU only


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------


def test_shape_bucket_pow2_and_canonical():
    assert tuning.shape_bucket({"T": 100, "B": 8}) == "B8_T128"
    assert tuning.shape_bucket({"B": 8, "T": 100}) == "B8_T128"  # order-free
    assert tuning.shape_bucket({"D": 1}) == "D1"


def test_tuning_cache_round_trip(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = tuning.TuningCache(path)
    assert cache.lookup("linrec", "cpu", "B8_T128") is None
    cache.put("linrec", "cpu", "B8_T128", {"ct": 128, "bb": 8, "bd": 256},
              stats={"best_s": 1e-3})
    cache.save()

    reloaded = tuning.TuningCache(path)
    assert reloaded.lookup("linrec", "cpu", "B8_T128") == {
        "ct": 128, "bb": 8, "bd": 256}
    assert reloaded.lookup("linrec", "cpu", "B8_T256") is None
    assert len(reloaded) == 1
    raw = json.load(open(path))
    assert raw["version"] == 1


def test_tuning_cache_corrupt_file_is_ignored(tmp_path):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        f.write("{not json")
    cache = tuning.TuningCache(path)
    assert cache.lookup("lif", "cpu", "X1") is None
    cache.put("lif", "cpu", "X1", {"ct": 8})
    cache.save()
    assert tuning.TuningCache(path).lookup("lif", "cpu", "X1") == {"ct": 8}


def test_autotune_persists_winner_and_dispatch_uses_it(tmp_path,
                                                       monkeypatch):
    path = str(tmp_path / "tuned.json")
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)

    spec = registry.get("linrec")
    args = spec.make_inputs(jax.random.PRNGKey(0))
    dims = spec.dims_of(*args)

    blocks, report = tuning.autotune("linrec", args, repeats=1)
    assert os.path.exists(path)
    assert report["winner"]["blocks"] == blocks
    assert {t["blocks"]["ct"] for t in report["timings"] if "best_s" in t}

    # dispatch-time resolution picks the persisted winner for this bucket...
    assert spec.resolve_blocks(dims) == blocks
    # ...and ignores it for a different bucket (falls back to defaults)
    other_dims = {"T": 4 * dims["T"], "B": dims["B"], "D": dims["D"]}
    default_blocks = spec.resolve_blocks(other_dims, use_cache=False)
    assert spec.resolve_blocks(other_dims) == default_blocks


def test_autotune_prunes_vmem_hogs(tmp_path, monkeypatch):
    """With a tiny VMEM budget every non-default candidate is pruned before
    timing; the spec-default baseline must survive and win."""
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "t.json"))
    monkeypatch.setenv("REPRO_VMEM_LIMIT_MB", "0.01")
    spec = registry.get("lif")
    # serving-scale shape: candidates fit to DISTINCT block configs (the
    # canonical parity inputs are so small they all collapse to one)
    k = jax.random.PRNGKey(0)
    args = (0.6 * jax.random.normal(k, (256, 8, 512)),
            jnp.full((512,), 0.9), jnp.zeros((8, 512)))
    blocks, report = tuning.autotune("lif", args, repeats=1)
    assert report["pruned"], "expected candidates above the 10 KiB budget"
    assert len([t for t in report["timings"] if "best_s" in t]) >= 1
    defaults = spec.resolve_blocks(spec.dims_of(*args), use_cache=False)
    assert blocks == defaults


def test_every_spec_has_vmem_model():
    for name in ALL_KERNELS:
        spec = registry.get(name)
        assert spec.vmem_bytes is not None, name
        args = spec.make_inputs(jax.random.PRNGKey(0))
        dims = spec.dims_of(*args)
        blocks = spec.resolve_blocks(dims, use_cache=False)
        est = spec.vmem_bytes(dims, blocks)
        assert 0 < est < 2 ** 30, (name, est)


def test_bundled_cache_fallback(tmp_path, monkeypatch):
    """A user-cache miss falls through to the checked-in CI cache; a user
    entry for the same bucket wins over the bundled one."""
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "user.json"))
    spec = registry.get("spikemm")
    args = spec.make_inputs(jax.random.PRNGKey(0))
    dims = spec.dims_of(*args)
    bucket = tuning.shape_bucket(dims)
    bundled = tuning.bundled_cache().lookup("spikemm", jax.default_backend(),
                                            bucket)
    if bundled is None:
        pytest.skip(f"no bundled entry for backend/bucket {bucket}")
    assert tuning.lookup_tuned("spikemm", dims) == bundled

    planted = {"bm": 8, "bk": 128, "bn": 128}
    tuning.default_cache().put("spikemm", jax.default_backend(), bucket,
                               planted)
    assert tuning.lookup_tuned("spikemm", dims) == planted


def test_tuned_blocks_still_produce_correct_results(tmp_path, monkeypatch):
    """End-to-end: plant a deliberately odd tuned config and check the
    kernel output is still exact — tuning may only change performance."""
    from repro.kernels.linrec.ops import linrec
    from repro.kernels.linrec.ref import linrec_naive

    path = str(tmp_path / "tuned.json")
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    spec = registry.get("linrec")
    k = jax.random.PRNGKey(1)
    a = jax.random.uniform(k, (48, 2, 130), jnp.float32, 0.5, 0.99)
    x = jax.random.normal(jax.random.fold_in(k, 1), (48, 2, 130))
    h0 = jnp.zeros((2, 130))
    dims = spec.dims_of(a, x, h0)

    cache = tuning.TuningCache(path)
    cache.put("linrec", jax.default_backend(), tuning.shape_bucket(dims),
              {"ct": 16, "bb": 8, "bd": 128})
    cache.save()
    assert spec.resolve_blocks(dims)["ct"] == 16    # the planted config wins

    y_ref, h_ref = linrec_naive(a, x, h0)
    y_k, h_k = linrec(a, x, h0, True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# implementation channels (block-sparse spikemm dispatch policy)
# ---------------------------------------------------------------------------


def _channel_rasters():
    k = jax.random.PRNGKey(11)
    M, K = 512, 1024
    sparse = jnp.zeros((M, K), jnp.float32).at[:64, :128].set(1.0)
    dense = (jax.random.uniform(k, (M, K)) < 0.5).astype(jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (K, 64), jnp.float32)
    return sparse, dense, w


def test_spikemm_channel_env_policy(monkeypatch):
    """never/auto/always routing, tracer conservatism, invalid value."""
    from repro.kernels.spikemm import ops
    sparse, dense, w = _channel_rasters()
    spec = registry.get("spikemm")
    blocks = spec.resolve_blocks(spec.dims_of(sparse, w))

    monkeypatch.setenv("REPRO_SPIKEMM_SPARSE", "never")
    assert ops._select_channel(sparse, w, blocks=blocks) is None
    monkeypatch.setenv("REPRO_SPIKEMM_SPARSE", "always")
    assert ops._select_channel(dense, w, blocks=blocks) == "sparse"
    monkeypatch.delenv("REPRO_SPIKEMM_SPARSE")
    assert ops._select_channel(sparse, w, blocks=blocks) == "sparse"
    assert ops._select_channel(dense, w, blocks=blocks) is None

    # abstract raster (under jit): occupancy unknowable -> dense
    seen = []

    def probe(s):
        seen.append(ops._select_channel(s, w, blocks=blocks))
        return s

    jax.jit(probe)(sparse)
    assert seen == [None]

    monkeypatch.setenv("REPRO_SPIKEMM_SPARSE", "bogus")
    with pytest.raises(ValueError, match="REPRO_SPIKEMM_SPARSE"):
        ops._select_channel(sparse, w, blocks=blocks)


def test_spikemm_auto_threshold_from_tuning_cache(tmp_path, monkeypatch):
    """The auto policy honors a tuned per-(backend, bucket) threshold: a
    zero threshold pins even a near-empty raster to the dense channel."""
    from repro.kernels.spikemm import ops
    sparse, _, w = _channel_rasters()
    spec = registry.get("spikemm")
    blocks = spec.resolve_blocks(spec.dims_of(sparse, w))
    dims = spec.dims_of(sparse, w)
    monkeypatch.delenv("REPRO_SPIKEMM_SPARSE", raising=False)
    # fresh cache path per scenario: the default-cache singleton caches
    # its first load of a given path
    for permille, expect in ((0, None), (1000, "sparse")):
        path = str(tmp_path / f"cache_{permille}.json")
        monkeypatch.setenv("REPRO_TUNING_CACHE", path)
        cache = tuning.TuningCache(path)
        cache.put("spikemm.sparse_th", jax.default_backend(),
                  tuning.shape_bucket(dims), {"permille": permille})
        cache.save()
        assert ops.sparse_threshold(dims) == permille / 1000.0
        assert ops._select_channel(sparse, w, blocks=blocks) == expect


def test_dispatch_routes_through_selected_channel(monkeypatch):
    """dispatch() must hand the call to the channel pair the router picks
    (observed via a wrapped spec), and fall through when it returns None."""
    from repro.kernels.spikemm import ops
    sparse, _, w = _channel_rasters()
    calls = []
    spec = registry.get("spikemm")
    wrapped = dataclasses.replace(
        spec,
        ref=lambda *a, **kw: calls.append("dense") or spec.ref(*a, **kw),
        channels={"sparse": registry.Channel(
            ref=lambda *a, **kw: calls.append("sparse")
            or spec.channels["sparse"].ref(*a, **kw),
            pallas=spec.channels["sparse"].pallas)})
    monkeypatch.setitem(registry._REGISTRY, "spikemm", wrapped)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    monkeypatch.setenv("REPRO_SPIKEMM_SPARSE", "always")
    registry.dispatch("spikemm", (sparse, w))
    monkeypatch.setenv("REPRO_SPIKEMM_SPARSE", "never")
    registry.dispatch("spikemm", (sparse, w))
    assert calls == ["sparse", "dense"]
