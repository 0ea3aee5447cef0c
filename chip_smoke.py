#!/usr/bin/env python3
"""Chip smoke test: the served SNN path, end to end, on one TPU chip.

    python chip_smoke.py          # from the repository root, on a TPU host

It raises at once when JAX finds no TPU: there is no CPU fallback. All
phases run in this one process (a chip belongs to one process), with
`REPRO_STRICT=1`, so any kernel degradation raises instead of reaching the
reference quietly, and the incident log must stay empty after every phase.

  a. Kernel parity: every registered family, compiled (not interpreted),
     forward and VJP against its XLA reference.
  b. Main path: 64 concurrent SHD sessions (700 -> 64 -> 20) of 128 steps
     at ~8% input density, served through `StreamClient` on one
     `BatchedEngine` (window 32, 64 slots) with chunk sizes that never line
     up with the window; then the DH-LIF variant on a second engine.
  c. The other first-cell paths through `plan.run`: the recurrent-ALIF ECG
     model, the plastic pair-STDP window, and a 10^5-neuron banded
     compressed topology streamed through `plan.run_stream`.

Every served result is compared with the fp32 stepper reference:
`plan.run_reference` (`events.run` and the same learning pass, XLA
reference kernels) on the same chip under
`jax.default_matmul_precision("highest")`. The served path runs at
the default precision. Its kernels contract f32 operands at full f32
precision (`repro.kernels.common.f32_precision`); the bounds below allow
for the bf16 operand rounding that the default precision would apply.

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

os.environ["REPRO_STRICT"] = "1"          # before repro is imported
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# --- tolerances --------------------------------------------------------------
# unit roundoff of bf16, the operand rounding the MXU applies to f32 matmuls
# at default precision (coarser than any arithmetic the served path uses)
EPS_BF16 = 2.0 ** -8
# disagreeing spikes per reference spike: only threshold crossings within
# that rounding of v_th may flip; a faulty kernel disagrees on most spikes
SPIKE_FLIP_MAX = 0.02
# f32 re-association of the readout's linear recurrence (associative scan vs
# sequential fold, ~1e-6 relative per step) on readouts of magnitude ~10
READOUT_ATOL = 1e-3
# f32 re-association of the summed STDP updates on weights clipped to |w|<=1
WEIGHT_ATOL = 1e-5

# --- sizes -------------------------------------------------------------------
SHD = dict(n_in=700, n_hidden=64, n_out=20)
WINDOW, CAPACITY = 32, 64
N_SESSIONS, SESSION_STEPS, INPUT_DENSITY = 64, 128, 0.08
CHUNK_SIZES = (17, 23, 31, 40)            # never aligned with the window
DH_SESSIONS, DH_STEPS = 8, 64
ECG_BATCH, ECG_STEPS, ECG_DENSITY = 8, 128, 0.2
PLASTIC_BATCH, PLASTIC_STEPS, PLASTIC_DENSITY = 8, 128, 0.1
TOPO_N, TOPO_BAND, TOPO_OUT = 100_000, 32, 8
TOPO_BATCH, TOPO_STEPS, TOPO_CHUNK, TOPO_DENSITY = 2, 64, 16, 0.1
SEED = 0


def log(*args) -> None:
    print(*args, flush=True)


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed (raised, so `-O` cannot drop it)."""


def require(ok, what) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU; JAX found {dev.platform}")
    return dev


@contextlib.contextmanager
def env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def assert_no_incidents(phase: str) -> None:
    from repro.kernels import incidents
    evs = incidents()
    require(not evs, f"{phase}: {len(evs)} incidents: {evs[:1]}")


def assert_fused(plan, label: str) -> None:
    from repro.core import plan as plan_mod
    fallback = [s for s in plan.segments if s.kind == plan_mod.FALLBACK]
    require(not fallback, f"{label}: fallback segments in {plan.describe()}")
    log(f"[{label}] plan: {plan.describe()}")


def assert_kernels_in(hlo: str, label: str) -> None:
    n_kernels = hlo.count("tpu_custom_call")
    require(n_kernels > 0, f"{label}: no Pallas kernel in the resident step")
    log(f"[{label}] resident step holds {n_kernels} tpu_custom_call sites")


def spikes(rng, shape, density) -> np.ndarray:
    return (rng.random(shape) < density).astype(np.float32)


# --- running a model window by window -----------------------------------------


def run_windows(nodes, params, x, window, *, learn=False, jit=False,
                hidden="hidden", reference=False):
    """`plan.run` (or, with `reference`, `plan.run_reference`) over
    consecutive windows of x (T, B, n_in), carrying state and (when
    learning) the chunked-online weights. Returns (outputs, hidden spikes,
    final state) as numpy / a state tree."""
    from repro.core import events, plasticity
    from repro.core import plan as plan_mod

    plan = plan_mod.compile_program(nodes)

    def one(p, xw, st):
        if reference:
            return plan_mod.run_reference(nodes, p, xw, state=st,
                                          record=(hidden,), learn=learn)
        return plan_mod.run(nodes, p, xw, state=st, plan=plan,
                            record=(hidden,), learn=learn)

    fn = jax.jit(one) if jit else one
    state = events.init_state(nodes, x.shape[1], jnp.float32, params)
    outs, hids = [], []
    for t0 in range(0, x.shape[0], window):
        p = plasticity.apply_learned(nodes, params, state) if learn else params
        state, out, rec = fn(p, jnp.asarray(x[t0:t0 + window]), state)
        outs.append(np.asarray(out))
        hids.append(np.asarray(rec[hidden]))
    return np.concatenate(outs), np.concatenate(hids), state


def reference_windows(nodes, params, x, window, *, learn=False,
                      hidden="hidden"):
    """The fp32 stepper reference: `plan.run_reference` (`events.run`,
    then for a plastic model the same chunked-online learning pass) with
    XLA reference kernels and full-precision f32 matmuls."""
    with env(REPRO_KERNEL_IMPL="ref"), \
            jax.default_matmul_precision("highest"):
        return run_windows(nodes, params, x, window, learn=learn,
                           hidden=hidden, reference=True)


def li_filter(x: np.ndarray, tau: float) -> np.ndarray:
    """y_t = tau * y_{t-1} + x_t along axis 0 (float64)."""
    y = np.zeros(x.shape, np.float64)
    acc = np.zeros(x.shape[1:], np.float64)
    for t in range(x.shape[0]):
        acc = tau * acc + x[t]
        y[t] = acc
    return y


def compare(label, out, hid, ref_out, ref_hid, w_ro, tau_ro) -> dict:
    """Hold served (out, hid) to the reference.

    Hidden spikes: at most SPIKE_FLIP_MAX disagreements per reference
    spike. Readout (an LI integrator of hidden @ W_ro): the error must be
    explained by the disagreeing spikes and by bf16 rounding of W_ro,
        |dR| <= LI(|dH| @ |W|) (1 + eps) + eps LI(H @ |W|) + READOUT_ATOL,
    which holds because the LI filter is linear with a positive decay.
    """
    n_ref = float(ref_hid.sum())
    require(n_ref > 0, f"{label}: the reference hidden layer never spiked")
    dh = np.abs(hid.astype(np.float64) - ref_hid)
    flip_share = float(dh.sum()) / n_ref
    aw = np.abs(np.asarray(w_ro, np.float64))
    bound = (li_filter(dh @ aw, tau_ro) * (1 + EPS_BF16)
             + EPS_BF16 * li_filter(hid.astype(np.float64) @ aw, tau_ro)
             + READOUT_ATOL)
    err = np.abs(out.astype(np.float64) - ref_out)
    rep = {"readout_max_abs_err": float(err.max()),
           "readout_max_abs_ref": float(np.abs(ref_out).max()),
           "readout_worst_err_over_bound": float((err / bound).max()),
           "hidden_spikes_ref": int(n_ref),
           "hidden_spike_flip_share": flip_share}
    log(f"[{label}] " + json.dumps(rep))
    require(flip_share <= SPIKE_FLIP_MAX,
            f"{label}: {flip_share:.4f} of hidden spikes disagree "
            f"(limit {SPIKE_FLIP_MAX})")
    require((err <= bound).all(),
            f"{label}: readout error {err.max():.3e} exceeds what flips and "
            f"bf16 rounding explain (worst ratio "
            f"{rep['readout_worst_err_over_bound']:.3f})")
    return rep


# --- phase a ---------------------------------------------------------------------


def phase_parity() -> None:
    from repro.kernels import parity, registry
    require(not registry.interpret_mode(), "interpret mode on a TPU")
    # both sides at full f32 precision: this phase checks each kernel's
    # logic; what default-precision rounding does end to end is phase b's
    with jax.default_matmul_precision("highest"):
        for name in registry.names():
            t0 = time.perf_counter()
            rep = parity.check_kernel(name)
            vjp = rep.get("vjp_max_err")
            log(f"[parity] {name}: fwd {rep['forward_max_err']:.3e} "
                f"vjp {'-' if vjp is None else f'{vjp:.3e}'} "
                f"({time.perf_counter() - t0:.1f} s)")
    assert_no_incidents("parity")


# --- phase b ---------------------------------------------------------------------


def serve_streams(eng, xs: np.ndarray) -> np.ndarray:
    """Drive one StreamClient generator per session, round-robin, with
    staggered arrivals; returns outputs (T, S, n_out)."""
    from repro.serve import StreamClient

    client = StreamClient(eng)
    n, T = xs.shape[0], xs.shape[1]
    gens, outs = {}, {i: [] for i in range(n)}
    for i in range(n):
        chunks, off, k = [], 0, i
        while off < T:
            size = CHUNK_SIZES[k % len(CHUNK_SIZES)]
            chunks.append(xs[i, off:off + size])
            off += size
            k += 1
        gens[i] = client.stream(None, chunks)
    live, r = {}, 0
    while gens or live:
        for i in [i for i in gens if i % 8 <= r]:   # staggered arrivals
            live[i] = gens.pop(i)
        for i, g in list(live.items()):
            try:
                outs[i].append(next(g))
            except StopIteration:
                del live[i]
        r += 1
    out = np.stack([np.concatenate(outs[i]) for i in range(n)], axis=1)
    require(out.shape == (T, n, eng.n_out), f"served shape {out.shape}")
    return out


def serve_and_check(label, nodes, params, n_sessions, steps, rng) -> dict:
    from repro.serve import EngineConfig, make_engine
    from repro.serve.engine import _resident_step

    eng = make_engine(nodes, params, EngineConfig(
        window=WINDOW, capacity=CAPACITY, queue_limit=None))
    assert_fused(eng.plan, label)
    xs = spikes(rng, (n_sessions, steps, SHD["n_in"]), INPUT_DENSITY)
    t0 = time.perf_counter()
    out = serve_streams(eng, xs)
    log(f"[{label}] served {n_sessions} sessions x {steps} steps in "
        f"{eng.stats()['windows_run']} windows "
        f"({time.perf_counter() - t0:.1f} s, compile included)")
    require(np.isfinite(out).all(), f"{label}: non-finite served output")

    step = _resident_step(eng.nodes, eng.plan, "flat", eng.cfg.guard)
    hlo = step.lower(params, eng.pool.leaves,
                     np.zeros(CAPACITY, np.int32), np.ones(CAPACITY, bool),
                     jnp.zeros((WINDOW, CAPACITY, SHD["n_in"]),
                               jnp.float32)).as_text()
    assert_kernels_in(hlo, label)

    # the same compiled plan window by window gives the hidden spikes the
    # engine does not expose; its readout must equal the served one
    x = xs.transpose(1, 0, 2)
    mir_out, hid, _ = run_windows(nodes, params, x, WINDOW, jit=True)
    from repro.core.plan import CROSS_ENGINE_ATOL
    mirror_err = float(np.abs(mir_out - out).max())
    require(mirror_err <= CROSS_ENGINE_ATOL,
            f"{label}: engine vs plan.run differ by {mirror_err}")
    ref_out, ref_hid, _ = reference_windows(nodes, params, x, WINDOW)
    rep = compare(label, out, hid, ref_out, ref_hid,
                  params["readout"]["w_hidden"], nodes[-1].neuron.tau)
    assert_no_incidents(label)
    return rep


def phase_serve(rng) -> None:
    from repro.core.snn_layers import make_dhsnn_shd

    nodes, params = make_dhsnn_shd(jax.random.PRNGKey(SEED), **SHD,
                                   dendritic=False)
    serve_and_check("serve_shd", nodes, params, N_SESSIONS, SESSION_STEPS,
                    rng)
    nodes, params = make_dhsnn_shd(jax.random.PRNGKey(SEED + 1), **SHD,
                                   dendritic=True)
    serve_and_check("serve_shd_dhlif", nodes, params, DH_SESSIONS, DH_STEPS,
                    rng)


# --- phase c ---------------------------------------------------------------------


def phase_ecg(rng) -> None:
    from repro.core import plan as plan_mod
    from repro.core.snn_layers import make_srnn_ecg

    nodes, params = make_srnn_ecg(jax.random.PRNGKey(SEED + 2))
    assert_fused(plan_mod.compile_program(nodes), "ecg_alifrec")
    x = spikes(rng, (ECG_STEPS, ECG_BATCH, 4), ECG_DENSITY)
    out, hid, _ = run_windows(nodes, params, x, WINDOW)
    ref_out, ref_hid, _ = reference_windows(nodes, params, x, WINDOW)
    compare("ecg_alifrec", out, hid, ref_out, ref_hid,
            params["readout"]["w_hidden"], nodes[-1].neuron.tau)
    assert_no_incidents("ecg_alifrec")


def stdp_gross_update(prog, pre: np.ndarray, post: np.ndarray) -> np.ndarray:
    """sum_t sum_k |amp_k| |P_k,t|^T |Q_k,t| from the reference spike trains:
    the total update magnitude each synapse sees, which bf16 rounding of
    the trace operands perturbs by at most 2 eps."""
    traj, shifted = {}, {}
    for tr in prog.traces:
        s = pre if tr.source == "pre" else post
        require(tr.decay.kind == "const", f"non-constant trace decay {tr}")
        y = li_filter(tr.scale * s, tr.decay.value)
        traj[tr.name] = y
        shifted[tr.name] = np.concatenate([np.zeros_like(y[:1]), y[:-1]])
    by_name = {t.name: t for t in prog.traces}

    def plane(factors, s):
        v = np.ones_like(s, np.float64)
        for f in factors:
            require(f != "mod", "no modulator in this phase")
            v = v * (s if f == "spikes" else
                     traj[f] if by_name[f].update == "before" else shifted[f])
        return v.reshape(-1, v.shape[-1])

    return sum(abs(t.amp) * plane(t.pre, pre).T @ plane(t.post, post)
               for t in prog.terms)


def phase_plastic(rng) -> None:
    from repro.core import plan as plan_mod
    from repro.core.snn_layers import make_plastic_ff

    nodes, params = make_plastic_ff(jax.random.PRNGKey(SEED + 3))
    plan = plan_mod.compile_program(nodes)
    assert_fused(plan, "plastic_stdp_seq")
    require([p.lower for p in plan.plastic] == [plan_mod.SYN_SEQ],
            f"plastic lowering {plan.plastic}")
    n_in = params["hidden"]["w_input"].shape[0]
    x = spikes(rng, (PLASTIC_STEPS, PLASTIC_BATCH, n_in), PLASTIC_DENSITY)
    out, hid, st = run_windows(nodes, params, x, WINDOW, learn=True)
    ref_out, ref_hid, ref_st = reference_windows(nodes, params, x, WINDOW,
                                                 learn=True)
    compare("plastic_stdp_seq", out, hid, ref_out, ref_hid,
            params["readout"]["w_hidden"], nodes[-1].neuron.tau)

    conn = nodes[0].connections[0]
    w = np.asarray(st["hidden"][f"syn:{conn.key}"]["w"], np.float64)
    w_ref = np.asarray(ref_st["hidden"][f"syn:{conn.key}"]["w"], np.float64)
    w0 = np.asarray(params["hidden"]["w_input"], np.float64)
    # columns whose post spike train flipped learn from different spikes
    same = (hid == ref_hid).all(axis=(0, 1))
    bound = (2 * EPS_BF16 * stdp_gross_update(conn.plastic, x, ref_hid)
             + WEIGHT_ATOL)
    err = np.abs(w - w_ref)[:, same]
    rep = {"learned_max_abs_err": float(err.max(initial=0.0)),
           "learned_max_abs_update": float(np.abs(w_ref - w0).max()),
           "columns_compared": int(same.sum()), "columns": int(same.size)}
    log("[plastic_stdp_seq] " + json.dumps(rep))
    require(same.sum() >= 0.9 * same.size,
            f"too many post columns flipped: {rep}")
    require((err <= bound[:, same]).all(),
            f"learned weights differ beyond 2 eps of the gross update: {rep}")
    assert_no_incidents("plastic_stdp_seq")


def banded_topology(n: int, band: int, rng):
    from repro.core import topology as topo
    rows = np.repeat(np.arange(n), 2 * band + 1)
    cols = rows + np.tile(np.arange(-band, band + 1), n)
    keep = (cols >= 0) & (cols < n)
    # signed weights: a sparsely active sheet (~10% of neurons per step)
    w = (0.1 * rng.standard_normal(int(keep.sum()))).astype(np.float32)
    return topo.encode((rows[keep], cols[keep], w), kind="sparse_coo",
                       n_pre=n, n_post=n)


def phase_topology(rng) -> None:
    from repro.core import events
    from repro.core import plan as plan_mod
    from repro.core.events import Connection
    from repro.core.neuron import LI, LIF
    from repro.core.snn_layers import ff_integrate

    t0 = time.perf_counter()
    enc = banded_topology(TOPO_N, TOPO_BAND, rng)
    tables = enc.lowering()
    log(f"[topology_1e5] {TOPO_N} neurons, {len(tables.jj)} gather blocks "
        f"of {tables.bk}x{tables.bn} ({time.perf_counter() - t0:.1f} s)")
    nodes = [
        events.LayerNode("hidden", LIF(tau=0.8, v_th=0.6), ff_integrate,
                         (Connection("input", topology=enc),), TOPO_N),
        events.LayerNode("readout", LI(tau=0.9), ff_integrate, ("hidden",),
                         TOPO_OUT),
    ]
    w_ro = (0.01 * rng.standard_normal((TOPO_N, TOPO_OUT))).astype(np.float32)
    params = {"hidden": {}, "readout": {"w_hidden": jnp.asarray(w_ro)}}
    plan = plan_mod.compile_program(nodes)
    assert_fused(plan, "topology_1e5")
    x = spikes(rng, (TOPO_STEPS, TOPO_BATCH, TOPO_N), TOPO_DENSITY)
    chunks = [jnp.asarray(x[t:t + TOPO_CHUNK])
              for t in range(0, TOPO_STEPS, TOPO_CHUNK)]
    out = np.concatenate([np.asarray(o) for _, o in
                          plan_mod.run_stream(nodes, params, chunks,
                                              plan=plan)])
    # the same chunks through plan.run again, jitted as run_stream jits
    # its chunks, recording the hidden spikes
    mir_out, hid, _ = run_windows(nodes, params, x, TOPO_CHUNK, jit=True)
    require(np.array_equal(mir_out, out), "run_stream != chunked plan.run")
    ref_out, ref_hid, _ = reference_windows(nodes, params, x, TOPO_CHUNK)
    compare("topology_1e5", out, hid, ref_out, ref_hid, w_ro, 0.9)
    assert_no_incidents("topology_1e5")


def main() -> int:
    dev = require_tpu()
    from repro import paths
    log(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{paths.enable_compile_cache()}")
    rng = np.random.default_rng(SEED)
    for name, phase in (("a_parity", phase_parity),
                        ("b_serve", lambda: phase_serve(rng)),
                        ("c_ecg", lambda: phase_ecg(rng)),
                        ("c_plastic", lambda: phase_plastic(rng)),
                        ("c_topology", lambda: phase_topology(rng))):
        t0 = time.perf_counter()
        phase()
        log(f"== phase {name} ok ({time.perf_counter() - t0:.1f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
