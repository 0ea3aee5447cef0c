"""Plain reference of the Potjans-Diesmann (2014) microcircuit, and the
synapses, background keys and initial potentials every run draws from its
seed.

Neurons are NEST's iaf_psc_exp, integrated exactly on the dt grid with V
held relative to E_L, in NEST's update order:

    V <- P22 V + P21 I         unless refractory (then V is held)
    I <- P11 I + arrivals_t + w_ext * poisson_t
    spike where V >= 15 mV; V <- 0; refractory for t_ref / dt steps

and a spike of neuron j at step t adds each synapse's weight into the
arrivals of its target at step t + delay. A per-step loop on the host
finds the step's spikes and pushes their rows of the raw synapse arrays
into a ring of arrivals; the Poisson counts invert one uniform per neuron
from fold_in(key of its population, step) through the Poisson CDF. Float32
throughout, every product at full precision. It imports nothing of the
program and takes nothing the program made.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

THRESHOLD_MV = 15.0


# --- parameters from the configuration ---------------------------------------


def propagators(cfg: Dict[str, Any]):
    """(P22, P21, P11) of iaf_psc_exp in float64."""
    nr, h = cfg["neuron"], cfg["dt_ms"]
    tm, ts, c = nr["tau_m_ms"], nr["tau_syn_ms"], nr["c_m_pF"]
    p22, p11 = math.exp(-h / tm), math.exp(-h / ts)
    return p22, tm * ts / (c * (tm - ts)) * (p22 - p11), p11


def psc_mean(cfg: Dict[str, Any]) -> float:
    """pA of the 0.15 mV PSP (NEST's postsynaptic_potential_to_current)."""
    nr = cfg["neuron"]
    tm, ts, c = nr["tau_m_ms"], nr["tau_syn_ms"], nr["c_m_pF"]
    sub = 1.0 / (ts - tm)
    pre = tm * ts / c * sub
    frac = (tm / ts) ** sub
    return cfg["psp_mean_mV"] / (pre * (frac ** tm - frac ** ts))


def refractory_steps(cfg: Dict[str, Any]) -> int:
    return int(round(cfg["neuron"]["t_ref_ms"] / cfg["dt_ms"]))


def projections(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every nonzero projection (post, pre, K, weight and delay law)."""
    n, C, w = cfg["n"], cfg["conn_prob"], psc_mean(cfg)
    out = []
    for q in range(len(n)):
        for p in range(len(n)):
            if C[q][p] <= 0:
                continue
            k = int(round(math.log(1 - C[q][p])
                          / math.log(1 - 1 / (n[p] * n[q]))))
            exc = p % 2 == 0
            mean = w * (1.0 if exc else cfg["g"])
            if (q, p) == (0, 2):
                mean *= cfg["l4e_to_l23e_factor"]
            d = cfg["delay_mean_ms"]["exc" if exc else "inh"]
            out.append({"post": q, "pre": p, "k": k, "w_mean": mean,
                        "w_sd": abs(mean) * cfg["w_rel_sd"], "d_mean": d,
                        "d_sd": d * cfg["delay_rel_sd"]})
    return out


def width(k: int, n_pre: int) -> int:
    """Row width that no seed is expected to pass: mean out-degree + 7 sd
    (a draw past it widens the rows; nothing is dropped)."""
    m = k / n_pre
    return max(1, int(math.ceil(m + 7.0 * math.sqrt(m) + 1.0)))


# --- what a run draws from its seed ---------------------------------------------


def _redrawn(rng, n: int, lo: float) -> np.ndarray:
    """n standard normals (float32), each redrawn until >= lo (NEST's
    `redraw`)."""
    z = rng.standard_normal(n, dtype=np.float32)
    bad = z < lo
    while bad.any():
        z[bad] = rng.standard_normal(int(bad.sum()), dtype=np.float32)
        bad = z < lo
    return z


def make_synapses(cfg: Dict[str, Any], rng) -> List[Dict[str, Any]]:
    """Every projection with its fan-out rows, as NEST's fixed_total_number
    rule with multapses: a projection's K synapses each pick their
    presynaptic neuron uniformly, so the out-degrees are multinomial, and
    their target uniformly; weights normal, redrawn until they keep their
    sign; delays normal, redrawn until >= dt and rounded to the grid.
    Drawn on the host (numpy, from `rng`; a device draw spent minutes of
    set-up on a TPU v5e) and put on the device a projection at a time. `rows` = (targets int32 in the target population, weights f32
    pA, delays int8 steps, counts int32); row j's first counts[j] entries
    are neuron j's synapses."""
    n, dt = cfg["n"], cfg["dt_ms"]
    out = []
    for pj in projections(cfg):
        k, n_pre = pj["k"], n[pj["pre"]]
        counts = rng.multinomial(k, np.full(n_pre, 1 / n_pre))
        F = max(width(k, n_pre), int(counts.max()))
        live = np.arange(F)[None, :] < counts[:, None]
        tgt = np.zeros((n_pre, F), np.int32)
        tgt[live] = rng.integers(0, n[pj["post"]], k, dtype=np.int32)
        w = np.zeros((n_pre, F), np.float32)
        # a weight keeps its sign: |w_mean| / w_sd = 10 s.d. from zero
        w[live] = pj["w_mean"] + pj["w_sd"] * np.sign(pj["w_mean"]) * \
            _redrawn(rng, k, -abs(pj["w_mean"]) / pj["w_sd"])
        z = _redrawn(rng, k, (dt - pj["d_mean"]) / pj["d_sd"])
        d = np.ones((n_pre, F), np.int8)
        d[live] = np.maximum(np.round((pj["d_mean"] + pj["d_sd"] * z) / dt),
                             1).astype(np.int8)
        out.append(dict(pj, rows=(jnp.asarray(tgt), jnp.asarray(w),
                                  jnp.asarray(d),
                                  jnp.asarray(counts.astype(np.int32)))))
    return out


def background_keys(cfg: Dict[str, Any], key) -> List[jax.Array]:
    """Raw PRNG key data (uint32[2]) of each population's background."""
    return [jax.random.fold_in(key, q) for q in range(len(cfg["n"]))]


def initial_v(cfg: Dict[str, Any], key) -> List[jax.Array]:
    """V0 ~ N(mean, sd) per neuron, relative to E_L."""
    v0, e_l = cfg["v0"], cfg["neuron"]["e_l_mV"]
    return [v0["mean_mV"] - e_l + v0["sd_mV"] * jax.random.normal(
        jax.random.fold_in(key, q), (m,), jnp.float32)
        for q, m in enumerate(cfg["n"])]


def poisson_table(lam: float) -> np.ndarray:
    """Float32 CDF of Poisson(lam), float64 sums rounded once, up to the
    first entry that rounds to 1."""
    p = math.exp(-lam)
    cdf, rows, k = p, [], 0
    while True:
        rows.append(np.float32(cdf))
        if rows[-1] == np.float32(1.0):
            return np.asarray(rows, np.float32)
        k += 1
        p = p * lam / k
        cdf = cdf + p


def background_lambda(cfg: Dict[str, Any]) -> List[float]:
    return [k * cfg["bg_rate_hz"] * cfg["dt_ms"] * 1e-3
            for k in cfg["k_ext"]]


def poisson_counts(key, t, table: jax.Array, n: int) -> jax.Array:
    """One population's background counts at step t."""
    u = jax.random.uniform(jax.random.fold_in(key, t), (n,), jnp.float32)
    return jnp.sum(u[None, :] >= table[:, None], axis=0, dtype=jnp.int32)


# --- the reference simulation ----------------------------------------------------


def lower_precision(syn: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The control's synapses: every weight rounded to bfloat16."""
    out = []
    for pj in syn:
        t, w, d, c = pj["rows"]
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
        out.append(dict(pj, rows=(t, w, d, c)))
    return out


class Reference:
    """The circuit stepped in time order from a given state.

    state: v, i (f32), ref (int32) of all neurons side by side in
    population order, the ring of arrivals (R, N) f32, the step t."""

    def __init__(self, cfg: Dict[str, Any], syn: List[Dict[str, Any]],
                 keys: Sequence[jax.Array]):
        self.cfg = cfg
        self.n = list(cfg["n"])
        self.base = np.concatenate([[0], np.cumsum(self.n)[:-1]]).astype(int)
        self.N = int(sum(self.n))
        self.syn = syn
        self.keys = list(keys)
        self.tables = [jnp.asarray(poisson_table(lam))
                       for lam in background_lambda(cfg)]
        p22, p21, p11 = propagators(cfg)
        self.p = (np.float32(p22), np.float32(p21), np.float32(p11))
        self.w_ext = np.float32(psc_mean(cfg))
        self.n_ref = refractory_steps(cfg)
        self.fire = jax.jit(self._fire)
        self.theta = np.float32(cfg["neuron"]["v_th_mV"]
                                - cfg["neuron"]["e_l_mV"])

    def _fire(self, v, i, ref, ring, t):
        """One step of every neuron (its constants: the propagators and
        the small background tables)."""
        R = ring.shape[0]
        slot = t % R
        arrivals = ring[slot]
        ring = ring.at[slot].set(0.0)
        bg = jnp.concatenate([
            poisson_counts(k, t, tab, m)
            for k, tab, m in zip(self.keys, self.tables, self.n)])
        p22, p21, p11 = self.p
        held = ref > 0
        v = jnp.where(held, v, p22 * v + p21 * i)
        i = p11 * i + (arrivals + self.w_ext * bg.astype(jnp.float32))
        s = v >= self.theta
        v = jnp.where(s, 0.0, v)
        ref = jnp.where(s, self.n_ref, jnp.where(held, ref - 1, ref))
        return v, i, ref, ring, s

    @staticmethod
    def _push(ring, idx, t, rows, meta):
        """The synapses of the step's spiking neurons into the ring at slot
        (t + delay) mod R. idx (populations, S): each population's
        spiking neurons (indices in it, -1 padding); `rows` every
        projection's arrays (operands, never constants), `meta` its
        presynaptic population and its target population's offset."""
        R = ring.shape[0]
        for (tgt, w, d, cnt), (p, base) in zip(rows, meta):
            live_j = idx[p] >= 0
            j = jnp.where(live_j, idx[p], 0)
            live = live_j[:, None] & (jnp.arange(tgt.shape[1])[None, :]
                                      < cnt[j][:, None])
            slot = (t + d[j].astype(jnp.int32)) % R
            ring = ring.at[slot, base + tgt[j]].add(
                jnp.where(live, w[j], 0.0))
        return ring

    def run(self, state: Dict[str, Any], T: int):
        """-> (final state, spike steps, spike neurons) over T steps."""
        fire, push = self.fire, _PUSH
        bounds = np.concatenate([self.base, [self.N]])
        rows = tuple(pj["rows"] for pj in self.syn)
        meta = tuple((pj["pre"], int(self.base[pj["post"]]))
                     for pj in self.syn)
        v, i, ref, ring = (state["v"], state["i"], state["ref"],
                           state["ring"])
        t0 = int(state["t"])
        steps, neurons = [], []
        with jax.default_matmul_precision("highest"):
            for t in range(t0, t0 + T):
                v, i, ref, ring, s = fire(v, i, ref, ring, jnp.int32(t))
                idx = np.flatnonzero(np.asarray(s))
                steps.append(np.full(idx.shape, t))
                neurons.append(idx)
                if idx.size:
                    pop = np.searchsorted(bounds, idx, "right") - 1
                    most = int(np.bincount(pop).max())
                    size = 1 << max(4, (most - 1).bit_length())
                    pad = np.full((len(self.n), size), -1, np.int32)
                    for p in np.unique(pop):
                        mine = idx[pop == p] - bounds[p]
                        pad[p, :mine.size] = mine
                    ring = push(ring, jnp.asarray(pad), jnp.int32(t), rows,
                                meta)
        final = {"v": v, "i": i, "ref": ref, "ring": ring, "t": t0 + T}
        return final, np.concatenate(steps), np.concatenate(neurons)


_PUSH = jax.jit(Reference._push, static_argnums=4)
