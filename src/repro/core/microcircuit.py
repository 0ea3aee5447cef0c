"""The Potjans & Diesmann (2014) cortical microcircuit as a Program.

Potjans & Diesmann, "The cell-type specific cortical microcircuit",
Cerebral Cortex 24(3):785-806 (2014), doi:10.1093/cercor/bhs358 — the
reference model of NEST (`Potjans_2014`), GeNN, PyNN and SpiNNaker: 1 mm^2
of cortex in 8 populations (77,169 `iaf_psc_exp` neurons), 2.99e8
synapses with a delay each, and Poisson background input. The tables
below are the paper's Tables 4-5 as NEST 3's `network_params.py` holds
them (the "original" V0 setting, thalamic input off).

Every population is one `LayerNode` of `IafPscExp` neurons with a
Poisson background (`Background`); every nonzero projection is a
`FanOut` event edge into its target, so the plan compiles the circuit to
one EVENT segment. `draw_projections` draws the synapses on the host as
NEST's `fixed_total_number` rule with multapses does: a projection's K
synapses each pick their presynaptic and their target neuron uniformly
(so a neuron's out-degree is multinomial); weights are normal with a
relative s.d. of 0.1, redrawn until they keep their sign; delays are
normal with a relative s.d. of 0.5, redrawn until at least dt, then
rounded to the dt grid. `build` takes them as fan-out rows.

`scale` < 1 (tests only) cuts every population's N and every in-degree
by the same factor: K = K(full) * scale^2.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events
from repro.core.events import Connection
from repro.core.neuron import IafPscExp, poisson_table
from repro.core.snn_layers import ff_integrate
from repro.core.topology import FanOut

POPULATIONS = ("L23e", "L23i", "L4e", "L4i", "L5e", "L5i", "L6e", "L6i")
N_FULL = (20683, 5834, 21915, 5479, 4850, 1065, 14395, 2948)
# connection probabilities C[post][pre]
CONN_PROB = (
    (0.1009, 0.1689, 0.0437, 0.0818, 0.0323, 0.0, 0.0076, 0.0),
    (0.1346, 0.1371, 0.0316, 0.0515, 0.0755, 0.0, 0.0042, 0.0),
    (0.0077, 0.0059, 0.0497, 0.135, 0.0067, 0.0003, 0.0453, 0.0),
    (0.0691, 0.0029, 0.0794, 0.1597, 0.0033, 0.0, 0.1057, 0.0),
    (0.1004, 0.0622, 0.0505, 0.0057, 0.0831, 0.3726, 0.0204, 0.0),
    (0.0548, 0.0269, 0.0257, 0.0022, 0.06, 0.3158, 0.0086, 0.0),
    (0.0156, 0.0066, 0.0211, 0.0166, 0.0572, 0.0197, 0.0396, 0.2252),
    (0.0364, 0.001, 0.0034, 0.0005, 0.0277, 0.008, 0.0658, 0.1443),
)
K_EXT = (1600, 1500, 2100, 1900, 2000, 1900, 2900, 2100)
BG_RATE_HZ = 8.0
PSP_MEAN_MV = 0.15
W_REL_SD = 0.1
G = -4.0
L4E_TO_L23E = 2.0
DELAY_MS = (1.5, 0.75)             # excitatory, inhibitory source
DELAY_REL_SD = 0.5
V0_MEAN_MV, V0_SD_MV = -58.0, 10.0


def psc_per_psp(c_m: float, tau_m: float, tau_syn: float) -> float:
    """pA of PSC amplitude per mV of PSP peak (NEST's helpers.py)."""
    sub = 1.0 / (tau_syn - tau_m)
    pre = tau_m * tau_syn / c_m * sub
    frac = (tau_m / tau_syn) ** sub
    return 1.0 / (pre * (frac ** tau_m - frac ** tau_syn))


def synapse_count(c: float, n_pre: int, n_post: int) -> int:
    """NEST's fixed_total_number count for a connection probability."""
    if c <= 0.0:
        return 0
    return int(round(math.log(1.0 - c)
                     / math.log(1.0 - 1.0 / (n_pre * n_post))))


def sizes(scale: float = 1.0) -> Tuple[int, ...]:
    return tuple(max(1, int(round(n * scale))) for n in N_FULL)


def projection_table(scale: float = 1.0, neuron: IafPscExp = IafPscExp()
                     ) -> List[Dict[str, Any]]:
    """Every nonzero projection: post, pre, synapse count K, weight mean
    and s.d. (pA), delay mean and s.d. (ms)."""
    w = psc_per_psp(neuron.c_m, neuron.tau_m, neuron.tau_syn) * PSP_MEAN_MV
    out = []
    for q in range(8):
        for p in range(8):
            k = synapse_count(CONN_PROB[q][p], N_FULL[p], N_FULL[q])
            k = int(round(k * scale * scale))
            if k == 0:
                continue
            exc = p % 2 == 0
            mean = w * (1.0 if exc else G)
            if (q, p) == (0, 2):
                mean *= L4E_TO_L23E
            d = DELAY_MS[0 if exc else 1]
            out.append({"post": q, "pre": p, "k": k, "w_mean": mean,
                        "w_sd": abs(mean) * W_REL_SD, "d_mean": d,
                        "d_sd": d * DELAY_REL_SD})
    return out


def _redrawn(rng, n: int, lo: float) -> np.ndarray:
    """n standard normals, each redrawn until >= lo (NEST's `redraw`)."""
    z = rng.standard_normal(n, dtype=np.float32)
    bad = z < lo
    while bad.any():
        z[bad] = rng.standard_normal(int(bad.sum()), dtype=np.float32)
        bad = z < lo
    return z


def draw_projections(seed: int, scale: float = 1.0, dt: float = 0.1
                     ) -> List[Dict[str, Any]]:
    """Every projection of `projection_table`, drawn on the host, with
    `rows` = (targets int32, weights f32 pA, delays int8 steps, counts
    int32): row j's first counts[j] entries are presynaptic neuron j's
    synapses."""
    n = sizes(scale)
    rng = np.random.default_rng(seed)
    out = []
    for pj in projection_table(scale):
        k, n_pre, mean, sd = pj["k"], n[pj["pre"]], pj["w_mean"], pj["w_sd"]
        counts = rng.multinomial(k, np.full(n_pre, 1.0 / n_pre))
        live = np.arange(max(1, counts.max()))[None, :] < counts[:, None]
        tgt = np.zeros(live.shape, np.int32)
        tgt[live] = rng.integers(0, n[pj["post"]], k, dtype=np.int32)
        w = np.zeros(live.shape, np.float32)
        w[live] = mean + sd * np.sign(mean) * _redrawn(rng, k, -abs(mean) / sd)
        z = _redrawn(rng, k, (dt - pj["d_mean"]) / pj["d_sd"])
        d = np.ones(live.shape, np.int8)
        d[live] = np.maximum(np.round((pj["d_mean"] + pj["d_sd"] * z) / dt),
                             1)
        out.append(dict(pj, rows=(tgt, w, d, counts.astype(np.int32))))
    return out


def build(projections: Sequence[Dict[str, Any]], bg_keys: Sequence,
          n: Sequence[int] = N_FULL, neuron: IafPscExp = None,
          bg_rate_hz: float = BG_RATE_HZ, k_ext: Sequence[int] = K_EXT
          ) -> Tuple[List[events.LayerNode], Dict[str, Any]]:
    """(nodes, params) of the circuit from drawn projections (each with
    its `post` and `pre` population and `rows` as `draw_projections`
    gives them) and one background key (raw uint32[2]) per population;
    `n` the population sizes, `k_ext` their external synapses."""
    if neuron is None:
        w = psc_per_psp(250.0, 10.0, 0.5) * PSP_MEAN_MV
        neuron = IafPscExp(bg_weight=w)
    into: Dict[int, List[Connection]] = {q: [] for q in range(len(n))}
    for pj in projections:
        q, p = pj["post"], pj["pre"]
        into[q].append(Connection(
            "self" if p == q else POPULATIONS[p],
            topology=FanOut.from_rows(*pj["rows"], n_post=n[q])))
    nodes, params = [], {}
    for q, name in enumerate(POPULATIONS):
        nodes.append(events.LayerNode(name, neuron, ff_integrate,
                                      tuple(into[q]), n[q]))
        lam = k_ext[q] * bg_rate_hz * neuron.dt * 1e-3
        params[name] = {"neuron": {
            "bg_cdf": jnp.asarray(poisson_table(np.full(n[q], lam))),
            "bg_key": jnp.asarray(bg_keys[q], jnp.uint32)}}
    return nodes, params


def initial_state(nodes: Sequence[events.LayerNode], params: Dict[str, Any],
                  v0: Sequence[jax.Array], batch: int = 1):
    """The state at step 0: each population's V (mV relative to E_L) from
    `v0`, everything else at rest."""
    state = events.init_state(list(nodes), batch, jnp.float32, params)
    for n, v in zip(nodes, v0):
        state[n.name]["v"] = jnp.broadcast_to(
            jnp.asarray(v, jnp.float32), (batch, n.out_dim))
    return state


def draw_v0(key, scale: float = 1.0, e_l: float = -65.0) -> List[jax.Array]:
    """V0 ~ N(-58, 10) mV per neuron, relative to E_L."""
    return [V0_MEAN_MV - e_l + V0_SD_MV * jax.random.normal(
        jax.random.fold_in(key, q), (m,), jnp.float32)
        for q, m in enumerate(sizes(scale))]


def make_microcircuit(seed: int, scale: float = 1.0):
    """-> (nodes, params, initial state) of the circuit at `scale`."""
    projections = draw_projections(seed, scale)
    k_bg, k_v0 = jax.random.split(jax.random.PRNGKey(seed))
    bg_keys = [jax.random.fold_in(k_bg, q) for q in range(8)]
    nodes, params = build(projections, bg_keys, sizes(scale))
    return nodes, params, initial_state(nodes, params, draw_v0(k_v0, scale))


__all__ = ["POPULATIONS", "N_FULL", "CONN_PROB", "K_EXT",
           "psc_per_psp", "synapse_count", "sizes", "projection_table",
           "draw_projections", "build", "initial_state", "draw_v0",
           "make_microcircuit"]
