"""The microcircuit as the program builds it (`repro.core.microcircuit`),
holding the synapses, background keys and initial potentials the
benchmark drew from the seed."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax

from repro.core import microcircuit as mc
from repro.core.neuron import IafPscExp


def build(cfg: Dict[str, Any], syn: List[Dict[str, Any]],
          keys: Sequence[jax.Array], v0: Sequence[jax.Array]):
    """-> (nodes, params, initial state) of the program, its neuron, sizes
    and background taken from the configuration and its synapses as
    drawn."""
    nr = cfg["neuron"]
    neuron = IafPscExp(c_m=nr["c_m_pF"], tau_m=nr["tau_m_ms"],
                       tau_syn=nr["tau_syn_ms"], t_ref=nr["t_ref_ms"],
                       e_l=nr["e_l_mV"], v_th=nr["v_th_mV"],
                       v_reset=nr["v_reset_mV"], dt=cfg["dt_ms"],
                       bg_weight=mc.psc_per_psp(nr["c_m_pF"], nr["tau_m_ms"],
                                                nr["tau_syn_ms"])
                       * cfg["psp_mean_mV"])
    nodes, params = mc.build(syn, keys, n=cfg["n"], neuron=neuron,
                             bg_rate_hz=cfg["bg_rate_hz"], k_ext=cfg["k_ext"])
    return nodes, params, mc.initial_state(nodes, params, v0)
