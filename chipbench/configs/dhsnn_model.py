"""The DH-SNN as the program builds it (`make_dhsnn_shd(dendritic=True)`),
holding the weights the benchmark drew from the seed."""

from __future__ import annotations

from typing import Any, Dict

import jax


def build(cfg: Dict[str, Any], w: Dict[str, jax.Array]):
    """-> (nodes, params) of the program, with the benchmark's weights in
    place of the builder's own. Raises if the program's model departs
    from what the configuration states."""
    from repro.core.snn_layers import make_dhsnn_shd

    nodes, p0 = make_dhsnn_shd(jax.random.PRNGKey(0), n_in=cfg["n_in"],
                               n_hidden=cfg["n_hidden"], n_out=cfg["n_out"],
                               n_branches=cfg["n_branches"], dendritic=True)
    params = {"hidden": {"w_input": w["w_in"],
                         "neuron": {"w_tau_d": w["tau_d_logit"],
                                    "w_tau_s": w["tau_s_logit"]}},
              "readout": {"w_hidden": w["w_ro"]}}
    shapes = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), p0)
    ours = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), params)
    if shapes != ours:
        raise ValueError(f"parameter tree {ours} != the builder's {shapes}")
    hidden, readout = nodes
    if (hidden.neuron.v_th != cfg["v_th"]
            or hidden.neuron.n_branches != cfg["n_branches"]
            or readout.neuron.tau != cfg["readout_tau"]):
        raise ValueError(f"program model {nodes} departs from {cfg}")
    return nodes, params
