"""Quickstart: the paper's stack in 60 lines.

1. program a heterogeneous spiking network with the neuron DSL,
2. encode its topology with the 2-level tables (storage accounting),
3. compile + run it through the fused execution-plan engine,
4. map it onto the chip grid with the compiler,
5. estimate energy with the behavioural simulator.

Run: PYTHONPATH=src python examples/quickstart.py
(Set REPRO_SNN_EXPLAIN=1 to see the compiled segment schedule for every
Program anywhere in the stack, not just the one printed here.)
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events, plan, topology
from repro.core.mapping import Op, compile_network
from repro.core.neuron import ALIF, LI
from repro.core.simulator import LayerStats, simulate
from repro.core.snn_layers import ff_integrate

key = jax.random.PRNGKey(0)

# 1. a 2-layer network: 64 ALIF neurons (adaptive threshold) -> 10 readouts
n_in, n_hidden, n_out = 32, 64, 10
nodes = [
    events.LayerNode("hidden", ALIF(surrogate="sigmoid", alpha=4.0),
                     ff_integrate, inputs=("input", "self"), out_dim=n_hidden),
    events.LayerNode("readout", LI(), ff_integrate, inputs=("hidden",),
                     out_dim=n_out),
]
params = {
    "hidden": {"w_input": 0.5 * jax.random.normal(key, (n_in, n_hidden)),
               "w_self": 0.05 * jax.random.normal(key, (n_hidden, n_hidden)),
               "neuron": ALIF().param_init(key, (n_hidden,))},
    "readout": {"w_hidden": 0.3 * jax.random.normal(key, (n_hidden, n_out))},
}

# 2. topology tables: the fan-in side of `hidden` as a type-2 FC entry
enc = topology.encode_fc(np.asarray(params["hidden"]["w_input"]), n_cores=4)
print(f"topology: {enc.storage_bits()/8:.0f} B encoded vs "
      f"{enc.baseline_bits()/8:.0f} B unrolled "
      f"({enc.baseline_bits()/enc.storage_bits():.0f}x smaller)")

# 3. compile the Program to a fused execution plan and run it: the ALIF
# hidden layer pattern-matches the adaptive-threshold kernel (fused_rec via
# `alifrec`), the LI readout the associative `linrec` scan — no stepper
# fallback. `plan.run` is a drop-in for `events.run` (same signature and
# numerics); `plan.run_reference` runs the interpreted stepper instead.
compiled = plan.compile_program(nodes)
print(f"plan: {compiled.describe()}")
x = (jax.random.uniform(key, (100, 8, n_in)) < 0.05).astype(jnp.float32)
_, outs, recs = plan.run(nodes, params, x, record=("hidden",),
                         plan=compiled)
rate = float(jnp.mean(recs["hidden"]))
print(f"ran 100 INTEG/FIRE timesteps: hidden spike rate {rate:.1%}, "
      f"readout shape {outs.shape}")
_, ref_outs, _ = plan.run_reference(nodes, params, x)
print(f"max |plan - stepper reference| on the readout: "
      f"{float(jnp.max(jnp.abs(outs - ref_outs))):.1e}")

# 4. compile onto the chip grid
ops = [Op("hidden", "fc", n_hidden, n_in + n_hidden, ("input",)),
       Op("readout", "fc", n_out, n_hidden, ("hidden",))]
mapping = compile_network(ops, anneal_iters=200)
print(f"mapped to {mapping.meta['n_cores']} cores, "
      f"placement cost {mapping.cost:.0f} packet-hops")

# 5. energy estimate vs a dense GPU
stats = [LayerStats("hidden", n_hidden, n_hidden + n_out, rate,
                    2.0 * n_hidden * (n_in + n_hidden))]
rep = simulate(stats, timesteps=100)
print(f"simulated: {rep.power_w:.2f} W, {rep.efficiency_x:.0f}x better "
      f"FPS/W than the dense-GPU baseline")
