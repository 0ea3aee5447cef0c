"""Pipeline-parallel tests: the GPipe schedule must match sequential stage
application exactly (values and gradients), verified on an 8-fake-device
mesh in a subprocess (device-count override must not leak into this process)."""

import json
import os
import subprocess
import sys

import pytest

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.sharding.pipeline import microbatch, pipeline_apply, pipeline_loss_fn

S, M, mb, D = 4, 8, 4, 16
mesh = jax.make_mesh((S, 2), ("stage", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

key = jax.random.PRNGKey(0)
Ws = 0.3 * jax.random.normal(key, (S, D, D))
bs = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (S, D))
params = {"w": Ws, "b": bs}

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

x = jax.random.normal(jax.random.fold_in(key, 2), (M * mb, D))
xm = microbatch(x, M)

# sequential reference
ref = xm
for s in range(S):
    ps = jax.tree.map(lambda a: a[s], params)
    ref = jax.vmap(lambda xx: stage_fn(ps, xx))(ref)

out = pipeline_apply(stage_fn, params, xm, mesh)
err_fwd = float(jnp.max(jnp.abs(out - ref)))

# gradients through the pipeline vs sequential
y = jax.random.normal(jax.random.fold_in(key, 3), (M * mb, D))
ym = microbatch(y, M)

def loss_seq(params):
    h = xm
    for s in range(S):
        ps = jax.tree.map(lambda a: a[s], params)
        h = jax.vmap(lambda xx: stage_fn(ps, xx))(h)
    return jnp.mean((h - ym) ** 2)

loss_pipe = pipeline_loss_fn(stage_fn, lambda o, t: jnp.mean((o - t) ** 2),
                             mesh, n_micro=M)
g1 = jax.grad(loss_seq)(params)
g2 = jax.grad(lambda p: loss_pipe(p, x, y))(params)
err_g = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
print(json.dumps({"err_fwd": err_fwd, "err_grad": err_g}))
"""


@pytest.mark.slow
def test_pipeline_matches_sequential():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err_fwd"] < 1e-5, rec
    assert rec["err_grad"] < 1e-5, rec
