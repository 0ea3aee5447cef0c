"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

`make_production_mesh` is a FUNCTION so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS first; smoke tests see 1 CPU).

Axis semantics (DESIGN.md §4):
  pod    inter-pod data parallelism (DCN/ICI proxy links — the paper's
         inter-chip proxy units; gradient all-reduce crosses it once/step)
  data   intra-pod data parallelism (+ FSDP shard axis for big configs)
  model  tensor/expert parallelism (the paper's PSUM fan-in expansion)
"""

from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh for tests/elastic rescale (e.g. (4, 2) on 8 devices).

    Axes are `Auto`: the sharding rules place arrays with
    `with_sharding_constraint`, which takes only Auto axes, while
    `jax.make_mesh` defaults to Explicit ones."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def describe(mesh: jax.sharding.Mesh) -> str:
    return f"mesh{dict(zip(mesh.axis_names, mesh.devices.shape))}"
