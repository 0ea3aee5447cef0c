"""Ref <-> Pallas parity harness: the backbone of the fast CI tier.

Every kernel family registers canonical inputs, tolerances, and its
differentiable argument set in its `KernelSpec`; this module turns that
into a uniform check that the Pallas path (interpret mode off-TPU, real
Mosaic on TPU) agrees with the pure-jnp oracle on

  * the forward outputs (every leaf of the output pytree), and
  * the VJP: gradients of a fixed nonlinear scalar loss with respect to
    every `diff_argnums` input.

`check_kernel` raises AssertionError with the offending kernel/leaf on
mismatch and returns a numeric report on success, so it doubles as a test
assertion (tests/test_registry.py), a health probe
(`python -m repro.kernels.parity`) and the first phase of `chip_smoke.py`.

The oracle side always runs the XLA reference at full f32 matmul
precision: on a TPU, `auto` dispatch would otherwise pick the Pallas
kernel for both sides, and XLA's default f32 matmul there rounds its
operands to bf16.
"""

from __future__ import annotations

import contextlib
import os
from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import registry


@contextlib.contextmanager
def _reference() -> Iterator[None]:
    """Dispatch to the XLA reference, with f32 matmuls at full precision."""
    old = os.environ.get("REPRO_KERNEL_IMPL")
    os.environ["REPRO_KERNEL_IMPL"] = "ref"
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        if old is None:
            os.environ.pop("REPRO_KERNEL_IMPL")
        else:
            os.environ["REPRO_KERNEL_IMPL"] = old


def _loss(out) -> jax.Array:
    """Fixed nonlinear scalar reduction: weights every output leaf, keeps
    cotangents O(1), and breaks the symmetry a plain sum() would miss."""
    total = 0.0
    for i, leaf in enumerate(jax.tree_util.tree_leaves(out)):
        total = total + jnp.sum(jnp.sin(leaf.astype(jnp.float32) * (0.7 + i)))
    return total


def _max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) -
                                 jnp.asarray(b, jnp.float32))))


def check_kernel(name: str, *, seed: int = 0,
                 check_vjp: bool = True) -> Dict[str, float]:
    """Assert forward + VJP parity for one registered kernel."""
    spec = registry.get(name)
    if spec.make_inputs is None:
        raise ValueError(f"kernel {name!r} registered without make_inputs")
    args = spec.make_inputs(jax.random.PRNGKey(seed))

    with _reference():
        ref_out = spec.apply(args, False)
    pal_out = spec.apply(args, True)
    ref_leaves = jax.tree_util.tree_leaves(ref_out)
    pal_leaves = jax.tree_util.tree_leaves(pal_out)
    assert len(ref_leaves) == len(pal_leaves), (
        f"{name}: output pytree mismatch")
    report = {}
    fwd_err = 0.0
    for i, (r, p) in enumerate(zip(ref_leaves, pal_leaves)):
        assert r.shape == p.shape, (
            f"{name}: leaf {i} shape {p.shape} != ref {r.shape}")
        err = _max_err(r, p)
        fwd_err = max(fwd_err, err)
        assert err <= spec.tol, (
            f"{name}: forward leaf {i} max|err| {err:.3e} > tol {spec.tol}")
    report["forward_max_err"] = fwd_err

    if check_vjp and spec.diff_argnums:
        with _reference():
            grad_ref = jax.grad(lambda *a: _loss(spec.apply(a, False)),
                                spec.diff_argnums)(*args)
        grad_pal = jax.grad(lambda *a: _loss(spec.apply(a, True)),
                            spec.diff_argnums)(*args)
        vjp_err = 0.0
        for argnum, r, p in zip(spec.diff_argnums, grad_ref, grad_pal):
            err = _max_err(r, p)
            vjp_err = max(vjp_err, err)
            assert err <= spec.tol, (
                f"{name}: VJP wrt arg {argnum} max|err| {err:.3e} "
                f"> tol {spec.tol}")
        report["vjp_max_err"] = vjp_err
    return report


def check_all(*, seed: int = 0,
              names: Optional[Tuple[str, ...]] = None) -> Dict[str, Dict]:
    """Parity-check every registered kernel; raises on first failure."""
    registry.ensure_registered()
    return {name: check_kernel(name, seed=seed)
            for name in (names or registry.names())}


def main() -> None:
    reports = check_all()
    width = max(len(n) for n in reports)
    for name, rep in reports.items():
        vjp = rep.get("vjp_max_err")
        vjp_s = f"vjp {vjp:.3e}" if vjp is not None else "forward-only"
        print(f"{name:<{width}}  fwd {rep['forward_max_err']:.3e}  {vjp_s}")
    print(f"parity OK for {len(reports)} kernels "
          f"(backend={jax.default_backend()})")


if __name__ == "__main__":
    main()


__all__ = ["check_kernel", "check_all"]
