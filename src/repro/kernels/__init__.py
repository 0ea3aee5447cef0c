"""Pallas TPU kernels for the compute hot-spots TaiBai optimizes in hardware.

Each kernel is a package with three modules:

  kernel.py — the `pl.pallas_call` body with explicit BlockSpec VMEM tiling
              (TPU is the target; `interpret=True` executes the same body in
              Python on CPU for validation)
  ops.py    — the jit'd public wrapper; registers a `KernelSpec` with the
              unified registry and dispatches through it (ref vs Pallas
              policy, block resolution, tuning-cache lookup all live in
              `registry.py`, not per family)
  ref.py    — the pure-jnp oracle the tests assert against

Cross-cutting machinery (mirroring the paper's single multi-granularity
instruction set over heterogeneous dynamics):

  registry.py — KernelSpec registration + the one dispatch/policy layer,
                including the interpret-mode -> ref fallback off-TPU (the
                compiled path on a chip never falls back)
  incidents.py— per-process incident log of recorded degradations
                (query with `repro.kernels.incidents()`); REPRO_STRICT=1
                turns every degradation into a raised FallbackError
  tuning.py   — autotuner sweeping per-spec block candidates, persisted to
                a JSON cache keyed by (kernel, backend, shape bucket)
  parity.py   — ref<->Pallas forward + VJP agreement harness (fast CI tier)
  common.py   — padding + backend helpers shared by the wrappers

Kernels (paper instruction -> TPU adaptation):

  linrec    DIFF     chunked diagonal first-order recurrence y=a*y+x
                     (serves LIF/ALIF membranes, Mamba2 scans, RWKV6 decay)
  lif       DIFF+SEND fused integrate-fire over time (threshold/reset is not
                     associative, so this is its own serial-in-T kernel)
  spikemm   FINDIDX+LOCACC event-gated block-sparse spike x weight matmul:
                     silent (all-zero) spike blocks skip the MXU entirely
  attention —        flash attention (online softmax) for the LM substrate's
                     prefill path
  stdp      (FIRE-stage learning) fused trace-outer-product weight update:
                     one HBM->VMEM->HBM pass over the weight tile per step
"""

from repro.kernels.incidents import (FallbackError, FallbackEvent,  # noqa: E402
                                     clear_incidents, incidents,
                                     strict_mode)

__all__ = ["FallbackError", "FallbackEvent", "clear_incidents", "incidents",
           "strict_mode"]
