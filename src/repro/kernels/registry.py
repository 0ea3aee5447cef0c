"""Unified kernel registry: one registration + dispatch point for every
Pallas kernel family.

TaiBai's headline property is *programmability* — a multi-granularity
instruction set where LIF dynamics, plasticity, and dense attention run on
one substrate. The TPU-side analogue is this registry: each kernel family
registers its pure-jnp reference, its Pallas implementation, and a tunable
block specification ONCE, and every cross-cutting concern lives here
instead of being copy-pasted per family:

  * ref-vs-pallas dispatch policy (`force_pallas` arg, `REPRO_KERNEL_IMPL`
    env, interpret mode off-TPU),
  * block-shape resolution (per-axis alignment fitting, tuned-cache lookup
    via `repro.kernels.tuning`, explicit per-call overrides),
  * enumeration for the parity harness (`repro.kernels.parity`) and the
    autotuner / benchmarks.

Registering a new kernel means building one `KernelSpec` and calling
`register()` at the bottom of its `ops.py` — see any existing family for
the pattern. The spec carries everything the generic machinery needs:

    register(KernelSpec(
        name="mykern",
        ref=mykern_ref,                  # pure-jnp oracle
        pallas=_pallas_impl,             # (*args, blocks=, interpret=, **static)
        apply=lambda args, force=False: mykern(*args, force),
        block_axes=(BlockAxis("bt", "T", preferred=256, align=8), ...),
        dims_of=lambda *args: {"T": args[0].shape[0], ...},
        candidates=({"bt": 128}, {"bt": 256}),   # autotune sweep
        make_inputs=_make_inputs,        # key -> args (parity + tuning)
        diff_argnums=(0, 1),             # () => forward-only parity
        tol=1e-4,
    ))

Dispatch is also where the runtime's *failure story* lives (see
`dispatch`). Every degradation is recorded as a structured `FallbackEvent`
on the per-process incident log (`repro.kernels.incidents()`). Off-TPU, an
interpret-mode Pallas failure or a VMEM-model rejection degrades to the
reference, unless `REPRO_STRICT=1` makes it raise `FallbackError`. With
compiled kernels (a real chip) nothing degrades: the same failures raise,
so a chip run cannot pass on the reference while the chip ran no kernel.

Environment knobs:
  REPRO_KERNEL_IMPL     = ref | pallas | auto   (auto: pallas on TPU,
                                                 ref elsewhere)
  REPRO_PALLAS_INTERPRET= 0                     (off-TPU: trace compiled
                                                 Mosaic kernels, e.g. to
                                                 compile for a described
                                                 chip)
  REPRO_TUNING_CACHE    = path to the JSON tuning cache
  REPRO_STRICT          = 1: degradations raise instead of falling back
  REPRO_FAULTS          = fault-injection spec (see repro.core.faults)
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

from repro.kernels.common import on_tpu
from repro.kernels.incidents import FallbackError, degrade  # noqa: F401


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------


def interpret_mode() -> bool:
    """Pallas kernels execute in interpret mode off-TPU, never on a TPU.

    `REPRO_PALLAS_INTERPRET=0` turns it off off-TPU too, so the compiled
    kernels can be traced and compiled for a chip that is described but
    not attached."""
    if on_tpu():
        return False
    return os.environ.get("REPRO_PALLAS_INTERPRET") != "0"


def use_pallas(force_pallas: bool = False) -> bool:
    """Resolve the ref-vs-pallas choice for one call.

    `force_pallas=True` (the per-call/config escape hatch) always wins;
    otherwise `REPRO_KERNEL_IMPL` picks globally. `auto` (the default)
    prefers the Mosaic kernels on a real TPU — every family is gated by the
    ref<->Pallas parity harness, so the fast path is the default where it
    actually is fast — and keeps the XLA reference elsewhere (interpret-mode
    Pallas on CPU is a debugging tool, not an execution engine).
    """
    if force_pallas:
        return True
    mode = os.environ.get("REPRO_KERNEL_IMPL", "auto")
    if mode not in ("ref", "pallas", "auto"):
        raise ValueError(f"REPRO_KERNEL_IMPL={mode!r}: "
                         "expected 'ref', 'pallas', or 'auto'")
    if mode == "pallas":
        return True
    if mode == "ref":
        return False
    return on_tpu()


# ---------------------------------------------------------------------------
# block-shape resolution
# ---------------------------------------------------------------------------


def fit_block(n: int, preferred: int, align: int) -> int:
    """Largest block <= preferred that is a multiple of `align` and covers n
    evenly after padding; falls back to n rounded up to `align` when small."""
    if n <= preferred:
        return max(align, -(-n // align) * align)
    return preferred


def exact_block(n: int, preferred: int) -> int:
    """Largest block <= preferred that divides n exactly (no padding).

    Required for axes that chain state across grid steps (e.g. the LIF time
    axis): zero-padding such an axis would run extra dynamics steps and
    corrupt the carried state, so the block must tile the axis exactly.
    Worst case (prime n > preferred) degrades to 1 — correct, just serial.
    """
    b = min(max(1, n), max(1, preferred))
    while n % b:
        b -= 1
    return b


@dataclasses.dataclass(frozen=True)
class BlockAxis:
    """One tunable block dimension of a kernel's grid.

    `name` is the key in the blocks dict handed to the Pallas wrapper;
    `dim` names the logical tensor dimension (as produced by
    `KernelSpec.dims_of`) this block tiles; `preferred`/`align` reproduce
    the family's hand-picked defaults and TPU layout constraints.
    """

    name: str
    dim: str
    preferred: int
    align: int
    exact: bool = False  # block must divide the dim (state-chained axes)


@dataclasses.dataclass(frozen=True)
class Channel:
    """An alternative implementation pair for a kernel family.

    A channel is a *semantically identical* ref/Pallas pair that wins only
    on some inputs (e.g. the block-sparse `spikemm` gather path, which
    beats the dense kernel only below a block-occupancy threshold). Both
    callables receive the resolved `blocks=` dict (the ref too — a channel
    may restructure work at block granularity even off-TPU), and the Pallas
    side additionally gets `interpret=`.
    """

    ref: Callable[..., Any]
    pallas: Callable[..., Any]


@dataclasses.dataclass(frozen=True)
class TileModel:
    """Static description of a kernel's grid coverage and operand tiles.

    Purely declarative — `repro.analysis.check_kernel` uses it to prove,
    without tracing, that (a) the grid x index-map writes every output
    element exactly once (TB301/302) and (b) the `vmem_bytes` estimate is
    an honest bound on the per-grid-step operand tiles (TB304/305).

    out:   the output tensor's dims in order, each paired with the block
           axis that tiles it (None = the dim rides whole in every block,
           e.g. the resident N axis of the recurrent kernels).
    tiles: (dims, blocks) -> {operand name: per-grid-step tile shape in
           elements}; fp32 is assumed when converting to bytes.
    coverage: optional override returning, per grid cell, the per-output-
           axis (start, stop) half-open ranges. Defaults to the dense
           row-major tiling implied by `out`; exists so tests can inject
           gap/overlap defects without a real kernel.
    """

    out: Tuple[Tuple[str, Optional[str]], ...]
    tiles: Callable[[Mapping[str, int], Mapping[str, int]],
                    Mapping[str, Tuple[int, ...]]]
    coverage: Optional[Callable[[Mapping[str, int], Mapping[str, int]],
                                Any]] = None


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Everything the registry needs to dispatch, tune, and verify a kernel."""

    name: str
    ref: Callable[..., Any]
    pallas: Callable[..., Any]
    apply: Callable[..., Any]
    block_axes: Tuple[BlockAxis, ...]
    dims_of: Callable[..., Dict[str, int]]
    candidates: Tuple[Mapping[str, int], ...] = ()
    make_inputs: Optional[Callable[..., tuple]] = None
    # static kwargs matching make_inputs' canonical args: machinery that
    # calls spec.ref/spec.pallas directly (the autotuner) forwards these,
    # since required statics otherwise only ride along dispatch() calls
    tune_static: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    diff_argnums: Tuple[int, ...] = ()
    tol: float = 1e-4
    # (dims, blocks) -> estimated per-grid-step VMEM working set in bytes;
    # the autotuner prunes candidates that exceed the budget before timing.
    vmem_bytes: Optional[Callable[[Mapping[str, int], Mapping[str, int]],
                                  int]] = None
    # named alternative implementation channels + the dispatch-time router:
    # select_channel(*args, blocks=..., **static) returns a key into
    # `channels` or None for the default (spec.ref / spec.pallas) pair. The
    # router runs at trace/dispatch time, so it may inspect concrete values
    # (e.g. measure occupancy) but must route conservatively on tracers.
    channels: Mapping[str, Channel] = dataclasses.field(default_factory=dict)
    select_channel: Optional[Callable[..., Optional[str]]] = None
    # static grid/tile description for the analyzer (see TileModel)
    tile_model: Optional[TileModel] = None

    def resolve_blocks(self, dims: Mapping[str, int],
                       overrides: Optional[Mapping[str, int]] = None,
                       use_cache: bool = True) -> Dict[str, int]:
        """Overrides > tuned cache > spec preferred, each fitted to `dims`."""
        tuned: Mapping[str, int] = {}
        if use_cache:
            from repro.kernels import tuning  # local: avoid import cycle
            tuned = tuning.lookup_tuned(self.name, dims) or {}
        overrides = overrides or {}
        blocks = {}
        for ax in self.block_axes:
            pref = int(overrides.get(ax.name, tuned.get(ax.name,
                                                        ax.preferred)))
            if ax.exact:
                blocks[ax.name] = exact_block(dims[ax.dim], pref)
            else:
                blocks[ax.name] = fit_block(dims[ax.dim], pref, ax.align)
        return blocks


# ---------------------------------------------------------------------------
# the registry proper
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, KernelSpec] = {}

_KERNEL_MODULES = (
    "repro.kernels.linrec.ops",
    "repro.kernels.lif.ops",
    "repro.kernels.lifrec.ops",
    "repro.kernels.alifrec.ops",
    "repro.kernels.spikemm.ops",
    "repro.kernels.spikemm.gather",
    "repro.kernels.attention.ops",
    "repro.kernels.stdp.ops",
    "repro.kernels.event.ops",
)


def register(spec: KernelSpec) -> KernelSpec:
    """Idempotent by name: re-importing an ops module re-registers itself."""
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> KernelSpec:
    if name not in _REGISTRY:
        ensure_registered()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def names() -> Tuple[str, ...]:
    ensure_registered()
    return tuple(sorted(_REGISTRY))


def ensure_registered() -> None:
    """Import every kernel family so its module-level register() has run."""
    import importlib

    for mod in _KERNEL_MODULES:
        importlib.import_module(mod)


def dispatch(name: str, args: Sequence[Any], force_pallas: bool = False,
             overrides: Optional[Mapping[str, int]] = None, **static) -> Any:
    """Run kernel `name` on `args` through the unified policy.

    `static` kwargs (thresholds, causal flags, learning rates, ...) are
    forwarded verbatim to whichever implementation wins. `overrides` pins
    individual block sizes, bypassing the tuning cache for those axes.

    Families that registered `channels` + `select_channel` get a second
    routing layer: the router picks an implementation channel per call
    (e.g. block-sparse vs dense `spikemm` by measured occupancy), then the
    usual ref-vs-Pallas policy applies within the chosen channel.

    **Failures.** A raising Pallas call (genuine, or injected via a
    `compile_fail` fault — see `repro.core.faults`) and a call whose
    modeled VMEM working set (`KernelSpec.vmem_bytes`) busts the budget
    are both recorded as a `FallbackEvent`. The VMEM model is consulted
    for compiled calls always, and for interpret-mode calls only under
    simulated `vmem_limit` fault pressure, since interpret mode has no
    VMEM to blow. In interpret mode the call then degrades to the
    reference (or raises `FallbackError` under `REPRO_STRICT=1`); compiled
    calls always raise `FallbackError`, because a reference result on the
    chip would hide that no kernel ran. A failing channel router degrades
    to the default (dense) channel. Only what raises *through this call*
    is caught: a Mosaic compile error deferred to an outer jit's compile
    happens outside dispatch and is fatal anyway.
    """
    from repro.core import faults  # local: keep core<->kernels import acyclic

    spec = get(name)
    dims = spec.dims_of(*args)
    blocks: Optional[Dict[str, int]] = None

    def resolved_blocks() -> Dict[str, int]:
        nonlocal blocks
        if blocks is None:
            blocks = spec.resolve_blocks(dims, overrides)
        return blocks

    chan = None
    choice: Optional[str] = None
    if spec.select_channel is not None:
        try:
            choice = spec.select_channel(*args, blocks=resolved_blocks(),
                                         **static)
        except Exception as e:
            degrade("channel", name, "router", e, dims=dims, blocks=blocks)
            choice = None
        if choice is not None:
            chan = spec.channels[choice]

    def run_ref():
        if chan is not None:
            return chan.ref(*args, blocks=resolved_blocks(), **static)
        return spec.ref(*args, **static)

    if not use_pallas(force_pallas):
        return run_ref()

    pallas_fn = chan.pallas if chan is not None else spec.pallas
    interp = interpret_mode()
    blk = resolved_blocks()   # resolve up front so incidents carry context

    if spec.vmem_bytes is not None:
        from repro.kernels import tuning  # local: avoid import cycle
        limit = tuning.vmem_limit_bytes()
        pressured = faults.vmem_limit_override_bytes() is not None
        if not interp or pressured:
            est = spec.vmem_bytes(dims, blk)
            if est > limit:
                degrade("vmem", name, "vmem-model",
                        f"modeled working set {int(est)} B exceeds budget "
                        f"{limit} B", channel=choice, dims=dims,
                        blocks=blk, fatal=not interp)
                return run_ref()

    try:
        faults.maybe_fail_compile(name)
        return pallas_fn(*args, blocks=blk, interpret=interp, **static)
    except Exception as e:
        degrade("dispatch", name, "pallas", e, channel=choice, dims=dims,
                blocks=blk, fatal=not interp)
    return run_ref()


__all__ = ["BlockAxis", "Channel", "FallbackError", "KernelSpec", "TileModel",
           "register", "get", "names", "ensure_registered", "dispatch",
           "fit_block", "exact_block", "use_pallas", "interpret_mode"]
