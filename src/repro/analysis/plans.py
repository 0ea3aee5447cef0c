"""TB2xx: fusion explainability + predicted-VMEM checks over a Plan.

`core/plan.py` already decides *and records* why every stepper segment
fell back (`Segment.codes` / `PlasticLower.code`); this module lifts
those decisions into `Diagnostic` records (severity info — a fallback is
legal, just slow) and adds the one check only the analyzer can do
statically: predict each fused segment's kernel VMEM working set at the
tuned block shapes and compare it against `REPRO_VMEM_LIMIT_MB` (TB230)
before anything is traced.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence

from repro.core import plan as plan_mod
from repro.core.events import LayerNode
from repro.kernels import registry, tuning

from repro.analysis.diagnostics import Diagnostic, make


def compile_quiet(nodes: Sequence[LayerNode]) -> "plan_mod.Plan":
    """compile_program with the REPRO_CHECK hook latched off (the analyzer
    calls the planner; the planner must not call the analyzer back)."""
    prev = plan_mod._IN_CHECK
    plan_mod._IN_CHECK = True
    try:
        return plan_mod.compile_program(list(nodes))
    finally:
        plan_mod._IN_CHECK = prev


def _fallback_diags(plan: "plan_mod.Plan") -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for seg in plan.segments:
        if seg.kind != plan_mod.FALLBACK:
            continue
        entries = [e.strip() for e in seg.reason.split(";")] if seg.reason \
            else []
        if len(seg.codes) == len(seg.names) == len(entries):
            for name, code, entry in zip(seg.names, seg.codes, entries):
                msg = entry.split(":", 1)[1].strip() if ":" in entry else entry
                if msg.startswith(code):
                    msg = msg[len(code):].strip()
                out.append(make(
                    code, name, msg,
                    hint="runs through the per-step stepper segment"))
        else:
            # whole-program fallback: one code covers every node
            code = seg.codes[0] if seg.codes else "TB201"
            out.append(make(
                code, seg.names[0] if seg.names else "program",
                seg.reason or "program compiles to a single stepper segment",
                hint="runs through the per-step stepper segment"))
    for p in plan.plastic:
        if p.code:
            out.append(make(
                p.code, f"{p.node}.{p.conn}", p.reason,
                hint="the rule runs through plasticity.synapse_step"))
    return out


# fused lowering family -> kernel spec name(s), keyed by recurrence
_FAMILY_KERNELS = {
    (plan_mod.LOWER_LI, False): ("linrec",),
    (plan_mod.LOWER_LIF, False): ("lif",),
    (plan_mod.LOWER_LIF, True): ("lifrec",),
    (plan_mod.LOWER_ALIF, False): ("alif",),
    (plan_mod.LOWER_ALIF, True): ("alifrec",),
    (plan_mod.LOWER_DHLIF, False): ("linrec", "lif"),
}


def _fire_dims(kernel: str, family: str, T: int, B: int, n: int,
               n_branches: int) -> Dict[str, int]:
    if kernel == "linrec":
        # the dhlif prologue scans the branch-flattened (T, B*K, N) tensor
        b = B * n_branches if family == plan_mod.LOWER_DHLIF else B
        return {"T": T, "B": b, "D": n}
    return {"T": T, "B": B, "N": n}


def _predict_vmem(kernel: str, dims: Mapping[str, int]
                  ) -> Optional[Dict[str, Any]]:
    try:
        spec = registry.get(kernel)
    except KeyError:
        return None
    if spec.vmem_bytes is None:
        return None
    blocks = spec.resolve_blocks(dims)
    return {"kernel": kernel, "blocks": blocks,
            "bytes": int(spec.vmem_bytes(dims, blocks))}


def _vmem_diags(nodes: Sequence[LayerNode], plan: "plan_mod.Plan",
                T: int, B: int,
                params: Optional[Dict[str, Any]] = None) -> List[Diagnostic]:
    limit = tuning.vmem_limit_bytes()
    by_name = {n.name: n for n in nodes}
    widths = {n.name: n.out_dim for n in nodes}
    out: List[Diagnostic] = []

    def check(site: str, pred: Optional[Dict[str, Any]]) -> None:
        if pred is not None and pred["bytes"] > limit:
            out.append(make(
                "TB230", site,
                f"{pred['kernel']} predicts "
                f"{pred['bytes'] / 2**20:.1f} MiB at blocks "
                f"{pred['blocks']} > budget {limit / 2**20:.1f} MiB",
                hint="raise REPRO_VMEM_LIMIT_MB or retune; dispatch will "
                     "reject the compiled channel and degrade"))

    for seg in plan.segments:
        if seg.kind in (plan_mod.FALLBACK, plan_mod.EVENT):
            continue              # EVENT: XLA operations, no VMEM tiles
        node = by_name[seg.names[0]]
        prog = node.neuron.program
        kb = prog.n_branches
        for kernel in _FAMILY_KERNELS.get(
                (seg.lower, seg.kind == plan_mod.FUSED_REC), ()):
            check(node.name, _predict_vmem(
                kernel, _fire_dims(kernel, seg.lower, T, B, node.out_dim, kb)))
        # the hoisted INTEG spikemm per feed, when the source width is known
        for c in node.connections:
            if c.src == "self":
                continue
            src_dim = widths.get(c.src)
            if src_dim is None and params is not None:
                w = params.get(node.name, {}).get(c.weight_key)
                shape = getattr(w, "shape", None)
                if shape is not None and len(shape) >= 2:
                    src_dim = int(shape[-2])
            if src_dim is None:
                continue
            n_out = node.out_dim * (kb if seg.lower == plan_mod.LOWER_DHLIF
                                    else 1)
            check(f"{node.name}.{c.key}", _predict_vmem(
                "spikemm", {"M": T * B, "K": src_dim, "N": n_out}))
    return out


def check_plan(nodes: Sequence[LayerNode],
               plan: Optional["plan_mod.Plan"] = None,
               T: Optional[int] = None, B: Optional[int] = None,
               params: Optional[Dict[str, Any]] = None) -> List[Diagnostic]:
    """TB201-210 fusion explainability (+ TB230 when T and B are given)."""
    if plan is None:
        plan = compile_quiet(nodes)
    out = _fallback_diags(plan)
    if T is not None and B is not None and nodes:
        out.extend(_vmem_diags(nodes, plan, int(T), int(B), params))
    return out


__all__ = ["check_plan", "compile_quiet"]
