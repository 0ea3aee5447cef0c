"""The program's own spans on the trace's clock, for the metric readers.

The serve engine keeps a log of its spans while a profiler trace runs
(`repro.serve.metrics.spans()`), stamped with `time.perf_counter_ns()`;
the profiler gives its events in ns from the start of its session. The
harness's `bench.step` spans and the program's `serve.step` spans are both
made once per `step()` call of the drive, so the k-th of one, by start,
pairs with the k-th of the other, and the median of their start
differences is the offset from the program's clock to the trace's.

An event here is a `tracing` event with the span's ids appended:
(plane, line, name, start_ns, end_ns, ids), on the trace's clock. There
are none (None) where the program keeps no span log, where the log
dropped records, where the two step counts differ, or where the fit's
median absolute residual exceeds `MAX_RESIDUAL_NS`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from tracing import SPAN_PREFIX

PLANE, LINE = "/host:CPU", "program spans"
STEP, BENCH_STEP = "serve.step", SPAN_PREFIX + "step"
MAX_RESIDUAL_NS = 100_000

ProgramEvent = Tuple[str, str, str, int, int, Dict[str, Any]]


def program_log() -> Optional[Tuple[Sequence, int]]:
    """(span records, records dropped) of the program's log, or None
    where the program keeps none."""
    try:
        from repro.serve import metrics
    except ImportError:
        return None
    spans = getattr(metrics, "spans", None)
    dropped = getattr(metrics, "dropped", None)
    if spans is None or dropped is None:
        return None
    return spans(), dropped()


def fit(program_starts: Sequence[int], bench_starts: Sequence[int]
        ) -> Tuple[int, float]:
    """(offset, median absolute residual) of bench = program + offset,
    over pairs in order."""
    d = np.asarray(bench_starts, np.int64) - np.asarray(program_starts,
                                                        np.int64)
    off = int(round(float(np.median(d))))
    return off, float(np.median(np.abs(d - off)))


def align(records: Sequence, bench_steps: Sequence[Tuple]
          ) -> Optional[Tuple[List[ProgramEvent], int, float]]:
    """Every record on the trace's clock, with the fit's (offset,
    residual), or None where the steps do not pair or fit."""
    ours = sorted(r.start_ns for r in records if r.name == STEP)
    theirs = sorted(ev[3] for ev in bench_steps)
    if not ours or len(ours) != len(theirs):
        return None
    off, res = fit(ours, theirs)
    if res > MAX_RESIDUAL_NS:
        return None
    return ([(PLANE, LINE, r.name, r.start_ns + off, r.end_ns + off,
              dict(r.ids)) for r in records], off, res)


def events(view, at: str = "start") -> Optional[List[ProgramEvent]]:
    """The program's events whose start (`at="start"`) or end (`"end"`)
    falls inside the traced window."""
    if not hasattr(view, "program_events"):
        log = program_log()
        got = None
        if log is not None and not log[1]:
            got = align(log[0], [ev for ev in view.trace.spans
                                 if ev[2] == BENCH_STEP])
        view.program_events = got[0] if got else None
    evs = view.program_events
    if evs is None:
        return None
    k = 3 if at == "start" else 4
    lo, hi = view.trace.lo, view.trace.hi
    return [ev for ev in evs if lo <= ev[k] < hi]


def served_steps(evs: Sequence[ProgramEvent]) -> List[ProgramEvent]:
    """The `serve.step` events that served a cohort."""
    return [ev for ev in evs if ev[2] == STEP and ev[5]["sessions"] > 0]


def mean_ms(view, name: str, served: bool = False) -> Optional[float]:
    """Mean duration of the window's `name` spans; with `served`, only
    those inside a step that served a cohort (by the `step` id)."""
    evs = events(view)
    if evs is None:
        return None
    if served:
        ids = {ev[5]["step"] for ev in served_steps(evs)}
        evs = [ev for ev in evs if ev[5].get("step") in ids]
    t = [ev[4] - ev[3] for ev in evs if ev[2] == name]
    return 1e-6 * float(np.mean(t)) if t else None
