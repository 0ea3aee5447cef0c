"""The program's simulation spans on the trace's clock, for the metric
readers of stream cells.

`plan.run_stream` logs a `sim.chunk` span for every chunk (and a
`sim.readback` inside it) while a profiler trace runs; the `simulate`
entry wraps each chunk call in a `bench.step` span. The k-th of one, by
start, pairs with the k-th of the other, and `program_spans.fit` gives
the offset from the program's clock to the trace's. None where the
program keeps no span log, the log dropped records, the counts differ or
the fit's median residual exceeds `program_spans.MAX_RESIDUAL_NS`.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

import program_spans
from tracing import SPAN_PREFIX

CHUNK, BENCH_STEP = "sim.chunk", SPAN_PREFIX + "step"


def events(view) -> Optional[List]:
    """(name, start_ns, end_ns, ids) of the program's spans that start
    inside the traced window, on the trace's clock."""
    if not hasattr(view, "sim_events"):
        view.sim_events = None
        log = program_spans.program_log()
        if log is not None and not log[1]:
            recs = [r for r in log[0] if r.name.startswith("sim.")]
            ours = sorted(r.start_ns for r in recs if r.name == CHUNK)
            theirs = sorted(ev[3] for ev in view.trace.spans
                            if ev[2] == BENCH_STEP)
            if ours and len(ours) == len(theirs):
                off, res = program_spans.fit(ours, theirs)
                if res <= program_spans.MAX_RESIDUAL_NS:
                    view.sim_events = [(r.name, r.start_ns + off,
                                        r.end_ns + off, dict(r.ids))
                                       for r in recs]
    if view.sim_events is None:
        return None
    lo, hi = view.trace.lo, view.trace.hi
    return [ev for ev in view.sim_events if lo <= ev[1] < hi]


def mean_ms(view, name: str) -> Optional[float]:
    evs = events(view)
    if evs is None:
        return None
    t = [ev[2] - ev[1] for ev in evs if ev[0] == name]
    return 1e-6 * float(np.mean(t)) if t else None
