"""Scheduler queueing inside the program: 99th percentile of the
`serve.queue` records (a window's wait from entering the ready queue to
its pop in `next_cohort`) whose pop falls inside the traced window."""

import numpy as np

import program_spans


def read(view):
    evs = program_spans.events(view, at="end")
    if evs is None:
        return None
    wait = [ev[4] - ev[3] for ev in evs if ev[2] == "serve.queue"]
    return 1e-6 * float(np.percentile(wait, 99)) if wait else None
