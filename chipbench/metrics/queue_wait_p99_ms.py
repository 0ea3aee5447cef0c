"""Scheduler queueing: 99th percentile, over the windows due in the
measured window, of the start of the engine step that served a window
minus the time the chunk completing it was submitted."""

import numpy as np


def read(view):
    wait = view.records["summary"]["queue_wait_s"]
    return 1e3 * float(np.percentile(wait, 99)) if len(wait) else None
