"""Mean host time of `BatchedEngine.step()` over the steps that served a
cohort in the window: gather, input assembly and upload, the resident
step, the blocking read-back and the scatter."""

import numpy as np


def read(view):
    t = view.records["summary"]["step_s"]
    return 1e3 * float(np.mean(t)) if len(t) else None
