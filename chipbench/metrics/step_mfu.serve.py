"""The resident step's share of the chip's bf16 peak: 2 operations per
synaptic event of the cohorts' input spikes (the readout's events are not
visible from outside the step and count as none), over the summed host
time of the engine steps."""

import numpy as np


def read(view):
    c = view.records.get("counts")
    t = float(np.sum(view.records["summary"]["step_s"]))
    if not c or t <= 0:
        return None
    ops = sum(s["input"]["ops"] + s["readout"]["ops"] for s in c)
    return 100.0 * ops / t / view.peaks["bf16_flops_per_s"]
