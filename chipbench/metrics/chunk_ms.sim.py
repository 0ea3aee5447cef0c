"""Mean host time of a simulation chunk (the program's `sim.chunk` span:
the jitted chunk call to its spikes on the host) over the window."""

import sim_spans


def read(view):
    return sim_spans.mean_ms(view, "sim.chunk")
