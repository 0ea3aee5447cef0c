"""Mean host time of a chunk's read-back (the program's `sim.readback`
span: waiting for the chunk, copying its compacted spike words and
counters to the host, and decoding them into (step, neuron))."""

import sim_spans


def read(view):
    return sim_spans.mean_ms(view, "sim.readback")
