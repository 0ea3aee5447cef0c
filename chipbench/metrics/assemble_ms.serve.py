"""Mean host time of the step's operands (`serve.assemble`: the state
tuple padded to capacity with the synapse entries held aside, and the
(window, capacity, n_in) input filled and uploaded) over the engine
steps that served a cohort."""

import program_spans


def read(view):
    return program_spans.mean_ms(view, "serve.assemble", served=True)
