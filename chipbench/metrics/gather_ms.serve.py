"""Mean host time of the state gather (`serve.gather`: the cache's `get`
of each session's state, restoring spilled ones to the device) over the
engine steps that served a cohort."""

import program_spans


def read(view):
    return program_spans.mean_ms(view, "serve.gather", served=True)
