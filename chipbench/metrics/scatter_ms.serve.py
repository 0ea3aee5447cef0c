"""Mean host time of the state scatter (`serve.scatter`: the cache's
`put` of each session's new state, the output append and the counters)
over the engine steps that served a cohort."""

import program_spans


def read(view):
    return program_spans.mean_ms(view, "serve.scatter", served=True)
