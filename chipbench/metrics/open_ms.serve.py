"""Mean host time of a session `open` (the program's `serve.open` span):
the scheduler's entry, the fresh state's device zeros, the cache put."""

import program_spans


def read(view):
    return program_spans.mean_ms(view, "serve.open")
