"""Mean host time of the resident step's call (`serve.dispatch`: the
jitted step taking the cohort's state trees and returning once the work
is enqueued) over the engine steps that served a cohort."""

import program_spans


def read(view):
    return program_spans.mean_ms(view, "serve.dispatch", served=True)
