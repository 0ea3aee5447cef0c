"""Mean host time of the scheduler's cohort assembly (`serve.schedule`:
`next_cohort` popping the ready sessions and their window inputs) over
the engine steps that served a cohort."""

import program_spans


def read(view):
    return program_spans.mean_ms(view, "serve.schedule", served=True)
