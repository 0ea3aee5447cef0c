"""Mean host time of the output read-back (`serve.readback`: waiting for
the device and copying the cohort's outputs to the host) over the engine
steps that served a cohort."""

import program_spans


def read(view):
    return program_spans.mean_ms(view, "serve.readback", served=True)
