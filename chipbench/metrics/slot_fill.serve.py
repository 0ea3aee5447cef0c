"""Cohort packing: the share of the resident step's slot-steps that held
a session's real input, 100 x summed valid steps / summed (slots x
window) over the engine steps that served a cohort (`serve.step` ids)."""

import program_spans


def read(view):
    evs = program_spans.events(view)
    if evs is None:
        return None
    steps = program_spans.served_steps(evs)
    window = view.cfg["serve"]["window"]
    slots = sum(ev[5]["slots"] for ev in steps) * window
    if slots <= 0:
        return None
    return 100.0 * sum(ev[5]["steps"] for ev in steps) / slots
