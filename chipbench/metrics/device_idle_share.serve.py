"""Share of the engine steps' host time in which no operation ran on the
device: 1 - device-busy union inside the `step` spans / their summed
length, from the profiler trace."""


def read(view):
    tr = view.trace
    steps = tr.span_windows("step")
    total = sum(e - s for s, e in steps) / 1e9
    if total <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s(steps) / total)
