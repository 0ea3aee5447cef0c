"""The event push (`kernels/event`: each step's spikes scattered through
their fan-out rows into the ring of arriving currents) against its
roofline: the least time the chip needs for the window's counted events
(`counts.integ`: 2 operations and one 4-byte weight an event, the spikes,
and each step's arrivals written once) over the device time of the
push's pass loops in the trace.

The TPU trace names an XLA operation by its HLO instruction alone
(`%while.97 = (...) while(...), condition=..., body=...`: no scope, no
op_name; `tests/data/small.xplane.pb` shows the form), and XLA inlines a
jitted wrapper, so the push's XLA work keeps no name of its own. Its
pass loop is found from the one operation that writes the whole flat
ring, f32[R x batch x N]: the push's scatter. Each step's loop is the
innermost `while` around that step's scatters; it holds every pass's
ranking, its two window gathers and its scatter. Only the cumulative
count over the neurons that sizes the loop (one cumsum a step) lies
outside. `tests/test_chip_compile.py` asserts on the compiled chunk that
the scatter is that one operation and that it runs in a loop's body."""

import re

import counts


def push_loops_ns(ops, ring: int, lo: int, hi: int) -> int:
    """Summed duration of the innermost `while` events around the scatters
    into the flat ring of `ring` entries, inside [lo, hi)."""
    scatter = re.compile(rf"^%[\w.-]+ = f32\[{ring}\]\S* (fusion|scatter)\(")
    loop = re.compile(r"^%while[.\d]* = ")
    evs = sorted(((e[3], -e[4], bool(scatter.search(e[2])), e)
                  for e in ops if lo <= e[3] and e[4] <= hi
                  and (scatter.search(e[2]) or loop.search(e[2]))),
                 key=lambda x: (x[0], x[1]))
    open_loops, found = [], {}
    for start, _, is_scatter, e in evs:      # outer before inner at a tie
        while open_loops and open_loops[-1][4] <= start:
            open_loops.pop()
        if not is_scatter:
            open_loops.append(e)
        elif open_loops:
            w = open_loops[-1]
            found[(w[3], w[4])] = w[4] - w[3]
    return sum(found.values())


def read(view):
    c = view.records.get("counts")
    ring = view.records.get("ring_entries")
    if not c or not ring:
        return None
    trace = view.trace
    dev_ns = sum(push_loops_ns(trace.ops_by_device[d], ring, trace.lo,
                               trace.hi) for d in trace.devices())
    if dev_ns <= 0:
        return None
    T, N = view.records["T"], view.records["N"]
    least = sum(counts.least_time(counts.integ(x["events"], x["events"], T,
                                               N, N, x["spikes"]),
                                  view.peaks) for x in c)
    return 100.0 * least / (dev_ns / 1e9)

