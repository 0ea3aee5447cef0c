"""Synaptic events delivered (a spike times each synapse it fans out
to, as the program counts them per chunk) in the chunks whose spikes
reached the host inside the window, over the window's seconds."""


def read(view):
    c = view.records.get("counts")
    s = view.records.get("window_s")
    if not c or not s:
        return None
    return sum(x["events"] for x in c) / s
