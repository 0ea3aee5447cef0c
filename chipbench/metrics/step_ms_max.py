"""The longest `BatchedEngine.step()` of the window (harness clock): a
stall of the engine, which delays every window queued behind it."""


def read(view):
    t = view.records["summary"]["step_s"]
    return 1e3 * float(max(t)) if len(t) else None
