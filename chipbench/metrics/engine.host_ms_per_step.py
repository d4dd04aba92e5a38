"""Mean over the window's ``engine.step`` spans of the span's length less
the device-busy time inside it (trace), in ms: the engine's host work per
step."""


def read(run):
    tr = run.trace
    steps = tr.spans("engine.step") if tr else []
    if not steps:
        return None
    host = [(b - a) - tr.busy_in(a, b) for a, b in steps]
    return sum(host) / len(host) / 1e6
