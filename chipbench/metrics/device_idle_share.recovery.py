"""1 - device-busy time inside the window's ``engine.recover`` spans
(trace), in %."""


def read(run):
    tr = run.trace
    spans = tr.spans("engine.recover") if tr else []
    length = sum(b - a for a, b in spans)
    if not length:
        return None
    return 100.0 * (1.0 - sum(tr.busy_in(a, b) for a, b in spans) / length)
