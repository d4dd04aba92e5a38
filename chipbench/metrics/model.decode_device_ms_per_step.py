"""Device time of the decode program's executions inside the window's
``engine.step`` spans (trace), per step, in ms."""


def read(run):
    tr = run.trace
    steps = tr.spans("engine.step") if tr else []
    progs = tr.programs("decode_step", within=steps) if steps else []
    if not progs:
        return None
    return sum(b - a for _, a, b in progs) / len(steps) / 1e6
