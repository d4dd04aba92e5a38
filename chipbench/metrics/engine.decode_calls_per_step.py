"""Executions of the decode program per ``engine.step`` span (trace).
Equal to the active slots while decode runs one slot at a time; 1 once
it is batched."""


def read(run):
    tr = run.trace
    steps = tr.spans("engine.step") if tr else []
    if not steps:
        return None
    return len(tr.programs("decode_step", within=steps)) / len(steps)
