"""Host time of the arena commits inside the window's ``engine.step``
spans (benchmark-side span around ``arena.commit``), per step, in ms."""


def read(run):
    steps = run.rec.in_window("engine.step")
    if not steps:
        return None
    commits = run.rec.in_window("arena.commit")
    starts = [a for a, _ in steps]
    import bisect

    total = 0.0
    for a, b in commits:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and b <= steps[i][1]:
            total += b - a
    return total / len(steps) * 1e3
