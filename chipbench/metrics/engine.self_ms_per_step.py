"""Self time of the program's ``serve.step`` spans: each span's length
less the spans directly under it, averaged over the window's steps, in
ms.  With the sync, dispatch and record metrics it adds up to the mean
step."""
from chipbench import program_spans as P


def read(run):
    found = P.steps(run)
    if not found:
        return None
    total = sum((s.t1 - s.t0) - sum(c.t1 - c.t0 for c in kids)
                for s, kids in found)
    return total / len(found) * 1e3
