"""95th percentile of every gap between consecutive output tokens of one
request, both served inside the window (host clock), in ms."""
import numpy as np


def read(run):
    lo, hi = run.rec.window
    gaps = []
    for req in run.requests:
        ts = [t for t in req.times if lo <= t <= hi]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
