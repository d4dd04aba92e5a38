"""Median, over the window's crashes, of the time from the ``crash()``
call to the first post-recovery token of the last session live at the
crash (host clock), in s."""
import numpy as np


def read(run):
    ttft = [c["ttft_s"] for c in run.window_crashes()]
    return float(np.median(ttft)) if ttft else None
