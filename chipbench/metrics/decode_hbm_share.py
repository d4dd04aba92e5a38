"""Share of the chip's HBM bandwidth that the window's decode steps need:
per step, every weight once in bf16 plus each active session's state
once (``flops.step_bytes``), summed over the window's steps, over the
window's seconds times the peak, in %.  Host clock, counts from the
configuration's shapes."""
import collections

from chipbench import flops


def read(run):
    if not run.peaks:
        return None
    steps = collections.defaultdict(list)
    for r, i, t in run.window_tokens():
        steps[t].append(len(r.prompt) + i)
    total = sum(flops.step_bytes(run.dims, pos) for pos in steps.values())
    return 100.0 * total / (run.window_s * run.peaks["hbm_bytes_per_s"])
