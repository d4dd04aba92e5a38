"""Share of the chip's bf16 peak that the window's output tokens need:
every token's model FLOPs at its position (``flops.token_flops``) over
the window's seconds times the peak, in %.  Host clock, counts from the
configuration's shapes."""
from chipbench import flops


def read(run):
    if not run.peaks:
        return None
    total = sum(flops.token_flops(run.dims, len(r.prompt) + i)
                for r, i, _ in run.window_tokens())
    return 100.0 * total / (run.window_s * run.peaks["bf16_flops_per_s"])
