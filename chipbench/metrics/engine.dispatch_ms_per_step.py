"""Host time in the program's per-slot dispatch spans (cache extract,
B=1 decode, cache re-seat: ``serve.slot.*``), per ``serve.step`` span in
the window, in ms."""
from chipbench import program_spans as P

NAMES = {"serve.slot.extract", "serve.slot.decode", "serve.slot.reseat"}


def read(run):
    return P.child_ms_per_step(run, NAMES)
