"""Host time in the program's ``serve.sync`` spans (the greedy token's
read to the host, which waits out the slot's decode), per ``serve.step``
span in the window, in ms."""
from chipbench import program_spans as P


def read(run):
    return P.child_ms_per_step(run, {"serve.sync"})
