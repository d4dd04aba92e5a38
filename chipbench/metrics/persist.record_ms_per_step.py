"""Host time of the persistence work inside each ``serve.step`` span
(the program's ``persist.record`` spans: token-log read and append,
request-table update; and its ``persist.commit`` span), per step in the
window, in ms."""
from chipbench import program_spans as P


def read(run):
    return P.child_ms_per_step(run, {"persist.record", "persist.commit"})
