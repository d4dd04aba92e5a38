"""Device time of recovery's rebuild, per crash in the window, in ms:
the programs (first chip's ``XLA Modules``) that start between the
program's ``recover.engine`` span's start, mapped to the trace's clock,
and the next decode program: the grouped re-prefills and cache scatters
the ``engine`` stage dispatches and does not wait for."""
import bisect

from chipbench import program_spans as P


def read(run):
    to_trace = P.to_trace_clock(run)
    tr = run.trace
    if to_trace is None or not tr.devices:
        return None
    starts = sorted(to_trace(s.t0) for s in P.in_window(run)
                    if s.name == "recover.engine")
    if not starts:
        return None
    modules = sorted(tr.devices[0][2], key=lambda m: m[1])
    module_starts = [a for _, a, _ in modules]
    total = 0
    for t in starts:
        for name, a, b in modules[bisect.bisect_left(module_starts, t):]:
            if "decode_step" in name:
                break
            total += b - a
    return total / len(starts) / 1e6
