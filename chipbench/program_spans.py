"""The program's own spans (``repro.obs``) in a run, for the metric
readers: those inside the measured window, each serving step with the
spans directly under it, and a map from their clock to the trace's.

The program records spans only while the profiler runs, so a
``--trace 0`` run holds none; nor does a program older than
``repro.obs``.  Then every function here returns an empty result and
the readers return ``None``.
"""
from __future__ import annotations

import bisect

STEP = "serve.step"


def in_window(run) -> list:
    """``repro.obs.Span`` records that lie inside ``run.rec.window``
    (host clock, ``time.perf_counter`` seconds)."""
    try:
        from repro import obs
    except ImportError:
        return []
    lo, hi = run.rec.window
    return [s for s in obs.recorded() if s.t0 >= lo and s.t1 <= hi]


def steps(run) -> list:
    """``(step, children)`` for every ``serve.step`` span in the window,
    in time order: ``children`` are the spans opened directly under it
    (same thread, inside its interval)."""
    spans = in_window(run)
    found = sorted((s for s in spans if s.name == STEP), key=lambda s: s.t0)
    starts = [s.t0 for s in found]
    children = [[] for _ in found]
    for s in spans:
        if s.parent != STEP:
            continue
        i = bisect.bisect_right(starts, s.t0) - 1
        if i >= 0 and s.t1 <= found[i].t1 and s.thread == found[i].thread:
            children[i].append(s)
    return list(zip(found, children))


def child_ms_per_step(run, names) -> "float | None":
    """Host time of the spans named in ``names`` directly under each
    ``serve.step``, summed over the window's steps, per step, in ms."""
    found = steps(run)
    if not found:
        return None
    total = sum(c.t1 - c.t0 for _, kids in found for c in kids
                if c.name in names)
    return total / len(found) * 1e3


def to_trace_clock(run):
    """A map from ``time.perf_counter`` seconds to the trace's
    nanoseconds, linear through both ends of the ``window`` span, which
    the benchmark records on both clocks (so drift between them is
    removed); ``None`` without a trace holding that span."""
    host = run.rec.spans.get("window")
    tr = run.trace
    if not host or tr is None or "window" not in tr.spans_all:
        return None
    h0, h1 = host[-1]
    d0, d1 = tr.window
    scale = (d1 - d0) / (h1 - h0)
    return lambda t: d0 + (t - h0) * scale
