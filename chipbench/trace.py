"""From a JAX profiler trace (``.xplane.pb``) to device busy and idle
time, device time per program, and idle gaps named by the host span open
at the time.

Device work is read from each TPU plane's ``XLA Ops`` line (one event per
operation executed) and grouped into programs by the ``XLA Modules``
line.  Host spans are the ``jax.profiler.TraceAnnotation`` events the
benchmark writes (``spans.Recorder``); the ``window`` span bounds the
measured window.  All times are on the trace's clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"


def start(directory: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=opts)


def stop(directory: Path, span_names) -> "Reduction":
    """Stop the profiler and reduce its trace, keeping the host spans
    named in ``span_names``."""
    import jax

    jax.profiler.stop_trace()
    paths = glob.glob(f"{directory}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return load(paths[0], set(span_names))


def program(name: str) -> str:
    """A module event's name without its numeric suffix:
    ``jit_decode_step(17)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def load(path: str, span_names=None, window: str = WINDOW) -> "Reduction":
    """Reduce the trace at ``path``; the host span named ``window``
    bounds the measured window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns))
                           for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(program(ev.name), int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in line.events]
            devices.append((plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if span_names is None or ev.name in span_names:
                        spans.setdefault(ev.name, []).append(
                            (int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)))
    return Reduction(devices, spans, window)


class Reduction:
    def __init__(self, devices, spans: Dict[str, List[Tuple[int, int]]],
                 window: str = WINDOW):
        self.devices = sorted(devices)
        self.spans_all = spans
        if window in spans:
            self.window = max(spans[window], key=lambda s: s[1] - s[0])
        else:
            ends = [t for _, ops, _ in devices for o in ops for t in o[1:]]
            self.window = (min(ends), max(ends)) if ends else (0, 0)
        w0, w1 = self.window
        self.busy = []          # per device: merged op intervals in window
        for _, ops, _ in self.devices:
            clipped = [(max(a, w0), min(b, w1)) for _, a, b in ops
                       if b > w0 and a < w1]
            self.busy.append(_merge(clipped))
        self._starts = [[a for a, _ in b] for b in self.busy]

    # -- sizes of the window ----------------------------------------------
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_in(self, a: int, b: int) -> float:
        """Device-busy nanoseconds inside [a, b), averaged over chips."""
        if not self.busy:
            return 0.0
        total = 0
        for busy, starts in zip(self.busy, self._starts):
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(busy) and busy[i][0] < b:
                lo, hi = max(busy[i][0], a), min(busy[i][1], b)
                if hi > lo:
                    total += hi - lo
                i += 1
        return total / len(self.busy)

    def busy_s(self) -> float:
        return self.busy_in(*self.window) / 1e9

    # -- host spans and programs --------------------------------------------
    def spans(self, name: str) -> List[Tuple[int, int]]:
        w0, w1 = self.window
        return sorted(s for s in self.spans_all.get(name, ())
                      if s[0] >= w0 and s[1] <= w1)

    def programs(self, match: str, within=None) -> List[Tuple[str, int, int]]:
        """Module executions (first chip) whose name contains ``match``,
        started inside one of the ``within`` spans (default: the
        window)."""
        if not self.devices:
            return []
        within = within if within is not None else [self.window]
        starts = [a for a, _ in within]
        out = []
        for name, a, b in self.devices[0][2]:
            if match not in name:
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < within[i][1]:
                out.append((name, a, b))
        return out

    def innermost(self, t: int) -> str:
        """The latest-started host span open at ``t``."""
        best, name = None, "none"
        for n, spans in self.spans_all.items():
            for a, b in spans:
                if a <= t < b and (best is None or a > best):
                    best, name = a, n
        return name

    def idle_gaps(self) -> List[Tuple[int, int]]:
        """Gaps of the first chip's busy time inside the window."""
        w0, w1 = self.window
        if not self.busy:
            return [(w0, w1)]
        gaps, t = [], w0
        for a, b in self.busy[0]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < w1:
            gaps.append((t, w1))
        return gaps

    def breakdown(self, n: int = 10) -> dict:
        """The device operations that took most time (by program and
        HLO instruction name, e.g. ``jit_decode_step/%while.6``), and the longest idle gaps named by the host
        span open in them."""
        per_op: Dict[str, float] = {}
        if self.devices:
            _, ops, modules = self.devices[0]
            starts = [a for _, a, _ in modules]
            w0, w1 = self.window
            for name, a, b in ops:
                if b <= w0 or a >= w1:
                    continue
                i = bisect.bisect_right(starts, a) - 1
                mod = modules[i][0] if i >= 0 and a < modules[i][2] else "?"
                key = f"{mod}/{name.split(' ', 1)[0]}"
                per_op[key] = per_op.get(key, 0.0) + (b - a) / 1e9
        ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops_top],
                "idle_gaps": [[self.innermost((a + b) // 2), (b - a) / 1e9]
                              for a, b in gaps]}
