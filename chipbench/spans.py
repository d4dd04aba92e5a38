"""What the benchmark records on the host: spans around the calls it
makes into each layer (host clock, and a ``jax.profiler.TraceAnnotation``
of the same name so a device trace can name its idle gaps), and the
backend compiles JAX reports, each tagged with the span open at the
time."""
from __future__ import annotations

import collections
import contextlib
import time

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Recorder:
    def __init__(self):
        self.spans = collections.defaultdict(list)   # name -> [(t0, t1)]
        self.compiles = []          # (end time, seconds, program, spans)
        self.window = None          # (open, close) on the host clock
        self._open = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        self._open.append(name)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._open.pop()
            self.spans[name].append((t0, time.perf_counter()))

    def wrap(self, obj, attr: str, name: str) -> None:
        """Route every call of ``obj.attr`` through ``span(name)``."""
        fn = getattr(obj, attr)
        if getattr(fn, "_chipbench_span", None) == name:
            return

        def spanned(*args, **kw):
            with self.span(name):
                return fn(*args, **kw)

        spanned._chipbench_span = name
        setattr(obj, attr, spanned)

    def __call__(self, event: str, duration: float, **kw) -> None:
        """JAX monitoring listener: record backend compiles."""
        if event == COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), float(duration),
                                  kw.get("fun_name", "?"),
                                  tuple(self._open)))

    def in_window(self, name: str):
        lo, hi = self.window
        return [(a, b) for a, b in self.spans.get(name, ())
                if a >= lo and b <= hi]

    def window_compiles(self):
        lo, hi = self.window
        return [c for c in self.compiles if lo <= c[0] <= hi]
