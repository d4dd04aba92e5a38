"""Spans inside the program, recorded only while the JAX profiler runs.

``span(name, rid=None)`` marks a piece of host work.  With no profiler
collecting it returns one shared no-op object, so the served path pays a
single ``TraceAnnotation.is_enabled()`` check per span.  While the
profiler collects it opens a ``jax.profiler.TraceAnnotation`` of the same
name, which puts the span on the profiler's host plane on the clock of
the device's operations, and appends a ``Span`` on ``time.perf_counter``
to an in-memory list that ``recorded()`` returns.  The profiler is the
only switch: there is no option and no environment variable.

A count is the number of spans of one name; ``FlushStats`` stays the
persistence layer's counter.  Every name starts with ``serve.``,
``persist.`` or ``recover.`` (``PREFIXES``).  ``rid`` goes into the
record only: an annotation's keyword arguments would rewrite the event's
name in the trace.  Each thread keeps its own stack of open spans, so a
span opened in a pool thread (``recover(concurrency>1)``) has the parent
that thread had open, not the caller's.
"""
from __future__ import annotations

import threading
import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIXES = ("serve.", "persist.", "recover.")


class Span(NamedTuple):
    name: str
    t0: float                   # time.perf_counter() seconds
    t1: float
    parent: Optional[str]       # the span open in this thread at t0
    rid: Optional[int]
    thread: int


_spans: List[Span] = []
_local = threading.local()


class _Off:
    """The span while no profiler collects: does nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def clock(self, t0: float, t1: float) -> None:
        pass


_OFF = _Off()


class _On:
    __slots__ = ("name", "rid", "parent", "t0", "t1", "_ann")

    def __init__(self, name: str, rid: Optional[int]):
        self.name, self.rid = name, rid
        self.t1 = None

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter() if self.t1 is None else self.t1
        self._ann.__exit__(*exc)
        _local.stack.pop()
        _spans.append(Span(self.name, self.t0, t1, self.parent, self.rid,
                           threading.get_ident()))
        return False

    def clock(self, t0: float, t1: float) -> None:
        """Record ``[t0, t1)`` (the caller's own readings of the same
        work, e.g. a ``StageReport``'s) in place of the span's."""
        self.t0, self.t1 = t0, t1


def span(name: str, rid: Optional[int] = None):
    """Context manager over one piece of host work (module docstring)."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _On(name, rid)


def recorded() -> List[Span]:
    """Every span closed while the profiler ran, in closing order."""
    return list(_spans)


def clear() -> None:
    _spans.clear()
