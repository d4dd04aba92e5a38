"""Flushed lines of every kind (data, snapshot, journal, integrity) of
both arenas in the window (``FlushStats``), per output token."""


def read(run):
    n = len(run.window_tokens())
    return run.flush_lines / n if n else None
