"""Positions recovery's ``engine`` stage re-prefills, per crash in the
window: every live session's logged tokens but the last, plus the meta
tokens before each (the program's ``rebuild_positions`` detail).  ``None``
for a program that does not report it."""


def read(run):
    counts = []
    for c in run.window_crashes():
        st = c["report"].stage("engine")
        if st is None or "rebuild_positions" not in st.detail:
            return None
        counts.append(st.detail["rebuild_positions"])
    return sum(counts) / len(counts) if counts else None
