"""Sum of the ``RecoveryReport`` stages other than ``engine`` (request
table, LRU, pages, journal: host work), per crash in the window, in ms."""


def read(run):
    crashes = run.window_crashes()
    if not crashes:
        return None
    total = sum(st.seconds for c in crashes for st in c["report"].stages
                if st.name != "engine")
    return total / len(crashes) * 1e3
