"""Backend compile seconds JAX reports inside ``recover()``, per crash in
the window (JAX monitoring event, tagged with the open span)."""


def read(run):
    crashes = run.window_crashes()
    if not crashes:
        return None
    secs = sum(c[1] for c in run.rec.window_compiles()
               if "engine.recover" in c[3])
    return secs / len(crashes)
