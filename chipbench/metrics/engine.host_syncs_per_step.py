"""Device-to-host reads on the step path (the program's ``serve.sync``
spans) per ``serve.step`` span in the window.  Equal to the active slots
while every slot reads its token on its own."""
from chipbench import program_spans as P


def read(run):
    found = P.steps(run)
    if not found:
        return None
    return sum(c.name == "serve.sync" for _, kids in found
               for c in kids) / len(found)
