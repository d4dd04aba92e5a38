"""Process start to window open: imports, weights, engine, every
compile and warm-up the window needs (host clock), in s."""


def read(run):
    return run.setup_s
