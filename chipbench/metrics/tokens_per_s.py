"""Output tokens served in the window over the window's seconds (host
clock; the window ends when its last step's tokens reach the host)."""


def read(run):
    return len(run.window_tokens()) / run.window_s
