"""Contributions folded into released versions per second, over the whole
window (host clock; the window ends when its last release is ready)."""


def read(run):
    return run.contributions / run.window_s
