"""Seconds from process start to the window's opening: loading, weights,
engine, plan landing and every compile (host clock)."""

UNIT = "s"


def read(win):
    return win.setup_s
