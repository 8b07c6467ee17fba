"""Median host time of one ``Engine.prefill`` call in the window, its
first-token read-back included (ms)."""

import loadgen

UNIT = "ms"


def read(win):
    v = [(t1 - t0) * 1e3 for t0, t1, _ in win.prefill_calls
         if win.t_open <= t0 <= win.t_close]
    return loadgen.percentile(v, 50) if v else None
