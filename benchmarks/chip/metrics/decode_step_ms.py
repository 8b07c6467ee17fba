"""Median host time of one ``Engine.generate_step(1)`` call in the window,
every slot's token read back (ms)."""

import loadgen

UNIT = "ms"


def read(win):
    v = [(t1 - t0) * 1e3 for t0, t1, _ in win.decode_calls
         if win.t_open <= t0 <= win.t_close]
    return loadgen.percentile(v, 50) if v else None
