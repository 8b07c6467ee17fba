"""95th percentile of every gap between two consecutive tokens of one
request, as read back on the host (ms), over the requests of the window."""

import numpy as np

import loadgen

UNIT = "ms"


def read(win):
    gaps = [np.diff(r.times) * 1e3 for r in win.requests if len(r.times) > 1]
    if not gaps:
        return None
    return loadgen.percentile(np.concatenate(gaps), 95)
