"""Median, over the decode steps of the traced window, of the time inside
the harness's ``decode`` annotation during which no op runs on the device
(ms): the host work of one ``generate_step(1)``, eager group dispatch and
the token read-back included."""

import loadgen

UNIT = "ms"


def read(win):
    t = win.trace
    if t is None or not t.host_only.get("decode"):
        return None
    return loadgen.percentile([x * 1e3 for x in t.host_only["decode"]], 50)
