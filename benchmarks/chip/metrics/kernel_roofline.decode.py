"""Device-time-weighted roofline share of the ops in the traced window
(%): the sum over ops of max(FLOPs / peak FLOP/s, bytes / peak bandwidth)
over the sum of their device times.  FLOPs and bytes come from the shapes
the trace records for each op (``trace_reduce.ops_and_bytes``); ops without
shapes are left out (``Summary.unshaped``)."""

UNIT = "%"


def read(win):
    t = win.trace
    if t is None:
        return None
    return t.roofline
