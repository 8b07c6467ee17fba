"""Seconds of set-up spent tracing, planning (or replaying plans from the
StitchCache) and compiling every stitched specialization the traffic uses
(host clock around ``serving.land``)."""

UNIT = "s"


def read(win):
    return win.land_s
