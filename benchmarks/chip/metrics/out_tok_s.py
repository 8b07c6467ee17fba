"""Output tokens read back inside the window, over the window's seconds
(tokens/s); counts requests cut off at the close too."""

UNIT = "tokens/s"


def read(win):
    n = sum(1 for r in win.served for t in r.times
            if win.t_open <= t <= win.t_close)
    return n / win.seconds
