"""Model FLOP utilization of serving: 2 x (matmul parameters) FLOPs per
output token, times the output tokens read back in the window per second,
over the chip's bf16 peak (%).  The matmul parameters are the
architecture's count (``arch.load(cfg).matmul_params_per_token``), which
leaves out attention over the context, so this is a lower bound on the
model work done."""

import arch

UNIT = "%"


def read(win):
    n = arch.load(win.cfg).matmul_params_per_token(win.cfg)
    toks = sum(1 for r in win.served for t in r.times
               if win.t_open <= t <= win.t_close)
    return 100.0 * 2 * n * toks / win.seconds / win.peaks["bf16_flops_per_s"]
