"""Model FLOP utilization of serving: 2 x (matmul parameters) FLOPs per
output token, times the output tokens read back in the window per second,
over the chip's bf16 peak (%).  Attention over the context is not counted,
so this is a lower bound on the model work done."""

import weights

UNIT = "%"


def read(win):
    s = weights.dims(win.cfg)
    D, F, L, Hq, Hkv, dh, V = (s[k] for k in ("D", "F", "L", "Hq", "Hkv", "dh", "V"))
    per_layer = D * (Hq + 2 * Hkv) * dh + Hq * dh * D + 3 * D * F
    n = L * per_layer + D * V
    toks = sum(1 for r in win.served for t in r.times
               if win.t_open <= t <= win.t_close)
    return 100.0 * 2 * n * toks / win.seconds / win.peaks["bf16_flops_per_s"]
