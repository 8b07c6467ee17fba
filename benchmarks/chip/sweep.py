"""Rate sweep of an open-loop serving cell: the highest arrival rate the
system sustains, from which the cell's fixed rate is set (0.8 x that).

    python benchmarks/chip/sweep.py --workload qwen3-1.7b.chat \\
        --rates 3,4,5,6,7 --seconds 20 --seed 5

Sets up once, then runs one window per rate with the cell's mix at that
rate and prints one JSON line per rate: the offered and completed rates,
the TTFT tail, the admission wait in the first and the last third of the
window, and the backlog (requests arrived but not admitted) at the close.
A rate is sustained where no request is left in the backlog at the close
and the last third's mean admission wait is at most twice the first
third's: the queue does not grow over the window.  The knee is the highest
sustained rate; the cell runs at 0.8 x that.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    from repro.launch.hostenv import use_compile_cache

    import loadgen
    import serving

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    workload, cfg, mix = harness.load_cell(args.workload)
    check = dict(workload["check"], drain_s=5.0)
    eng = None
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(mix, rate_per_s=rate)
        if eng is None:
            eng, w, reqs, _ = serving.setup(workload, cfg, mix, args.seed,
                                            args.seconds)
        else:
            reqs = loadgen.generate(mix, args.seed, args.seconds,
                                    cfg["vocab_size"])
            serving.land(eng, reqs)
        win = serving.Window(workload=workload, cfg=cfg, mix=mix,
                             seconds=args.seconds)
        serving.measure(eng, win, reqs, check)
        third = args.seconds / 3

        def waits(lo, hi):
            return [(r.admitted - win.t_open - r.arrival) * 1e3 for r in reqs
                    if r.admitted is not None and lo <= r.arrival < hi]

        def mean(xs):
            return sum(xs) / len(xs) if xs else float("nan")

        done_in = sum(1 for r in reqs
                      if r.times and len(r.times) >= r.out_len
                      and r.times[-1] <= win.t_close)
        backlog = sum(1 for r in reqs if r.arrival <= args.seconds
                      and (r.admitted is None
                           or r.admitted > win.t_close))
        ttft = [(r.times[0] - win.t_open - r.arrival) * 1e3
                for r in reqs if r.times]
        first = mean(waits(0, third))
        last = mean(waits(2 * third, args.seconds))
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "completed_per_s": done_in / args.seconds,
            "ttft_p50_ms": loadgen.percentile(ttft, 50),
            "ttft_p95_ms": loadgen.percentile(ttft, 95),
            "admit_wait_p95_first_ms": loadgen.percentile(waits(0, third), 95),
            "admit_wait_p95_last_ms": loadgen.percentile(
                waits(2 * third, args.seconds), 95),
            "admit_wait_mean_first_ms": first,
            "admit_wait_mean_last_ms": last,
            "backlog_at_close": backlog,
            "sustained": backlog == 0 and last <= 2 * first,
            "decode_step_ms_p50": loadgen.percentile(
                [(b - a) * 1e3 for a, b, _ in win.decode_calls], 50),
            "compiles_in_window": win.compiles,
            "memory_peak_bytes": serving._memory_peak()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
