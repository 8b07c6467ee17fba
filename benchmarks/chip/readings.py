"""Readings that set a cell's limit: the widest logit gap of the program
on many seeds, and of the control on a few, in one process.

    python benchmarks/chip/readings.py --workload qwen3-1.7b.chat \\
        --seeds 101,102,103 --control-seeds 101,102 --seconds 12

Set-up is paid once: the engine is built and landed for the first seed,
and each further seed swaps in its own weights and requests (landing any
new shape), runs a window of ``--seconds`` at the cell's own load, and
reads the widest gap over the same sample the harness would check.  For a
control seed the control (the reference with float8 weights,
``quant="fp8"``) reads the gap of the token it puts first at the same
positions.  ``--ref-eps`` also reads the gap against a reference whose
RMSNorm eps is the one given (the program's own, where the configuration
states another), to see how much of the gap that departure explains.  One
JSON line per seed goes to standard output.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import harness  # noqa: F401  (puts the checkout's src on the path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--ref-eps", type=float)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    from repro.launch.hostenv import use_compile_cache

    import correctness
    import serving
    import weights as wmod
    import loadgen

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    workload, cfg, mix = harness.load_cell(args.workload)
    check = workload["check"]
    eng = None
    for seed in seeds:
        if eng is None:
            eng, w, reqs, _ = serving.setup(workload, cfg, mix, seed,
                                            args.seconds)
        else:
            eng.params = w = wmod.make(cfg, seed)
            reqs = loadgen.generate(mix, seed, args.seconds, cfg["vocab_size"])
            serving.land(eng, reqs)
        win = serving.Window(workload=workload, cfg=cfg, mix=mix,
                             seconds=args.seconds)
        finished = serving.measure(eng, win, reqs, check)
        picked = correctness.sample(finished, seed, check["sample_requests"],
                                    check["sample_tokens"])
        gap, npos = correctness.widest_gap(w, cfg, picked)
        row = {"seed": seed, "gap": gap, "positions": npos,
               "requests": len(picked), "finished": len(finished),
               "compiles_in_window": win.compiles}
        if args.ref_eps is not None:
            row["gap_ref_eps"], _ = correctness.widest_gap(
                w, dict(cfg, rms_norm_eps=args.ref_eps), picked)
        if seed in control:
            row["control_gap"], _ = correctness.widest_gap(w, cfg, picked,
                                                           quant="fp8")
        print(json.dumps(row), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
