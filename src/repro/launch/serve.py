"""Serving driver: static batch or continuous batching under open-loop load.

Continuous mode (default for attention families) drives the slot scheduler
with a Poisson arrival process — requests arrive on their own clock whether
or not the server keeps up (open loop), prompt lengths and token budgets are
ragged, and the report shows throughput plus latency percentiles:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --slots 4 --requests 32 --rate 20 --prompt-len 24 --new-tokens 16

Static mode replays the legacy fixed-batch lock-step loop:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --mode static --slots 4 --prompt-len 32 --new-tokens 16

``--stitch`` builds a :class:`repro.cache.CompilationService` (persistent
when ``--cache-dir`` is given), serves through the stitched decode artifact
(miss-then-upgrade: the XLA fallback answers instantly while the stitch
pipeline compiles in the background), and prints ``Engine.stitch_report()``
at exit.

``--model-parallel`` (with real devices or ``--host-devices N``) builds the
host mesh and turns on the engine's DP-replica dispatch: the scheduler's
batched decode step spreads its slots across the data-parallel replicas via
``shard_map``, and with ``--stitch`` the decode graph is traced and solved
at shard-local shapes under a mesh-keyed cache entry.
"""

from __future__ import annotations

import sys

# --host-devices must take effect before the first jax import (jax locks
# the device count at first init); argparse proper still declares the flag.
# Only when run as the launcher: importing build_engine changes nothing.
from repro.launch.hostenv import force_host_devices, use_compile_cache

if __name__ == "__main__":
    force_host_devices(argv=sys.argv)

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.models import build_model
from repro.serve import Engine, ServeConfig
from repro.serve.scheduler import RAGGED_FAMILIES


def build_engine(args, cfg, model, params):
    svc = None
    if args.stitch:
        from repro.cache import CompilationService, StitchCache
        svc = CompilationService(StitchCache(directory=args.cache_dir),
                                 plan_budget=args.plan_budget)
    # DP-replica dispatch is opt-in (--mesh, implied by --model-parallel>1):
    # a multi-device host with the default slot count must not change
    # behavior or hit the slots-divisibility check uninvited
    mesh = None
    if args.mesh or args.model_parallel != 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(args.model_parallel)
    eng = Engine(model, params, ServeConfig(
        batch=args.slots, max_len=args.max_len,
        max_new_tokens=args.new_tokens, eos_id=args.eos,
        stitch_execute=args.stitch,
        paged=False if args.dense else None,
        page_size=args.page_size, num_pages=args.num_pages,
        prefix_cache=args.prefix_cache), stitch_service=svc, mesh=mesh)
    if mesh is not None:
        print(f"mesh={dict(mesh.shape)} dp_replicas={eng.dp_replicas}")
    print(f"kv_layout={'paged' if eng.paged else 'dense'}"
          + (f" page_size={args.page_size}" if eng.paged else "")
          + (" prefix_cache=on" if args.prefix_cache else ""))
    return eng


def make_workload(args, cfg):
    """Ragged prompts + Poisson arrival offsets (open loop).  With
    ``--prefix-pool N`` the prompts are drawn from N distinct templates
    (shared system prompts), making the trace prefix-heavy: every repeat
    of a template is a whole-prompt prefix-cache hit."""
    rng = np.random.default_rng(args.seed)
    lo = max(1, args.prompt_len // 2)
    hi = max(lo + 1, args.prompt_len)
    news = rng.integers(max(1, args.new_tokens // 4), args.new_tokens + 1,
                        args.requests)
    if args.prefix_pool > 0:
        pool_lens = rng.integers(lo, hi + 1, args.prefix_pool)
        pool = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
                for p in pool_lens]
        prompts = [pool[i] for i in rng.integers(0, len(pool), args.requests)]
    else:
        lens = rng.integers(lo, hi + 1, args.requests)
        prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
                   for p in lens]
    if args.rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    else:
        arrivals = np.zeros(args.requests)
    return prompts, news, arrivals


def run_continuous(args, eng, prompts, news, arrivals):
    t0 = time.monotonic()
    pending = list(zip(prompts, news, arrivals))
    i = 0
    while i < len(pending) or eng.scheduler.queue or eng.scheduler.n_active:
        now = time.monotonic() - t0
        while i < len(pending) and pending[i][2] <= now:
            p, n, at = pending[i]
            eng.submit(p, max_new_tokens=int(n), arrival_time=t0 + at)
            i += 1
        if eng.scheduler.queue or eng.scheduler.n_active:
            eng.step()
        elif i < len(pending):
            time.sleep(max(0.0, pending[i][2] - (time.monotonic() - t0)))
    report = eng.serve_report()
    # tokens_per_sec above is busy-time capacity (decode seconds only);
    # wall-clock includes idle gaps between Poisson arrivals and is the
    # number comparable to static mode's report
    wall = time.monotonic() - t0
    report["wall_elapsed_s"] = wall
    report["wall_tokens_per_sec"] = report["total_tokens"] / max(wall, 1e-9)
    return report


def run_static(args, eng, prompts, news):
    """Legacy lock-step: pad every group of ``slots`` requests into one
    rectangle, decode to the group's worst-case budget."""
    total_tokens = 0
    t0 = time.monotonic()
    for g in range(0, len(prompts), args.slots):
        group = prompts[g:g + args.slots]
        while len(group) < args.slots:           # ride-along padding rows
            group = group + [group[-1]]
        lens = [len(p) for p in group]
        rect = np.zeros((args.slots, max(lens)), np.int32)
        for r, p in enumerate(group):
            rect[r, :len(p)] = p
        eng.cfg.max_new_tokens = int(max(news[g:g + args.slots]))
        toks = eng.generate(rect, prompt_lens=lens)
        total_tokens += int(sum(min(n, toks.shape[1])
                                for n in news[g:g + args.slots]))
    dt = time.monotonic() - t0
    return {"requests_finished": len(prompts), "total_tokens": total_tokens,
            "elapsed_s": dt, "tokens_per_sec": total_tokens / max(dt, 1e-9)}


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=["continuous", "static"], default=None,
                    help="default: continuous for attention families")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=None,
                    help="KV capacity (default prompt-len + new-tokens)")
    ap.add_argument("--requests", type=int, default=16,
                    help="continuous mode: number of open-loop requests")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate req/s (0 = all at t=0)")
    ap.add_argument("--eos", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stitch", action="store_true",
                    help="serve decode AND bucketed prefills through the "
                         "stitched artifact (miss-then-upgrade)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged-KV block size in tokens (paged is the "
                         "default layout off-mesh)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV page-pool size (default: worst case, "
                         "slots*ceil(max_len/page_size)+1)")
    ap.add_argument("--dense", action="store_true",
                    help="force the legacy dense per-slot KV rectangles")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-hashed prompt-KV reuse (paged only): "
                         "repeated prompts skip prefill via a page-table "
                         "splice")
    ap.add_argument("--prefix-pool", type=int, default=0,
                    help="draw prompts from N distinct templates (prefix-"
                         "heavy trace; 0 = all prompts unique)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent StitchCache directory (with --stitch)")
    ap.add_argument("--plan-budget", type=float, default=None,
                    help="wall-clock seconds the fusion-plan ILP may spend "
                         "per graph before degrading to the greedy heuristic "
                         "(anytime solve; keeps background upgrades bounded)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="model-axis size of the host mesh (must divide the "
                         "device count); >1 implies --mesh")
    ap.add_argument("--mesh", action="store_true",
                    help="enable the DP-replica decode dispatch over the "
                         "host mesh (slots must divide the DP size)")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N host-platform devices (see module "
                         "docstring)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(enables span tracing AND the measured kernel "
                         "timer; load the file at https://ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default=None,
                    help="write the obs metrics-registry snapshot (counters/"
                         "histograms + serve/stitch reports) as JSON at exit")
    return ap


def main():
    args = make_parser().parse_args()
    use_compile_cache()
    if args.max_len is None:
        args.max_len = args.prompt_len + args.new_tokens

    from repro import obs
    if args.trace_out:
        obs.enable_tracing()
        obs.enable_timing()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.mode is None:
        args.mode = "continuous" if cfg.family in RAGGED_FAMILIES else "static"

    if cfg.family == "audio":
        _serve_audio(args, cfg, model, params)
        return

    eng = build_engine(args, cfg, model, params)
    prompts, news, arrivals = make_workload(args, cfg)
    if args.mode == "continuous":
        report = run_continuous(args, eng, prompts, news, arrivals)
    else:
        report = run_static(args, eng, prompts, news)
    print(f"arch={cfg.name} mode={args.mode} slots={args.slots}")
    print(json.dumps(report, indent=2, default=float))
    if args.prefix_cache and eng.prefix_cache is not None:
        print("prefix_cache:")
        print(json.dumps(eng.prefix_cache.report(), indent=2, default=float))
    if args.stitch:
        print("stitch_report:")
        print(json.dumps(eng.stitch_report(), indent=2, default=str))
    if args.trace_out:
        print(f"trace: {obs.save_trace(args.trace_out)} "
              f"({len(obs.tracer)} events)")
    if args.metrics_json:
        reg = obs.registry()
        reg.register_provider("serve", eng.serve_report)
        reg.register_provider("stitch", eng.stitch_report)
        reg.register_provider("engine", eng.report)
        reg.to_json(args.metrics_json, report=report)
        print(f"metrics: {args.metrics_json}")


def _serve_audio(args, cfg, model, params):
    """Enc-dec (audio) family: cross-attn decode outside the engine."""
    import jax.numpy as jnp
    from repro.models import encdec
    rng = np.random.default_rng(args.seed)
    B, P = args.slots, args.prompt_len
    prompts = rng.integers(0, cfg.vocab, (B, P)).astype(np.int32)
    frames = np.asarray(rng.standard_normal((B, P, cfg.d_model)), np.float32)
    t0 = time.time()
    enc_out = encdec.encode(params, jnp.asarray(frames), cfg)
    logits, _ = model.prefill(params, jnp.asarray(prompts),
                              frames=jnp.asarray(frames))
    cache = model.init_cache(B, P + args.new_tokens)
    out_toks = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    dec = jax.jit(lambda p, c, t, e: model.decode_step(p, c, t, enc_out=e))
    for _ in range(args.new_tokens):
        out_toks.append(np.asarray(tok))
        logits, cache = dec(params, cache, tok, enc_out)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    toks = np.concatenate(out_toks, axis=1)
    dt = time.time() - t0
    print(f"arch={cfg.name}: generated {toks.shape} in {dt:.2f}s "
          f"({B * args.new_tokens / dt:.1f} tok/s)")
    print(toks[:, :12])


if __name__ == "__main__":
    main()
