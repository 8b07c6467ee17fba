"""Training driver — real execution on whatever devices exist.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On this container that is CPU execution of the reduced config (the e2e
example trains a ~100M-param model); on a TPU slice the same driver runs
the full config over :func:`make_production_mesh` — everything between the
CLI and the hardware is mesh-shape agnostic.

``--stitch [--cache-dir DIR]`` routes the step through the FusionStitching
pipeline (:class:`repro.train.StitchedTrainStep`): the backward pass traces
to StitchIR, the AdamW+clip update runs as one packed multi-tensor kernel,
and each step polls the cache so the run upgrades from the instant XLA
fallback to stitched plans as background compiles land.

``--stitch`` composes with ``--model-parallel`` (and any multi-device
host): the stitched step dispatches through ``shard_map`` on per-shard
graphs — batch rows split across the mesh for the backward pass, params
updated TP-shard-locally by the packed kernel — with mesh-keyed cache
entries.  ``--host-devices N`` forces N host-platform devices for CI /
laptop rehearsal (the same ``--xla_force_host_platform_device_count``
mechanism as :mod:`repro.launch.dryrun`).
"""

from __future__ import annotations

import sys

# --host-devices must take effect before the first jax import (jax locks
# the device count at first init); argparse proper still declares the flag
from repro.launch.hostenv import force_host_devices, use_compile_cache

force_host_devices(argv=sys.argv)

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_reduced
from repro.data import DataConfig, SyntheticTokens
from repro.ft import StepFailure, TrainSupervisor
from repro.launch.mesh import make_host_mesh
from repro.models import batch_pspecs, build_model, param_pspecs
from repro.optim import AdamWConfig, adamw
from repro.train import TrainState, init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (e.g. to hit ~100M params)")
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--stitch", action="store_true",
                    help="run the step through the FusionStitching pipeline "
                         "(backward pass traced to StitchIR + packed AdamW "
                         "kernel), upgrading from the XLA fallback as "
                         "background compiles land")
    ap.add_argument("--cache-dir", default=None,
                    help="StitchCache directory (fusion plans persist and "
                         "replay across runs)")
    ap.add_argument("--plan-budget", type=float, default=None,
                    help="wall-clock seconds the fusion-plan ILP may spend "
                         "per graph before degrading to the greedy heuristic "
                         "(anytime solve; a huge backward graph can never "
                         "hang a background upgrade thread)")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N host-platform devices (must be first-"
                         "parsed before jax init; see module docstring)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(enables span tracing AND the measured kernel "
                         "timer; load the file at https://ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default=None,
                    help="write the obs metrics-registry snapshot (plus the "
                         "stitched step report) as JSON at exit")
    args = ap.parse_args()
    use_compile_cache()

    from repro import obs
    if args.trace_out:
        obs.enable_tracing()
        obs.enable_timing()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    over = {}
    if args.d_model:
        over["d_model"] = args.d_model
    if args.n_layers:
        over["n_layers"] = args.n_layers
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = build_model(cfg)
    mesh = make_host_mesh(args.model_parallel)
    print(f"arch={cfg.name} params~{cfg.param_count():,} mesh={dict(mesh.shape)}")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                          total_steps=args.steps)
    state = init_state(model, jax.random.PRNGKey(0))

    aparams = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                           state.params)
    pspecs = param_pspecs(aparams, cfg, mesh)

    stitched = None
    if args.stitch:
        # stitched training: the backward pass and packed AdamW+clip update
        # execute through compiled StitchIR artifacts, polling the cache each
        # step so the run upgrades from the XLA fallback mid-flight.  On a
        # multi-device mesh both phases dispatch through shard_map on
        # per-shard graphs (mesh-keyed cache entries).
        from repro.cache import CompilationService, StitchCache
        from repro.train import StitchedTrainStep
        svc = CompilationService(cache=StitchCache(args.cache_dir),
                                 plan_budget=args.plan_budget)
        stitched = StitchedTrainStep(model, opt_cfg,
                                     microbatches=args.microbatches,
                                     service=svc, mesh=mesh,
                                     param_specs=pspecs)

    if stitched is not None and stitched.mesh is not None:
        # packed panels update shard-local param/moment slices, so m/v must
        # stay co-located with params (no ZeRO offset on the stitched path)
        state_sh = stitched.state_shardings()
    else:
        opt_specs = adamw.opt_state_pspecs(state.opt, pspecs, mesh)
        state_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            TrainState(params=pspecs, opt=opt_specs, step=P()),
            is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, state_sh)

    data = SyntheticTokens(DataConfig(cfg.vocab, args.seq, args.batch))
    bspecs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          batch_pspecs(data.batch(0), mesh),
                          is_leaf=lambda x: isinstance(x, P))
    if stitched is not None:
        step_fn = stitched
    else:
        step_raw = make_train_step(model, opt_cfg, microbatches=args.microbatches)
        step_fn = jax.jit(step_raw, in_shardings=(state_sh, bspecs),
                          donate_argnums=(0,))

    def data_fn(step: int):
        return jax.device_put(data.batch(step), bspecs)

    sup = TrainSupervisor(step_fn, data_fn, args.ckpt_dir,
                          ckpt_every=args.ckpt_every)
    t0 = time.time()
    last = [t0]

    orig_step = sup.train_step

    def logged(state, batch):
        out_state, metrics = orig_step(state, batch)
        s = int(jax.device_get(out_state.step))
        if s % args.log_every == 0 or s == 1:
            now = time.time()
            print(f"step {s:5d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"{(now - last[0]) / args.log_every:.3f}s/step", flush=True)
            last[0] = now
        return out_state, metrics

    sup.train_step = logged
    state = sup.run(state, args.steps)
    print(f"done: {args.steps} steps in {time.time() - t0:.1f}s; "
          f"final loss {sup.metrics_log[-1]['loss']:.4f}")
    if stitched is not None:
        stitched.wait(timeout=60.0)
        rep = stitched.report()
        grad_plan = rep["grad"].get("plan") or {}
        opt_plan = rep["optimizer"].get("plan") or {}
        mesh_note = (f" mesh={rep['mesh']} (shard_map per-shard graphs)"
                     if "mesh" in rep else "")
        print(f"stitch: grad {rep['grad']['status']} "
              f"({grad_plan.get('n_ops', '?')} ops -> "
              f"{grad_plan.get('n_kernels', '?')} kernels), "
              f"optimizer {rep['optimizer']['status']} "
              f"({opt_plan.get('n_ops', '?')} ops -> "
              f"{opt_plan.get('n_kernels', '?')} packed kernel(s)), "
              f"fallback_steps={rep['fallback_steps']}{mesh_note}")
        placements = rep.get("cache", {}).get("per_placement")
        if placements:
            print(f"stitch cache per-placement: {placements}")
    if args.trace_out:
        print(f"trace: {obs.save_trace(args.trace_out)} "
              f"({len(obs.tracer)} events)")
    if args.metrics_json:
        reg = obs.registry()
        if stitched is not None:
            reg.register_provider("train", stitched.report)
        reg.to_json(args.metrics_json)
        print(f"metrics: {args.metrics_json}")


if __name__ == "__main__":
    main()
