"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--hw V100|TPU_V5E] [--quick]

Sections:
  Table 2  workload characteristics (graph size, kernels, avg kernel us,
           memory-intensive time ratio)
  Table 3  kernel compression + modeled speedup (TF / XLA / FusionStitching)
  Fig. 6   fusion-pattern class composition
  Table 4  scratch (VMEM/shared) statistics incl. Alg.4 alloc/req
  Cache    StitchCache cold vs warm compile times (same-graph recompile and
           record replay onto a freshly built isomorphic graph)
  Verify   static-verifier wall clock per workload (verify overhead vs the
           cold compile) and offline verify_compiled findings (gated at 0)
  Serving  continuous-batching vs static-batch tokens/sec on a mixed-length
           request stream (warmed; measures scheduling, not compiles)
  Training stitched train step vs plain jit: backward-graph kernel
           compression (off/xla/stitch) and the packed multi-tensor
           AdamW+clip update collapsing to a single kernel
  Sharding mesh-aware stitched train step under shard_map on the forced
           multi-device host platform: per-shard backward/packed kernel
           counts, trajectory agreement with the single-device stitched
           run, mesh-keyed cache entries
  Compute  compute-intensive stitching: transformer block (q/k/v GEMMs +
           Pallas flash attention + gelu MLP) -> ONE stitched kernel, plus
           the serving decode step's plan kernel counts
  Packing  horizontal FFD packing (§4.2) on a wide-expert MoE block:
           stitched-kernel count packed vs unpacked, packs formed, modeled
           + measured step time
  Perf     measured interpret-mode execution of stitched kernels vs oracle
           on the classic patterns (CPU wall time, correctness evidence)

Output: ``name,us_per_call,derived`` CSV rows per section.  With
``--json PATH`` a machine-readable BENCH record (per-workload kernel
counts, modeled step times, cache cold/warm compile times) is also written
— the start of the perf trajectory across PRs.
"""

from __future__ import annotations

# The Sharding section needs a multi-device host platform; force 8 CPU
# devices before the first jax import so the record is identical locally
# and in CI.  An operator-provided count via XLA_FLAGS is respected.  The
# modeled/kernel-count metrics of the other sections are device-count
# independent.
from repro.launch.hostenv import force_host_devices, use_compile_cache

force_host_devices(8)

import argparse
import json
import sys
import time

import numpy as np

from repro.core import (
    CostModel, FusionPattern, OpKind, StitchCompiler, TPU_V5E, V100,
)

from .workloads import build_all


def table2(graphs, cost: CostModel):
    print("\n# Table 2 — workload characteristics")
    print("name,graph_size,kernels_tf,avg_kernel_us,mem_ratio")
    for name, g in graphs.items():
        nodes = g.compute_nodes()
        times = [cost.kernel_time(g, n.name) + cost.hw.launch_latency
                 for n in nodes]
        mem_time = sum(t for n, t in zip(nodes, times) if n.is_memory_intensive())
        total = sum(times)
        print(f"{name},{len(g.nodes)},{len(nodes)},"
              f"{1e6 * total / max(len(nodes), 1):.2f},"
              f"{100 * mem_time / max(total, 1e-12):.0f}%")


def table3(graphs, cost: CostModel):
    print("\n# Table 3 — kernel compression & modeled speedup "
          f"(hw={cost.hw.name}, phi={cost.hw.launch_latency * 1e6:.0f}us)")
    print("name,xla/tf-kernel,fs/tf-kernel,fs/xla-kernel,"
          "xla/tf-perf,fs/tf-perf,fs/xla-perf")
    ratios_k, ratios_p = [], []
    results = {}
    for name, g in graphs.items():
        stats = {}
        for mode in ("off", "xla", "stitch"):
            cg = StitchCompiler(hw=cost.hw, mode=mode, use_pallas=False).compile(g)
            stats[mode] = cg.stats
        k_tf, k_xla, k_fs = (stats[m].n_kernels for m in ("off", "xla", "stitch"))
        t_tf, t_xla, t_fs = (stats[m].modeled_time for m in ("off", "xla", "stitch"))
        row = (k_tf / k_xla, k_tf / k_fs, k_xla / k_fs,
               t_tf / t_xla, t_tf / t_fs, t_xla / t_fs)
        ratios_k.append(row[2])
        ratios_p.append(row[5])
        results[name] = {
            "graph_size": len(g.nodes),
            "kernels": {"off": k_tf, "xla": k_xla, "stitch": k_fs},
            "modeled_time_s": {"off": t_tf, "xla": t_xla, "stitch": t_fs},
            "compression_fs_over_xla": row[2],
            "speedup_fs_over_xla": row[5],
        }
        print(f"{name},{row[0]:.2f},{row[1]:.2f},{row[2]:.2f},"
              f"{row[3]:.2f},{row[4]:.2f},{row[5]:.2f}")
    gk = float(np.exp(np.mean(np.log(ratios_k))))
    gp = float(np.exp(np.mean(np.log(ratios_p))))
    print(f"GEOMEAN,fs/xla-kernel={gk:.2f},fs/xla-perf={gp:.2f}")
    print("# paper: fs/xla kernel compression 2.9x avg (1.18-10.4x); "
          "fs/xla speedup 1.4x geomean (1.25-1.85x)")
    return results


def fig6(graphs):
    print("\n# Fig. 6 — fusion pattern composition (stitch mode)")
    print("name,elemwise,reduction,gemm")
    for name, g in graphs.items():
        cg = StitchCompiler(mode="stitch", use_pallas=False).compile(g)
        pc = cg.stats.pattern_classes
        tot = max(sum(pc.values()), 1)
        print(f"{name},{pc.get('elemwise', 0) / tot:.2f},"
              f"{pc.get('reduction', 0) / tot:.2f},{pc.get('gemm', 0) / tot:.2f}")


def fig7_fig8(graphs, cost: CostModel):
    """Fig. 7: accumulated kernel time normalized to the XLA baseline.
    Fig. 8: stitch-mode kernel-time breakdown by pattern class."""
    print("\n# Fig. 7 — accumulated kernel time, normalized to xla "
          "(launch overhead excluded)")
    print("name,fs/xla_kernel_time")
    reductions = []
    breakdowns = {}
    for name, g in graphs.items():
        times = {}
        classes = {"elemwise": 0.0, "reduction": 0.0, "gemm": 0.0}
        for mode in ("xla", "stitch"):
            cg = StitchCompiler(hw=cost.hw, mode=mode, use_pallas=False).compile(g)
            total = 0.0
            for grp in cg.groups:
                if len(grp.members) == 1:
                    (m,) = grp.members
                    t = cost.kernel_time(g, m)
                    cls = ("gemm" if g[m].is_compute_intensive() else
                           "reduction" if g[m].kind is OpKind.REDUCTION else
                           "elemwise")
                else:
                    p = FusionPattern(g, grp.members)
                    t = cost.fused_time(p)
                    cls = p.pattern_class
                total += t
                if mode == "stitch":
                    classes[cls] = classes.get(cls, 0.0) + t
            times[mode] = total
        ratio = times["stitch"] / times["xla"]
        reductions.append(ratio)
        breakdowns[name] = classes
        print(f"{name},{ratio:.2f}")
    avg = float(np.mean(reductions))
    print(f"AVERAGE,{avg:.2f}")
    print(f"# paper Fig.7: ~39% kernel-time reduction vs xla (ratio ~0.61); "
          f"ours {100 * (1 - avg):.0f}% ({avg:.2f})")

    print("\n# Fig. 8 — stitch kernel-time breakdown by pattern class")
    print("name,elemwise,reduction,gemm")
    for name, cls in breakdowns.items():
        tot = max(sum(cls.values()), 1e-12)
        print(f"{name},{cls['elemwise'] / tot:.2f},{cls['reduction'] / tot:.2f},"
              f"{cls['gemm'] / tot:.2f}")


def table4(graphs, cost: CostModel):
    print("\n# Table 4 — scratch-memory statistics (Alg. 4)")
    print("name,pt_ratio,shd_avg_kb,max_shd_kb,alloc_over_req")
    from repro.core import ScratchAllocator
    for name, g in graphs.items():
        cg = StitchCompiler(hw=cost.hw, mode="stitch", use_pallas=False).compile(g)
        chosen = [FusionPattern(g, grp.members) for grp in cg.groups
                  if len(grp.members) > 1]
        n_with = 0
        allocs, reqs = [], []
        for p in chosen:
            req = cost.scratch_request(p)
            if not req:
                continue
            n_with += 1
            plan = ScratchAllocator(g).allocate(req)
            allocs.append(plan.allocated)
            reqs.append(plan.requested)
        if not chosen:
            continue
        pt = n_with / len(chosen)
        avg = np.mean(allocs) / 1024 if allocs else 0.0
        mx = max(allocs) / 1024 if allocs else 0.0
        aor = (sum(allocs) / sum(reqs)) if reqs else 1.0
        print(f"{name},{pt:.2f},{avg:.1f},{mx:.1f},{aor:.2f}")


def cache_timing(graphs, cost: CostModel, quick: bool) -> dict:
    """StitchCache amortization: cold (full pattern-gen + ILP + tuning)
    vs warm (same-graph recompile through the live memo) vs replay (plan
    record applied to a freshly built isomorphic graph)."""
    from repro.cache import StitchCache
    from .workloads import build_all

    print("\n# Cache — StitchCache cold/warm compile times")
    print("name,cold_ms,warm_ms,replay_ms,warm_speedup,replay_speedup")
    rebuilt = {} if quick else build_all()
    out = {}
    warm_ratios = []
    for name, g in graphs.items():
        cache = StitchCache()
        comp = StitchCompiler(hw=cost.hw, mode="stitch", cache=cache)
        t0 = time.perf_counter()
        comp.compile(g)
        cold = time.perf_counter() - t0
        comp.compile(g)                    # absorb one-time warm-path setup
        t0 = time.perf_counter()
        warm_cg = comp.compile(g)
        warm = time.perf_counter() - t0
        assert warm_cg.stats.cache_status == "hit"
        replay = None
        if name in rebuilt:
            t0 = time.perf_counter()
            replay_cg = comp.compile(rebuilt[name])
            replay = time.perf_counter() - t0
            assert replay_cg.stats.cache_status == "hit"
        warm_ratios.append(cold / max(warm, 1e-9))
        out[name] = {
            "cold_compile_s": cold,
            "warm_compile_s": warm,
            "replay_compile_s": replay,
            "warm_speedup": cold / max(warm, 1e-9),
            "replay_speedup": cold / max(replay, 1e-9) if replay else None,
        }
        replay_ms = "" if replay is None else f"{replay * 1e3:.2f}"
        replay_x = "" if replay is None else f"{cold / max(replay, 1e-9):.0f}x"
        print(f"{name},{cold * 1e3:.2f},{warm * 1e3:.3f},{replay_ms},"
              f"{cold / max(warm, 1e-9):.0f}x,{replay_x}")
    geo = float(np.exp(np.mean(np.log(warm_ratios))))
    print(f"GEOMEAN,warm_speedup={geo:.0f}x")
    return {"per_workload": out, "warm_speedup_geomean": geo}


def verify_section(graphs, cost: CostModel, cache: dict) -> dict:
    """Static-verifier cost & cleanliness: per-workload verify="plans"
    wall-clock (the in-compile IR/plan audit) against the cache section's
    cold compile, plus a full offline :func:`verify_compiled` sweep.  Both
    gated: findings must stay at zero and the verify overhead must stay a
    rounding error next to pattern-gen + ILP + tuning."""
    from repro.analysis import errors, verify_compiled

    print("\n# Verify — static verification wall-clock & findings")
    print("name,verify_ms,overhead_vs_cold,errors")
    out = {}
    findings_total = 0
    fracs = []
    for name, g in graphs.items():
        comp = StitchCompiler(hw=cost.hw, mode="stitch", use_pallas=False,
                              verify="full")
        cg = comp.compile(g)
        budget = comp.gen_cfg.scratch_budget
        if budget is None:
            budget = comp.hw.onchip_budget
        # best-of-3: the overhead fraction is gated (max:0.05) and the full
        # IR+plan audit is milliseconds — one descheduled sample must not
        # fail the build
        best, fs = None, []
        for _ in range(3):
            t0 = time.perf_counter()
            fs = verify_compiled(cg, scratch_budget=budget, cost=comp.cost)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        n_err = len(errors(fs))
        findings_total += n_err
        cold = cache["per_workload"].get(name, {}).get("cold_compile_s")
        frac = best / cold if cold else None
        if frac is not None:
            fracs.append(frac)
        out[name] = {"verify_s": best, "overhead_frac": frac, "errors": n_err}
        frac_str = f"{100 * frac:.2f}%" if frac is not None else "-"
        print(f"{name},{best * 1e3:.2f},{frac_str},{n_err}")
    mx = max(fracs) if fracs else 0.0
    print(f"MAX_OVERHEAD,{100 * mx:.2f}%,findings_total={findings_total}")
    return {"per_workload": out, "findings_total": findings_total,
            "max_overhead_frac": mx}


def serving(quick: bool) -> dict:
    """Continuous vs static batching on a mixed-length request stream.

    Same tiny model, same ragged requests, same slot count; static lock-step
    pads every group to its worst-case prompt and decodes to its worst-case
    token budget, the continuous scheduler evicts/refills per request.  Both
    paths are warmed (compiled) before timing, so the ratio measures
    scheduling, not XLA compiles."""
    import jax
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    print("\n# Serving — continuous vs static batching (mixed-length stream)")
    print("name,us_per_call,derived")
    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    slots, max_len = 4, 64
    n_req = 8 if quick else 16
    lens = rng.integers(4, 25, n_req)
    news = np.where(np.arange(n_req) % 4 == 3, 24, 6)   # one straggler per 4
    prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32) for p in lens]

    # one engine per mode, reused across warmup and timed runs — a fresh
    # Engine would re-jit its decode step and the timing would measure XLA
    # compiles instead of scheduling
    eng_static = Engine(model, params, ServeConfig(batch=slots, max_len=max_len))
    eng_cont = Engine(model, params, ServeConfig(batch=slots, max_len=max_len))

    def run_static() -> int:
        tokens = 0
        for g in range(0, n_req, slots):
            group = prompts[g:g + slots]
            glens = [len(p) for p in group]
            rect = np.zeros((slots, max(glens)), np.int32)
            for r, p in enumerate(group):
                rect[r, :len(p)] = p
            eng_static.cfg.max_new_tokens = int(max(news[g:g + slots]))
            eng_static.generate(rect, prompt_lens=glens)
            tokens += int(np.sum(news[g:g + slots]))    # useful tokens only
        return tokens

    def run_continuous() -> int:
        for p, n in zip(prompts, news):
            eng_cont.submit(p, max_new_tokens=int(n))
        fins = eng_cont.drain()
        return sum(len(f.tokens) for f in fins)

    results = {}
    reps = 2 if quick else 3
    for name, fn in (("static", run_static), ("continuous", run_continuous)):
        fn()                                            # warm the compiles
        best = None
        for _ in range(reps):        # best-of-reps: tokens_per_sec is gated
            t0 = time.perf_counter()   # (direction-aware), so damp scheduler
            tokens = fn()              # jitter instead of gating one sample
            dt = time.perf_counter() - t0
            if best is None or dt < best[1]:
                best = (tokens, dt)
        tokens, dt = best
        results[name] = {"tokens": tokens, "seconds": dt,
                         "tokens_per_sec": tokens / max(dt, 1e-9)}
        print(f"serve_{name},{dt / max(tokens, 1) * 1e6:.1f},"
              f"{tokens / max(dt, 1e-9):.1f}tok/s")
    speedup = (results["continuous"]["tokens_per_sec"]
               / max(results["static"]["tokens_per_sec"], 1e-9))
    print(f"SPEEDUP,continuous/static={speedup:.2f}x")
    results["prefix"] = serving_prefix(model, params, cfg, rng, slots,
                                       max_len, n_req)
    return {"n_requests": n_req, "slots": slots,
            "static": results["static"], "continuous": results["continuous"],
            "continuous_over_static": speedup,
            "prefix": results["prefix"]}


def serving_prefix(model, params, cfg, rng, slots: int, max_len: int,
                   n_req: int) -> dict:
    """Prefix-heavy trace (requests drawn from 4 shared prompt templates)
    through paged KV + the content-hashed prefix cache, with both decode
    and the bucketed prefills served through stitch().  The gated metrics
    are deterministic: the prefix-cache hit rate must be nonzero (repeated
    prompts actually skip prefill) and every landed per-bucket prefill
    plan must report stitched kernels."""
    from repro.cache import CompilationService
    from repro.serve import Engine, ServeConfig

    svc = CompilationService()
    eng = Engine(model, params,
                 ServeConfig(batch=slots, max_len=max_len, page_size=8,
                             prefix_cache=True, stitch_execute=True),
                 stitch_service=svc)
    pool = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
            for p in rng.integers(8, 25, 4)]
    reqs = [pool[i] for i in rng.integers(0, len(pool), n_req)]

    def run() -> int:
        for p in reqs:
            eng.submit(p, max_new_tokens=6)
        return sum(len(f.tokens) for f in eng.drain())

    run()                                               # warm the compiles
    t0 = time.perf_counter()
    tokens = run()
    dt = time.perf_counter() - t0
    # join background stitch compiles so per-bucket kernel counts are
    # deterministic before the record is written
    pending = eng.land_plans(timeout=120.0)
    rep = eng.report()
    plans = {k: (v["plan"] or {}).get("n_kernels", 0)
             for k, v in rep["prefill"]["plans"].items()}
    px = rep["prefix_cache"]
    print(f"serve_prefix,{dt / max(tokens, 1) * 1e6:.1f},"
          f"hit_rate={px['hit_rate']:.2f}")
    print(f"serve_prefix_prefill_kernels,,{sum(plans.values())} "
          f"across {len(plans)} bucket(s), {pending} plan(s) pending")
    return {"tokens": tokens, "seconds": dt,
            "tokens_per_sec": tokens / max(dt, 1e-9),
            "prefix_cache": {"hit_rate": px["hit_rate"], "hits": px["hits"],
                             "misses": px["misses"]},
            "prefill": {"n_kernels": sum(plans.values()),
                        "buckets": len(plans), "plans": plans,
                        "pending": pending},
            "kv": {"peak_used": rep["kv"]["peak_used"],
                   "page_size": rep["kv"]["page_size"]}}


def training(quick: bool) -> dict:
    """Stitched training step vs plain jit: backward-graph kernel compression
    (off/xla/stitch) and the packed multi-tensor AdamW+clip update collapsing
    to one kernel, plus wall-clock step times (CPU interpret mode for the
    stitched path — overhead expected; the *deterministic* metrics are the
    kernel counts and modeled times the regression gate consumes)."""
    import jax
    import jax.numpy as jnp
    from repro.cache import CompilationService
    from repro.configs import get_reduced
    from repro.core import StitchCompiler
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.train import StitchedTrainStep, init_state, make_train_step

    print("\n# Training — stitched backward + packed AdamW vs jit step")
    print("name,us_per_call,derived")
    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    opt_cfg = AdamWConfig(warmup_steps=5, total_steps=100)
    B, S = 2, 16

    def batch(i):
        r = np.random.default_rng(1000 + i)
        return {"tokens": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32),
                "labels": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32)}

    state_jit = init_state(model, jax.random.PRNGKey(0))
    state_st = init_state(model, jax.random.PRNGKey(0))

    jit_step = jax.jit(make_train_step(model, opt_cfg))
    svc = CompilationService()
    st_step = StitchedTrainStep(model, opt_cfg, service=svc)

    # warm both paths; let the background stitch compiles land and upgrade
    state_jit, _ = jit_step(state_jit, batch(0))
    state_st, _ = st_step(state_st, batch(0))
    st_step.wait(timeout=300.0)
    state_st, _ = st_step(state_st, batch(1))   # poll -> upgraded artifacts

    reps = 2 if quick else 5
    times = {}
    for name, fn, s0 in (("jit", jit_step, state_jit),
                         ("stitched", st_step, state_st)):
        s = s0
        t0 = time.perf_counter()
        for i in range(reps):
            s, m = fn(s, batch(10 + i))
            jax.block_until_ready(m["loss"])
        times[name] = (time.perf_counter() - t0) / reps
        print(f"train_step_{name},{times[name] * 1e6:.0f},"
              + ("baseline" if name == "jit" else "interpret-mode-overhead-expected"))

    rep = st_step.report()
    statuses = {"grad": rep["grad"]["status"],
                "optimizer": rep["optimizer"]["status"],
                "fallback_steps": rep["fallback_steps"]}
    grad_graph = st_step._grad.graph
    if (grad_graph is None or rep["grad"].get("plan") is None
            or st_step._packed is None
            or rep["optimizer"].get("plan") is None):
        # trace/compile failure: the step served the jit fallback.  Record
        # the statuses but omit the gated metrics — check_regression then
        # reports "metric missing" (a clear gated failure, not a crash here).
        print(f"# training: stitched step unavailable ({statuses}); "
              "gated metrics omitted")
        return {"batch": B, "seq": S,
                "step_time_s": {"jit": times["jit"],
                                "stitched": times["stitched"]},
                "status": statuses}
    grad_kernels = {}
    grad_times = {}
    for mode in ("off", "xla"):
        cg = StitchCompiler(mode=mode, use_pallas=False).compile(grad_graph)
        grad_kernels[mode] = cg.stats.n_kernels
        grad_times[mode] = cg.stats.modeled_time
    grad_plan = rep["grad"]["plan"]
    grad_kernels["stitch"] = grad_plan["n_kernels"]
    grad_times["stitch"] = grad_plan["modeled_time"]

    packed = st_step._packed
    opt_graph = packed.graph
    cg_off = StitchCompiler(mode="off", use_pallas=False).compile(opt_graph)
    opt_plan = rep["optimizer"]["plan"]
    print(f"train_grad_kernels,,off={grad_kernels['off']} "
          f"xla={grad_kernels['xla']} stitch={grad_kernels['stitch']}")
    print(f"train_packed_update,,{cg_off.stats.n_kernels} ops -> "
          f"{opt_plan['n_kernels']} packed kernel(s)")
    print(f"# stitched upgrade: grad={rep['grad']['status']} "
          f"optimizer={rep['optimizer']['status']} "
          f"fallback_steps={rep['fallback_steps']}")

    return {
        "batch": B, "seq": S,
        "step_time_s": {"jit": times["jit"], "stitched": times["stitched"]},
        "grad": {
            "n_ops": grad_plan["n_ops"],
            "kernels": {**grad_kernels},
            "modeled_time_s": {**grad_times},
        },
        "packed_update": {
            "n_ops": opt_plan["n_ops"],
            "kernels": {"off": cg_off.stats.n_kernels,
                        "stitch": opt_plan["n_kernels"]},
            "modeled_time_s": {"off": cg_off.stats.modeled_time,
                               "stitch": opt_plan["modeled_time"]},
        },
        "status": statuses,
    }


def sharding(quick: bool) -> dict | None:
    """Mesh-aware stitched training under shard_map (forced 8-device host):
    per-shard backward + packed-update plans at shard-local shapes,
    trajectory agreement with the single-device stitched run, and the
    mesh-keyed cache behavior (one entry per placement)."""
    import jax
    import jax.numpy as jnp
    from repro.cache import CompilationService, StitchCache
    from repro.configs import get_reduced
    from repro.core import StitchCompiler
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.train import StitchedTrainStep, init_state

    n = len(jax.devices())
    print("\n# Sharding — shard_map stitched train step (per-shard graphs)")
    if n < 2:
        print("# skipped: single-device host "
              "(set --xla_force_host_platform_device_count)")
        return None
    mesh = make_host_mesh(2)
    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    opt_cfg = AdamWConfig(warmup_steps=5, total_steps=100)
    B, S = n, 8

    def batch(i):
        r = np.random.default_rng(7000 + i)
        return {"tokens": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32),
                "labels": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32)}

    # max_background=0 makes the upgrade deterministic: step 0 runs the XLA
    # fallbacks, then the stitched plans are landed synchronously
    svc_sh = CompilationService(max_background=0)
    st_sh = StitchedTrainStep(model, opt_cfg, service=svc_sh, mesh=mesh)
    svc_1d = CompilationService(max_background=0)
    st_1d = StitchedTrainStep(model, opt_cfg, service=svc_1d)

    s_sh = jax.device_put(init_state(model, jax.random.PRNGKey(0)),
                          st_sh.state_shardings())
    s_1d = init_state(model, jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    s_sh, m_sh = st_sh(s_sh, batch(0))
    s_1d, m_1d = st_1d(s_1d, batch(0))
    deltas = [abs(float(m_sh["loss"]) - float(m_1d["loss"]))]
    for st, svc in ((st_sh, svc_sh), (st_1d, svc_1d)):
        for phase in (st._grad, st._packed):
            svc.compiler("stitch", phase.placement).compile(
                phase.graph, bypass_cache_lookup=True)
    steps = 2 if quick else 4
    for i in range(1, steps):
        s_sh, m_sh = st_sh(s_sh, batch(i))
        s_1d, m_1d = st_1d(s_1d, batch(i))
        deltas.append(abs(float(m_sh["loss"]) - float(m_1d["loss"])))
    dt = time.perf_counter() - t0

    grad_plan = st_sh._grad.plan_stats()
    packed_plan = st_sh._packed.report().get("plan") or {}
    off = StitchCompiler(mode="off", use_pallas=False).compile(st_sh._grad.graph)

    # mesh-keyed entries: the same graph compiled under two placements makes
    # two distinct cache entries (neither shadows the other)
    entries_cache = StitchCache()
    for placement in (st_sh._grad.placement, ""):
        StitchCompiler(mode="stitch", use_pallas=False, cache=entries_cache,
                       placement=placement).compile(st_sh._packed.graph)
    mesh_keyed_entries = len(entries_cache.store.memory)

    print(f"shard_grad_kernels,,off={off.stats.n_kernels} "
          f"stitch={grad_plan['n_kernels']} (per-shard, "
          f"mesh={dict(mesh.shape)})")
    print(f"shard_packed_update,,{packed_plan.get('n_kernels')} packed "
          f"kernel(s) over TP-local panels")
    print(f"shard_trajectory,,max_loss_delta={max(deltas):.2e} over "
          f"{steps} steps ({dt:.1f}s)")
    print(f"shard_cache,,mesh_keyed_entries={mesh_keyed_entries} "
          f"(same graph, two placements)")
    print(f"# upgrade: grad={st_sh._grad.status} "
          f"optimizer={st_sh._packed.status} "
          f"fallback_steps={st_sh.fallback_steps}")

    return {
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "devices": n, "batch": B, "seq": S, "steps": steps,
        "grad_local": {
            "n_ops": grad_plan["n_ops"],
            "kernels": {"off": off.stats.n_kernels,
                        "stitch": grad_plan["n_kernels"]},
            "modeled_time_s": {"off": off.stats.modeled_time,
                               "stitch": grad_plan["modeled_time"]},
        },
        "packed_local": {
            "kernels": {"stitch": packed_plan.get("n_kernels")},
            "modeled_time_s": {"stitch": packed_plan.get("modeled_time")},
        },
        "trajectory": {"max_loss_delta_vs_single_device": max(deltas),
                       "statuses": {"grad": st_sh._grad.status,
                                    "optimizer": st_sh._packed.status,
                                    "fallback_steps": st_sh.fallback_steps}},
        "cache": {"mesh_keyed_entries": mesh_keyed_entries,
                  "per_placement": svc_sh.cache.report().get("per_placement")},
    }


def compute_stitching(quick: bool) -> dict:
    """Kernel-count evidence for compute-intensive stitching: a transformer
    block (rms -> q/k/v GEMMs -> Pallas flash attention -> output GEMM ->
    gelu MLP) compiling to one stitched kernel on each side of the flash
    attention kernel, and the serving decode step's plan.  Counts are deterministic — the gate
    holds them exactly (``lower`` direction + liveness), no wall clock."""
    import jax
    import jax.numpy as jnp
    from repro.cache import CompilationService
    from repro.configs import get_reduced
    from repro.exec import stitch
    from repro.kernels.flash_attention import flash_attention
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    print("\n# Compute stitching — GEMMs stitched around Pallas attention")
    print("name,us_per_call,derived")

    B, S, D, H = 2, 128, 16, 2
    dh, F = D // H, 64
    rng = np.random.default_rng(3)

    def mk(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)

    w = dict(wq=mk(D, D), wk=mk(D, D), wv=mk(D, D), wo=mk(D, D),
             w1=mk(D, F), w2=mk(F, D), g1=mk(D), g2=mk(D))
    x = mk(B, S, D)

    def rms(v, gain):
        return v * jax.lax.rsqrt(
            jnp.mean(v * v, axis=-1, keepdims=True) + 1e-6) * gain

    def attn_mlp_block(w, x):
        h = rms(x, w["g1"])
        q = (h @ w["wq"]).reshape(B, S, H, dh)
        k = (h @ w["wk"]).reshape(B, S, H, dh)
        v = (h @ w["wv"]).reshape(B, S, H, dh)
        a = flash_attention(q, k, v, causal=True).reshape(B, S, D)
        x2 = x + a @ w["wo"]
        return x2 + jax.nn.gelu(rms(x2, w["g2"]) @ w["w1"]) @ w["w2"]

    sf = stitch(attn_mlp_block, mode="offline", name="bench_attn_mlp_block")
    sf(w, x)
    plan = sf.report()["plan"] or {}
    block = {"n_ops": plan.get("n_ops"), "n_kernels": plan.get("n_kernels"),
             "pallas_groups": plan.get("pallas_groups")}
    print(f"block_fn_kernels,,{block['n_ops']}->{block['n_kernels']} "
          f"pallas={block['pallas_groups']}")

    # the same admission rules through serving: decode-step plan counts
    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    svc = CompilationService()
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, stitch_execute=True),
                 stitch_service=svc)
    for p in (rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
              for n in (6, 11)):
        eng.submit(p, max_new_tokens=4)
    eng.drain()
    pending = eng.land_plans(timeout=120.0)
    dplan = eng.report()["decode"]["plan"] or {}
    decode = {"n_ops": dplan.get("n_ops"), "n_kernels": dplan.get("n_kernels"),
              "pallas_groups": dplan.get("pallas_groups")}
    print(f"decode_step_kernels,,{decode['n_ops']}->{decode['n_kernels']} "
          f"pallas={decode['pallas_groups']}, {pending} plan(s) pending")
    return {"block_fn": block, "decode": decode}


def packing(quick: bool) -> dict:
    """Horizontal FFD packing on a wide-expert MoE block: the per-expert
    FFN chains are independent subgraphs, so the unpacked planner leaves
    them as per-expert kernel launches while the packer bins them into
    shared stitched kernels (paper §4.2).  The gated metrics are
    deterministic — packed kernel count (lower) and packs formed
    (positive); the measured interpret-mode step time is reported for the
    trajectory, not gated."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.core import StitchCompiler
    from repro.core.fusiongen import GenConfig
    from repro.core.trace import trace_to_graph
    from repro.models import build_model

    print("\n# Packing — horizontal FFD packs (MoE block, packed vs unpacked)")
    print("name,us_per_call,derived")
    # wide experts: each per-expert chain is register-feasible alone but the
    # dependence-connected monolith is not, so packing is the only cover
    # that shares launches (d_expert at the 2 MiB budget's edge)
    cfg = get_reduced("qwen2_moe_a2_7b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=16, top_k=2, d_expert=8192, n_shared=0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lp = model.layer_params(params, 0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 8, cfg.d_model)) * 0.1, cfg.dtype)
    g, names = trace_to_graph(model.block_fn, lp, x, name="moe_block")
    env = dict(zip(names, jax.tree_util.tree_leaves((lp, x))))

    reps = 1 if quick else 3
    out: dict = {}
    for key, pack in (("packed", True), ("unpacked", False)):
        comp = StitchCompiler(mode="stitch",
                              gen_cfg=GenConfig(pack_patterns=pack))
        art = comp.compile(g, bypass_cache_lookup=True)
        st = art.stats
        jax.block_until_ready(list(art(env).values()))   # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(list(art(env).values()))
        dt = (time.perf_counter() - t0) / reps
        out[key] = {
            "n_ops": st.n_ops,
            "n_kernels": st.n_kernels,
            "pallas_groups": st.pallas_groups,
            "packs": st.packs,
            "packed_subgraphs": st.packed_subgraphs,
            "modeled_time_s": st.modeled_time,
            "measured_step_s": dt,
        }
        print(f"moe_block_{key},{dt * 1e6:.0f},"
              f"{st.n_ops}->{st.n_kernels} kernels packs={st.packs} "
              f"modeled={st.modeled_time * 1e6:.1f}us")
    red = out["unpacked"]["n_kernels"] / max(out["packed"]["n_kernels"], 1)
    print(f"PACKING,kernel_reduction={red:.2f}x,"
          f"{out['packed']['packed_subgraphs']} subgraphs in "
          f"{out['packed']['packs']} pack(s)")
    return {
        "config": {"n_experts": cfg.moe.n_experts, "top_k": cfg.moe.top_k,
                   "d_expert": cfg.moe.d_expert, "d_model": cfg.d_model},
        "packed": out["packed"],
        "unpacked": out["unpacked"],
        "kernel_reduction": red,
    }


def perf_measured(quick: bool) -> dict:
    """Wall-clock interpret-mode stitched kernels vs unfused jnp on the
    canonical patterns — correctness + relative-ordering evidence — plus
    the obs kernel timer's measured-vs-modeled record for one stitched
    executable.  Returns the BENCH record's ``measured`` section; the
    regression gate checks the section *exists* (schema), the values stay
    ungated (interpret-mode wall clock is too noisy to gate)."""
    print("\n# Perf — measured (CPU interpret mode; relative ordering only)")
    print("name,us_per_call,derived")
    import jax
    from repro.kernels import ref
    from repro.kernels.norms import rmsnorm as k_rmsnorm
    from repro.kernels.softmax import softmax as k_softmax

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2048, 1024)).astype(np.float32)
    g = rng.standard_normal(1024).astype(np.float32)
    reps = 3 if quick else 10
    out: dict = {}

    def timeit(fn, *args):
        fn(*args)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / reps * 1e6

    unfused_rms = jax.jit(lambda x, g: ref.rmsnorm(x, g))
    t_ref = timeit(unfused_rms, x, g)
    t_pal = timeit(lambda x, g: k_rmsnorm(x, g), x, g)
    print(f"rmsnorm_oracle_jit,{t_ref:.1f},baseline")
    print(f"rmsnorm_stitched_interpret,{t_pal:.1f},interpret-mode-overhead-expected")
    out["rmsnorm_us"] = {"oracle_jit": t_ref, "stitched_interpret": t_pal}

    unfused_sm = jax.jit(lambda x: ref.softmax(x, 0.125))
    t_ref = timeit(unfused_sm, x)
    t_pal = timeit(lambda x: k_softmax(x, 0.125), x)
    print(f"softmax_oracle_jit,{t_ref:.1f},baseline")
    print(f"softmax_stitched_interpret,{t_pal:.1f},interpret-mode-overhead-expected")
    out["softmax_us"] = {"oracle_jit": t_ref, "stitched_interpret": t_pal}

    out["exec"] = _measured_exec(reps)
    return out


def _measured_exec(reps: int) -> dict:
    """Measured-vs-modeled through the opt-in obs kernel timer: one
    stitched executable, ``block_until_ready``-bracketed wall clock per
    call next to the plan's cost-model time — the per-plan comparison
    ``launch/inspect.py`` prints from a trace."""
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.exec import stitch

    def fused(x, g):
        h = x * jax.nn.sigmoid(1.702 * x)
        m = jnp.mean(h * h, axis=-1, keepdims=True)
        return h * jax.lax.rsqrt(m + 1e-6) * g

    sf = stitch(fused, mode="offline", name="bench_measured")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256, 512)), jnp.float32)
    g = jnp.asarray(rng.standard_normal(512), jnp.float32)
    sf(x, g)                                   # compile outside the timer
    obs.enable_timing()
    try:
        for _ in range(reps):
            sf(x, g)
    finally:
        obs.disable_timing()
    rep = sf.report()
    meas = (rep["measured"] or {}).get("stitched", {})
    plan = rep["plan"] or {}
    print(f"measured_stitched_exec,{meas.get('mean', 0.0) * 1e6:.1f},"
          f"modeled={plan.get('modeled_time', 0.0) * 1e6:.1f}us")
    return {"fn": "bench_measured", "calls": meas.get("count", 0),
            "measured_s": meas, "modeled_time_s": plan.get("modeled_time"),
            "n_kernels": plan.get("n_kernels")}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hw", default="V100", choices=["V100", "TPU_V5E"])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_*.json-style record of per-workload "
                         "kernel counts, modeled step times and cache "
                         "cold/warm compile times")
    args = ap.parse_args(sys.argv[1:])
    use_compile_cache()
    cost = CostModel(V100 if args.hw == "V100" else TPU_V5E)

    t0 = time.time()
    graphs = build_all()
    print(f"# built {len(graphs)} workload graphs in {time.time() - t0:.1f}s "
          f"(sizes: {', '.join(f'{k}={len(v.nodes)}' for k, v in graphs.items())})")

    table2(graphs, cost)
    workloads = table3(graphs, cost)
    fig6(graphs)
    fig7_fig8(graphs, cost)
    table4(graphs, cost)
    cache = cache_timing(graphs, cost, args.quick)
    verify = verify_section(graphs, cost, cache)
    serve = serving(args.quick)
    train = training(args.quick)
    shard = sharding(args.quick)
    compute = compute_stitching(args.quick)
    packs = packing(args.quick)
    measured = perf_measured(args.quick)

    if args.json:
        record = {
            "bench": "fusionstitching",
            "hw": cost.hw.name,
            "unix_time": time.time(),
            "quick": args.quick,
            "workloads": workloads,
            "cache": cache,
            "verify": verify,
            "serving": serve,
            "training": train,
            "compute_stitching": compute,
            "packing": packs,
            "measured": measured,
        }
        if shard is not None:
            record["sharding"] = shard
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"\n# wrote {args.json}")


if __name__ == "__main__":
    main()
