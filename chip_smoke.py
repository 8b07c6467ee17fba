"""Chip smoke: stitched serving of full-width qwen3-1.7b on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded paths, on a four-chip host

One chip.  ``qwen3_1_7b`` at its published widths (28 layers, d_model 2048,
16 query and 8 KV heads of 128, d_ff 6144, vocab 151936, tied embeddings),
random float32 weights from a seed, served through the same
``build_engine`` -> ``Engine`` path as ``python -m repro.launch.serve
--stitch``: 4 slots over paged KV.  The stitched plans of every prefill
bucket and of the decode step land before the first request.  Then the
stitched engine's prefill and decode logits are compared with those of the
same engine built without stitching (plain ``jax.jit``) at identical
inputs, and 8 seeded requests are served.

Four chips.  Two phases, each against its reference: the DP-replica
stitched serve (4 replicas over ``make_host_mesh(1)``, full width) against
the unsharded jit engine, and a few ``StitchedTrainStep`` steps at TP=2 x
DP=2, full width with depth cut (printed), against the sharded ``jax.jit``
loss trajectory.

Everything runs in this one process: a chip belongs to one process.  The
script fails (nonzero exit, no result line) when JAX finds no TPU, when a
stitched specialization ends in ``error`` or ``failed`` or never lands,
when the decode plan holds no Pallas kernel, when no decode step ran
through the stitched program, or when the stitched results leave the
reference's by more than the tolerances below.  Its last line is one JSON
object naming the device.  Every number printed before it is a liveness
record, not a measurement.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
ARCH = "qwen3-1.7b"
SLOTS = 4
# DP-replica serving: two slots per replica.  At one slot a replica's
# decode has no row axis longer than 1, and its final norm forms no
# stitched Pallas kernel.
DP_SLOTS = 8
MAX_LEN = 256
PAGE_SIZE = 16
REQUESTS = 8
PROMPT_LENS = (32, 128)     # inclusive range of the seeded prompt lengths
NEW_TOKENS = 16
DECODE_CHECK_STEPS = 3      # decode steps compared against the jit engine
PLAN_BUDGET = 60.0          # seconds the fusion-plan ILP may take per graph
# Logits agree within LOGIT_ULPS bfloat16 ulps (2**-8 relative) of the
# reference's largest |logit|.  Activations and the LM head are bfloat16.
# The stitched program runs the same ops a kernel at a time and may round
# an intermediate to bfloat16 where XLA's fusion keeps it in float32; each
# such rounding moves a logit by about one ulp of the logit scale.  A wrong
# kernel moves logits by the logit scale itself.
LOGIT_ULPS = 8
# The training phase cuts depth so that two TP-sharded train states (the
# stitched one and the jit reference), the gathered parameters and the
# gradients fit a 16 GB chip; widths stay published.
TRAIN_LAYERS = 4
TRAIN_STEPS = 4
TRAIN_BATCH, TRAIN_SEQ = 4, 128
# Loss and gradient norm agree with the sharded jit trajectory within these
# relative tolerances: the loss is a mean over TRAIN_BATCH * TRAIN_SEQ
# tokens of bfloat16 activations, so per-token rounding averages out; the
# gradient norm sums squares of bfloat16-rounded gradients.
LOSS_RTOL, GRAD_NORM_RTOL = 1e-2, 5e-2


class SmokeFailure(RuntimeError):
    pass


def _require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- serving -------------------------------------------------------------------

def _serve_args(slots: int, stitch: bool, mesh: bool = False,
                dense: bool = False):
    """The launcher's own argument defaults, set as a user would set them."""
    from repro.launch.serve import make_parser

    argv = ["--arch", ARCH, "--slots", str(slots), "--max-len", str(MAX_LEN),
            "--new-tokens", str(NEW_TOKENS), "--page-size", str(PAGE_SIZE),
            "--seed", str(SEED)]
    if stitch:
        argv += ["--stitch", "--plan-budget", str(PLAN_BUDGET)]
    if mesh:
        argv += ["--mesh"]
    if dense:
        argv += ["--dense"]
    return make_parser().parse_args(argv)


def _prompts(vocab: int, n: int, lens=PROMPT_LENS):
    import numpy as np

    rng = np.random.default_rng(SEED)
    sizes = rng.integers(lens[0], lens[1] + 1, n)
    return [rng.integers(0, vocab, (int(k),)).astype(np.int32) for k in sizes]


class _LogitTap:
    """Stands in for an engine's stitched function and keeps the logits of
    each call; everything else goes to the function it wraps."""

    def __init__(self, fn):
        self.fn = fn
        self.logits = []

    def __call__(self, *args, **kwargs):
        logits, cache = self.fn(*args, **kwargs)
        self.logits.append(logits)
        return logits, cache

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _check_logits(what: str, got, ref) -> tuple[float, float]:
    """(max |got - ref|, tolerance); raises when the difference exceeds the
    tolerance or when a row's greedy token differs although the reference's
    top-2 margin exceeds it."""
    import numpy as np

    got = np.asarray(got, np.float32).reshape(-1, np.shape(ref)[-1])
    ref = np.asarray(ref, np.float32).reshape(got.shape)
    _require(np.isfinite(got).all(), f"{what}: non-finite stitched logits")
    tol = LOGIT_ULPS * 2.0 ** -8 * float(np.abs(ref).max())
    diff = float(np.abs(got - ref).max())
    _require(diff <= tol, f"{what}: max |logit diff| {diff} > {tol}")
    top2 = np.partition(ref, -2, axis=-1)[:, -2:]
    firm = (top2[:, 1] - top2[:, 0]) > tol
    agree = got.argmax(-1) == ref.argmax(-1)
    _require(bool(agree[firm].all()),
             f"{what}: greedy tokens differ where the top-2 margin > {tol}")
    return diff, tol


def _land(eng, prompts) -> float:
    """Trace every prefill bucket and the decode step once, then block
    until their stitched plans land.  Returns the seconds it took."""
    from repro.serve.scheduler import ADMISSION_BUCKET

    t0 = time.perf_counter()
    per_bucket = {}
    for p in prompts:
        per_bucket.setdefault(ADMISSION_BUCKET.bucket_dim(len(p)), p)
    for p in per_bucket.values():
        eng.prefill(p)
    eng.insert(eng.prefill(prompts[0]), 0)
    eng.generate_step(1)
    eng.release(0)
    lacking = eng.land_plans()
    _require(lacking == 0, f"{lacking} stitched specializations never landed")
    return time.perf_counter() - t0


def _check_report(rep: dict) -> None:
    for which in ("decode", "prefill"):
        r = rep[which]
        for placement, sp in r["plans"].items():
            _require(sp["status"] == "hit",
                     f"{which} {placement!r}: status {sp['status']}: "
                     f"{r['error']}")
        _require(not r["errors"], f"{which}: compile errors {r['errors']}")
        _require(r["service_error"] is None,
                 f"{which}: service error {r['service_error']}")


def _plan_line(rep: dict) -> str:
    parts = []
    for which in ("decode", "prefill"):
        for placement, sp in sorted(rep[which]["plans"].items()):
            plan = sp["plan"]
            parts.append(f"{which}[{placement or 'single-device'}] "
                         f"ops={plan['n_ops']} kernels={plan['n_kernels']} "
                         f"pallas={plan['pallas_groups']}")
    return "; ".join(parts)


def _compare(stitched, ref, prompts) -> tuple[float, float]:
    """Fill one slot per prompt of ``stitched``.  Each prompt's prefill runs
    in both engines; then the same decode cache and tokens go to both
    engines' decode step for ``DECODE_CHECK_STEPS`` steps, each step
    continuing from the reference's output.  Returns the max logit
    difference and the smallest tolerance it was held to."""
    import jax.numpy as jnp

    taps = (_LogitTap(stitched._prefill_exec), _LogitTap(ref._prefill_exec))
    stitched._prefill_exec, ref._prefill_exec = taps
    try:
        for slot, p in enumerate(prompts):
            stitched.insert(stitched.prefill(p), slot)
            ref.prefill(p)
    finally:
        stitched._prefill_exec, ref._prefill_exec = (t.fn for t in taps)
    worst, tightest = 0.0, float("inf")
    first = []

    def check(what, got, want):
        nonlocal worst, tightest
        diff, tol = _check_logits(what, got, want)
        worst, tightest = max(worst, diff), min(tightest, tol)

    for got, want in zip(*(t.logits for t in taps)):
        check("prefill", got, want)
        first.append(int(jnp.argmax(want[0])))

    for slot in range(len(prompts)):
        stitched.kv.ensure(slot, DECODE_CHECK_STEPS)
    cache = stitched.kv.decode_cache()
    tok = jnp.zeros((stitched.cfg.batch, 1), jnp.int32).at[:len(first), 0].set(
        jnp.asarray(first, jnp.int32))
    rows = len(prompts)
    for step in range(DECODE_CHECK_STEPS):
        got, _ = stitched._decode_dispatch(cache, tok, {})
        want, cache = ref._decode_dispatch(cache, tok, {})
        check(f"decode step {step}", got[:rows], want[:rows])
        tok = jnp.argmax(want, axis=-1).astype(jnp.int32)[:, None]
    for slot in range(len(prompts)):
        stitched.release(slot)
    return worst, tightest


def _serve(eng, prompts, vocab: int) -> int:
    """Serve ``prompts`` in waves of one per slot through prefill -> insert
    -> generate_step; returns the tokens served."""
    import numpy as np

    served, slots = 0, eng.cfg.batch
    for at in range(0, len(prompts), slots):
        wave = prompts[at:at + slots]
        first = []
        for slot, p in enumerate(wave):
            px = eng.prefill(p)
            first.append(int(px.first_tokens[0]))
            eng.insert(px, slot)
        toks = eng.generate_step(NEW_TOKENS - 1)[:len(wave)]
        out = np.concatenate([np.asarray(first)[:, None], toks], axis=1)
        _require(((out >= 0) & (out < vocab)).all(), "token out of vocab")
        served += out.size
        for slot in range(len(wave)):
            eng.release(slot)
    return served


def _serve_phase(cfg, mesh=None) -> None:
    """Land, compare and serve: one chip (``mesh`` None) or DP replicas."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.serve import build_engine
    from repro.models import build_model

    model = build_model(cfg)
    t0 = time.perf_counter()
    init = jax.jit(model.init, out_shardings=(
        None if mesh is None else NamedSharding(mesh, P())))
    params = jax.block_until_ready(init(jax.random.PRNGKey(SEED)))
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab}: {nbytes / 1e9:.2f} GB of params in "
          f"{time.perf_counter() - t0:.1f}s")

    slots = SLOTS if mesh is None else DP_SLOTS
    stitched = build_engine(_serve_args(slots, stitch=True,
                                        mesh=mesh is not None),
                            cfg, model, params)
    ref = build_engine(_serve_args(slots, stitch=False,
                                   dense=mesh is not None),
                       cfg, model, params)
    if mesh is None:
        prompts = _prompts(cfg.vocab, REQUESTS)
    else:   # one admission bucket keeps the four-chip call short
        prompts = _prompts(cfg.vocab, slots, lens=(40, 64))

    land_s = _land(stitched, prompts)
    rep = stitched.report()
    _check_report(rep)
    print(f"plans landed in {land_s:.1f}s (trace + fallback runs + plan): "
          f"{_plan_line(rep)}")
    decode_plan = rep["decode"]["plan"]
    _require(decode_plan["pallas_groups"] > 0,
             "the decode plan has no Pallas group")
    calls0 = dict(rep["decode"]["calls"])

    t0 = time.perf_counter()
    diff, tol = _compare(stitched, ref, prompts[:slots])
    print(f"logits vs jit engine: max |diff| {diff:.6g} (tolerance at "
          f"least {tol:.6g}) over {min(slots, len(prompts))} prefills and "
          f"{DECODE_CHECK_STEPS} decode steps ({time.perf_counter() - t0:.1f}s"
          f", first stitched calls compile their kernels)")

    t0 = time.perf_counter()
    served = _serve(stitched, prompts, cfg.vocab)
    rep = stitched.report()
    _check_report(rep)
    calls = rep["decode"]["calls"]
    stitched_calls = calls["stitched"] - calls0["stitched"]
    _require(stitched_calls > 0, "no decode step ran stitched")
    _require(calls["fallback"] == calls0["fallback"],
             "decode fell back to jit while serving")
    print(f"served {len(prompts)} requests, {served} tokens in "
          f"{time.perf_counter() - t0:.1f}s; stitched decode calls "
          f"{stitched_calls}" + (f" over {stitched.dp_replicas} DP replicas"
                                 if mesh is not None else ""))


# -- training (four chips) -------------------------------------------------------

def _train_phase(cfg) -> None:
    """TP=2 x DP=2 StitchedTrainStep against the sharded jit step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.cache import CompilationService
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.train import StitchedTrainStep, init_state, make_train_step

    print(f"train: depth cut {cfg.n_layers} -> {TRAIN_LAYERS} layers so two "
          f"sharded train states fit; widths as published")
    cfg = dataclasses.replace(cfg, n_layers=TRAIN_LAYERS)
    model = build_model(cfg)
    mesh = make_host_mesh(2)
    opt_cfg = AdamWConfig(warmup_steps=2, total_steps=20)
    step = StitchedTrainStep(
        model, opt_cfg, service=CompilationService(plan_budget=PLAN_BUDGET),
        mesh=mesh)
    init = jax.jit(lambda key: init_state(model, key),
                   out_shardings=step.state_shardings())
    state = init(jax.random.PRNGKey(SEED))
    ref = init(jax.random.PRNGKey(SEED))
    jit_step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0,))

    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    for i in range(TRAIN_STEPS):
        batch = {k: jnp.asarray(rng.integers(0, cfg.vocab,
                                             (TRAIN_BATCH, TRAIN_SEQ)),
                                jnp.int32) for k in ("tokens", "labels")}
        state, m = step(state, batch)
        ref, m_ref = jit_step(ref, batch)
        loss, gnorm = float(m["loss"]), float(m["grad_norm"])
        loss_ref, gnorm_ref = float(m_ref["loss"]), float(m_ref["grad_norm"])
        rep = step.report()
        print(f"train step {i}: loss {loss:.6f} (jit {loss_ref:.6f}), "
              f"grad_norm {gnorm:.6f} (jit {gnorm_ref:.6f}), grad "
              f"{rep['grad']['status']}, optimizer "
              f"{rep['optimizer']['status']}")
        _require(np.isfinite([loss, gnorm]).all(), "non-finite train metrics")
        _require(abs(loss - loss_ref) <= LOSS_RTOL * abs(loss_ref),
                 f"step {i}: loss {loss} vs jit {loss_ref}")
        _require(abs(gnorm - gnorm_ref) <= GRAD_NORM_RTOL * abs(gnorm_ref),
                 f"step {i}: grad norm {gnorm} vs jit {gnorm_ref}")
        if i == 0:
            step.wait()     # land the stitched plans; the next step upgrades
    rep = step.report()
    for phase in ("grad", "optimizer"):
        r = rep[phase]
        _require(r["status"] == "hit", f"train {phase}: status {r['status']}")
        _require(not r["errors"], f"train {phase}: errors {r['errors']}")
    _require(rep["fallback_steps"] == 0, "train steps fell back to jit")
    _require(rep["grad"]["calls"]["stitched"] > 0, "no stitched train step")
    print(f"train: {TRAIN_STEPS} steps at mesh {rep['mesh']} in "
          f"{time.perf_counter() - t0:.1f}s, grad plan "
          f"{rep['grad']['plan']['n_kernels']} kernels "
          f"({rep['grad']['plan']['pallas_groups']} Pallas)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the DP-replica serve and TP x DP training "
                         "phases on a four-chip host, and nothing else")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.hostenv import use_compile_cache

    use_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    cfg = get_config(ARCH)
    if args.chips == 1:
        _serve_phase(cfg)
    else:
        from repro.launch.mesh import make_host_mesh

        _serve_phase(cfg, mesh=make_host_mesh(1))
        gc.collect()        # free the replicated serving params first
        _train_phase(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
