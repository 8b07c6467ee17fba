"""Stitching your own function: the jit-like ``repro.exec.stitch()`` API.

Five demos, none of which flow through the train or serve machinery:

1. an arbitrary user pytree function (nested dicts/tuples, kwargs),
2. a Mamba block and a Griffin RG-LRU block via ``Model.block_fn`` —
   workloads the fusion pipeline had never seen before the exec refactor,
3. compute stitching: a transformer block (q/k/v projections, Pallas flash
   attention, output projection, gelu MLP) collapsing to one stitched kernel
   on each side of the flash-attention kernel,
4. the same user function dispatched over a ``--model-parallel``-style
   host mesh through ``shard_map``, with a mesh-keyed cache placement,
5. horizontal packing: a wide-expert MoE block planned with FFD packs under
   the chip's VMEM budget.

    PYTHONPATH=src python examples/stitch_fn.py
"""

import sys

# rehearse the sharded demo on any host (respects operator XLA_FLAGS)
from repro.launch.hostenv import force_host_devices

force_host_devices(8, argv=sys.argv)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.cache import CompilationService
from repro.configs import get_reduced
from repro.exec import stitch
from repro.models import build_model


def show(name, sf):
    rep = sf.report()
    plan = rep.get("plan", {})
    print(f"  [{name}] status={rep['status']} "
          f"kernels={plan.get('n_ops', '?')}->{plan.get('n_kernels', '?')} "
          f"pallas={plan.get('pallas_groups', '?')} "
          f"stitched_calls={rep['stitched_calls']} "
          f"fallback_calls={rep['fallback_calls']}")


def check(got, want, what, tol=2e-4):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol)
    print(f"  {what}: matches the jit reference")


def demo_user_function(svc):
    print("\n-- 1. arbitrary pytree function ------------------------------")

    def my_fn(state, batch, *, temperature=1.0):
        h = jnp.tanh(batch["x"] @ state["w"]) + state["b"]
        e = jnp.exp(h / temperature - jnp.max(h, -1, keepdims=True))
        probs = e / jnp.sum(e, -1, keepdims=True)
        return {"probs": probs, "entropy": -jnp.sum(
            probs * jnp.log(probs + 1e-9), -1)}

    rng = np.random.default_rng(0)
    state = {"w": jnp.asarray(rng.standard_normal((64, 64)) * 0.1, jnp.float32),
             "b": jnp.asarray(rng.standard_normal(64) * 0.1, jnp.float32)}
    batch = {"x": jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)}

    sf = stitch(my_fn, service=svc, name="my_fn")
    out = sf(state, batch, temperature=0.7)        # step 0: fallback artifact
    svc.wait(120.0)                                # let the upgrade land
    out = sf(state, batch, temperature=0.7)        # upgraded: stitched plan
    check(out, jax.jit(lambda s, b: my_fn(s, b, temperature=0.7))(state, batch),
          "pytree + kwargs")
    show("my_fn", sf)


def demo_model_blocks(svc):
    print("\n-- 2. Mamba / Griffin blocks (never trained, never served) ---")
    rng = np.random.default_rng(1)
    for arch in ("falcon_mamba_7b", "recurrentgemma_9b"):
        model = build_model(get_reduced(arch))
        if model.block_fn is None:
            continue
        params = model.init(jax.random.PRNGKey(0))
        if arch == "falcon_mamba_7b":
            lp = model.layer_params(params, 0)
        else:  # griffin: first recurrent layer of the first super-block
            lp = jax.tree.map(lambda l: l[0], params["supers"])["l0"]
        x = jnp.asarray(rng.standard_normal(
            (2, 16, model.cfg.d_model)), model.cfg.dtype)
        sf = stitch(model.block_fn, service=svc, name=f"{arch}_block")
        out = sf(lp, x)
        svc.wait(120.0)
        out = sf(lp, x)
        # bf16 recurrent blocks: XLA rewrites the scan body under jit (loop
        # fusion changes bf16 roundings, compounding over time steps), so
        # even *eager* jax diverges from jit by a few bf16 ulps here
        tol = 5e-2 if model.cfg.dtype == "bfloat16" else 2e-4
        check(out, jax.jit(model.block_fn)(lp, x), f"{arch} block", tol=tol)
        show(f"{arch}_block", sf)


def demo_compute_stitching(svc):
    print("\n-- 3. compute stitching: transformer block -> 3 kernels -------")
    from repro.kernels.flash_attention import flash_attention

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

    sf = stitch(attn_mlp_block, service=svc, name="attn_mlp_block")
    out = sf(w, x)
    svc.wait(120.0)
    out = sf(w, x)                  # stitched | flash attention | stitched
    check(out, jax.jit(attn_mlp_block)(w, x), "attention+MLP block")
    show("attn_mlp_block", sf)


def demo_sharded(svc):
    print("\n-- 4. shard_map dispatch over the host mesh ------------------")
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2 if jax.device_count() % 2 == 0 else 1)
    allax = tuple(mesh.axis_names)

    def local_loss(params, b):
        h = jnp.tanh(b @ params["w"]) + params["c"]
        return jax.lax.pmean(jnp.mean(jnp.square(h)), allax), h

    rng = np.random.default_rng(2)
    params = {"w": jnp.asarray(rng.standard_normal((32, 32)) * 0.1, jnp.float32),
              "c": jnp.asarray(rng.standard_normal(32) * 0.1, jnp.float32)}
    b = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)

    sf = stitch(local_loss, service=svc, mesh=mesh,
                in_specs=(P(), P(allax)), out_specs=(P(), P(allax)),
                name="sharded_loss")
    loss, h = sf(params, b)
    svc.wait(120.0)
    loss, h = sf(params, b)
    ref_l, ref_h = jax.jit(
        lambda p, x: (jnp.mean(jnp.square(jnp.tanh(x @ p["w"]) + p["c"])),
                      jnp.tanh(x @ p["w"]) + p["c"]))(params, b)
    check((loss, h), (ref_l, ref_h), f"mesh={dict(mesh.shape)} dispatch")
    print(f"  cache placement: {sf.placement}")
    show("sharded_loss", sf)


def demo_horizontal_packing(svc):
    print("\n-- 5. horizontal packing: wide-expert MoE block ---------------")
    import dataclasses

    # experts wide enough that the dependence-connected monolith is
    # occupancy-infeasible (paper §4.2); on a TPU v5e the per-expert chains
    # do not pack either: each holds the stacked expert weights in VMEM,
    # over a kernel's budget, so the pack that forms is of smaller twins
    cfg = get_reduced("qwen2_moe_a2_7b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=16, top_k=2, d_expert=8192, n_shared=0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lp = model.layer_params(params, 0)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 8, cfg.d_model)) * 0.1, cfg.dtype)

    sf = stitch(model.block_fn, service=svc, name="moe_block")
    out = sf(lp, x)                 # step 0: fallback artifact
    svc.wait(240.0)
    out = sf(lp, x)                 # upgraded: packed stitched plan
    # bfloat16 ops outside Pallas kernels run one at a time and round each
    # intermediate, where jit's fusion keeps float32: the model-block
    # tolerance of demo 2
    check(out, jax.jit(model.block_fn)(lp, x), "MoE block", tol=5e-2)
    plan = sf.report().get("plan", {})
    assert plan.get("packs", 0) >= 1, "the MoE block must carry a pack"
    print(f"  packs={plan.get('packs')} "
          f"packed_subgraphs={plan.get('packed_subgraphs')} "
          f"kernels={plan.get('n_ops')}->{plan.get('n_kernels')}")
    show("moe_block", sf)


def main():
    svc = CompilationService()
    demo_user_function(svc)
    demo_model_blocks(svc)
    demo_compute_stitching(svc)
    demo_sharded(svc)
    demo_horizontal_packing(svc)
    print("\ncache:", {k: v for k, v in svc.cache.report().items()
                       if k in ("hits", "misses", "memory_entries")})
    print("OK")


if __name__ == "__main__":
    main()
