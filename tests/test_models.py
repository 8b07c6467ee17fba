"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
asserting output shapes + finiteness; decode/prefill consistency; pallas vs
ref kernel-mode equivalence at the model level."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config, get_reduced
from repro.kernels.ops import kernel_mode
from repro.models import SHAPES, build_model

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros((B, cfg.n_patch_tokens, cfg.d_model),
                                          cfg.dtype)
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_train_step_smoke(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    loss, aux = jax.jit(model.train_forward)(params, _batch(cfg))
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch} loss not finite"
    # gradient flows and is finite
    g = jax.grad(lambda p: model.train_forward(p, _batch(cfg))[0])(params)
    gn = sum(float(jnp.sum(jnp.square(l.astype(jnp.float32))))
             for l in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0, f"{arch} grad degenerate"


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_decode_shapes(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    B = 2
    cache = model.init_cache(B, 64)
    tok = jnp.zeros((B, 1), jnp.int32)
    extra = {}
    if cfg.family == "audio":
        extra["enc_out"] = jnp.zeros((B, 16, cfg.d_model), cfg.dtype)
    logits, new_cache = model.decode_step(params, cache, tok, **extra)
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert int(new_cache["length"]) == 1


@pytest.mark.parametrize("arch", ["qwen3_1_7b", "falcon_mamba_7b",
                                  "recurrentgemma_9b"])
def test_prefill_equals_decode_loop(arch):
    """prefill(prompt) logits == feeding the prompt token-by-token."""
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    B, S = 1, 8
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    lg_pre, _ = model.prefill(params, toks)
    cache = model.init_cache(B, S + 4)
    lg_dec = None
    for t in range(S):
        lg_dec, cache = model.decode_step(params, cache, toks[:, t:t + 1])
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(lg_dec),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["qwen3_1_7b", "granite_moe_1b_a400m",
                                  "falcon_mamba_7b", "recurrentgemma_9b"])
def test_pallas_mode_matches_ref_mode(arch):
    """Whole-model forward under kernel_mode('pallas') == ref mode."""
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg, B=2, S=32)
    with kernel_mode("ref"):
        l_ref, _ = model.train_forward(params, batch)
    with kernel_mode("pallas"):
        l_pal, _ = model.train_forward(params, batch)
    assert abs(float(l_ref) - float(l_pal)) < 5e-3, \
        f"{arch}: pallas {float(l_pal)} vs ref {float(l_ref)}"


def test_moe_capacity_drop_accounting():
    cfg = get_reduced("qwen2_moe_a2_7b")
    model = build_model(cfg)
    params = model.init(KEY)
    _, aux = model.train_forward(params, _batch(cfg, B=2, S=64))
    assert 0.0 <= float(aux["moe_drop_frac"]) < 0.5
    assert float(aux["moe_aux"]) > 0.5  # load-balance loss near 1 for uniform


def test_moe_block_horizontal_packing_acceptance():
    """Wide-expert MoE block (paper §4.2 acceptance): the planner must form
    >= 1 horizontal pack, fewer kernels than with ``pack_patterns=False``,
    every Pallas group within the chip's on-chip budget, and the packed
    plan, run as one program, must stay bitwise-equal to ``jax.jit`` of
    the block.  (Run op by op, every bfloat16 intermediate of the ops left
    outside Pallas kernels rounds, where XLA's fusion keeps float32.)

    The per-expert chains themselves do not pack here: each slices the
    stacked expert weights, which a kernel holds whole in VMEM, and at
    d_expert 8192 those tensors (32 MiB each) exceed a v5e kernel's 16 MiB,
    so no expert chain enters a Pallas kernel (the 4x compression this test
    once asserted rested on kernels the chip's compiler refuses)."""
    from repro.core import StitchCompiler
    from repro.core.fusiongen import GenConfig
    from repro.core.trace import trace_to_graph

    cfg = get_reduced("qwen2_moe_a2_7b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=16, top_k=2, d_expert=8192, n_shared=0))
    model = build_model(cfg)
    params = model.init(KEY)
    lp = model.layer_params(params, 0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 8, cfg.d_model)) * 0.1, cfg.dtype)
    g, names = trace_to_graph(model.block_fn, lp, x, name="moe_block")
    env = dict(zip(names, jax.tree_util.tree_leaves((lp, x))))
    ref_leaves = jax.tree_util.tree_leaves(jax.jit(model.block_fn)(lp, x))

    packed = StitchCompiler(mode="stitch", gen_cfg=GenConfig(
        pack_patterns=True)).compile(g, bypass_cache_lookup=True)
    unpacked = StitchCompiler(mode="stitch", gen_cfg=GenConfig(
        pack_patterns=False)).compile(g, bypass_cache_lookup=True)

    assert packed.stats.packs >= 1
    assert packed.stats.packed_subgraphs >= 2 * packed.stats.packs
    assert unpacked.stats.n_kernels > packed.stats.n_kernels, (
        f"packing compression eroded: {unpacked.stats.n_kernels} unpacked "
        f"vs {packed.stats.n_kernels} packed")
    from repro.core.cost import CostModel
    from repro.core.tuner import grid_row_block
    cost = CostModel()
    for plan in (packed, unpacked):
        for grp in plan.groups:
            if grp.kind == "pallas":
                t = grp.tuned
                need = (t.scratch_plan.allocated + cost.vmem_request(
                    t.pattern, grid_row_block(t.template)))
                assert need <= cost.hw.onchip_budget
    out = jax.jit(packed.__call__)(env)
    for name, want in zip(g.outputs, ref_leaves):
        np.testing.assert_array_equal(np.asarray(out[name]),
                                      np.asarray(want))


def test_param_count_analytic_close_to_actual():
    for arch in ("qwen3_1_7b", "phi3_mini_3_8b", "granite_moe_1b_a400m",
                 "falcon_mamba_7b"):
        cfg = get_reduced(arch)
        model = build_model(cfg)
        actual = sum(int(np.prod(l.shape))
                     for l in jax.tree.leaves(model.init(KEY)))
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.15, \
            f"{arch}: analytic {analytic} vs actual {actual}"


def test_full_configs_match_spec():
    """The full (non-reduced) configs carry the exact assigned sizes."""
    c = get_config("qwen2.5-32b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (64, 5120, 40, 8, 27648, 152064)
    assert c.qkv_bias
    c = get_config("granite-moe-1b-a400m")
    assert c.moe.n_experts == 32 and c.moe.top_k == 8 and c.vocab == 49155
    c = get_config("qwen2-moe-a2.7b")
    assert c.moe.n_experts == 60 and c.moe.top_k == 4 and c.moe.n_shared == 4
    c = get_config("falcon-mamba-7b")
    assert c.n_layers == 64 and c.ssm.d_state == 16 and c.vocab == 65024
    c = get_config("recurrentgemma-9b")
    assert c.n_layers == 38 and c.hybrid.window == 2048
    c = get_config("seamless-m4t-medium")
    assert c.n_encoder_layers == 12 and c.vocab == 256206
    c = get_config("llava-next-mistral-7b")
    assert c.n_patch_tokens == 576 and c.d_ff == 14336
    c = get_config("nemotron-4-15b")
    assert c.act == "sqrelu" and c.norm == "ln" and c.vocab == 256000
    c = get_config("phi3-mini-3.8b")
    assert c.d_model == 3072 and c.d_ff == 8192
    c = get_config("qwen3-1.7b")
    assert c.qk_norm and c.head_dim == 128
