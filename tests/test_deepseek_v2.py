"""DeepSeek-V2's latent attention, leading dense layer and dropless expert
share, served through the paged, stitched engine, against the plain
float32 reference of the chip benchmark (``benchmarks/chip/reference/
deepseek_v2.py``) on seeded random weights, at a small size with the
published structure: one dense layer then three routed layers, a latent
rank, the nope/rope split, YaRN on, this chip holding 4 of 16 routed
experts.

Tolerances.  Program and reference both compute in float32 and differ only
in the order of their sums (absorbed against expanded attention, a grouped
matmul against a dense one), so logits agree to ``RTOL``/``ATOL`` = 2e-4;
the same program in bfloat16 misses them by two orders of magnitude, which
``test_bfloat16_program_fails_the_tolerance`` checks.  Parts that do the
same arithmetic in another grouping (shares against the uncut layer,
absorbed against expanded decode) agree to 1e-5.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import arch  # noqa: E402
import weights  # noqa: E402
from reference import deepseek_v2 as ref  # noqa: E402

from repro import kernels, obs  # noqa: E402
from repro.cache import CompilationService, StitchCache  # noqa: E402
from repro.configs import get_reduced  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import layers, lm  # noqa: E402
from repro.serve import Engine, ServeConfig  # noqa: E402
from repro.serve.kv import PagedKV  # noqa: E402

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
TINY = {"model_type": "deepseek_v2", "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 4, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "norm_topk_prob": False, "routed_scaling_factor": 1.0,
        "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
        "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "max_position_embeddings": 163840, "rope_scaling": YARN,
        "deployment": {"experts": 16, "experts_held": [0, 4]},
        "serve_dtype": "float32"}
RTOL, ATOL = 2e-4, 2e-4


def _held(cfg: dict, e0: int, n: int) -> dict:
    return dict(cfg, n_routed_experts=n,
                deployment=dict(cfg["deployment"], experts_held=[e0, e0 + n]))


def _program(cfg: dict, dtype: str = "float32"):
    mcfg = dataclasses.replace(arch.load(cfg).program(cfg, "t"), dtype=dtype)
    return build_model(mcfg)


class _LogitTap:
    """Stands in for an engine's stitched function and keeps the logits of
    each call."""

    def __init__(self, fn):
        self.fn = fn
        self.logits = []

    def __call__(self, *args, **kwargs):
        logits, cache = self.fn(*args, **kwargs)
        self.logits.append(np.asarray(logits))
        return logits, cache

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _stitched_engine(model, w, tmp_path, slots=3, max_len=64):
    """A paged engine whose prefill and decode run their stitched plans:
    one round traces and plans, the next compiles them."""
    eng = Engine(model, w, ServeConfig(batch=slots, max_len=max_len,
                                       page_size=8, paged=True,
                                       stitch_execute=True),
                 stitch_service=CompilationService(StitchCache(tmp_path)))
    warm = np.arange(1, 12, dtype=np.int32)
    for _ in range(2):
        eng.insert(eng.prefill(warm), 0)
        eng.generate_step(1)
        eng.release(0)
        assert eng.land_plans() == 0
    return eng


def _serve(eng, prompts, steps):
    """Prefill each prompt into its slot, then ``steps`` decode steps;
    returns (first tokens, decode tokens)."""
    first = []
    for slot, p in enumerate(prompts):
        px = eng.prefill(p)
        eng.insert(px, slot)
        first.append(int(px.first_tokens[0]))
    return first, eng.generate_step(steps)


def test_stitched_prefill_and_paged_decode_match_reference(tmp_path):
    cfg = TINY
    w = weights.make(cfg, 3)
    eng = _stitched_engine(_program(cfg), w, tmp_path)
    eng._exec, eng._prefill_exec = _LogitTap(eng._exec), _LogitTap(eng._prefill_exec)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (21, 9, 30)]
    first, toks = _serve(eng, prompts, 4)
    for slot, p in enumerate(prompts):
        want = ref.logits_at(w, p, [len(p) - 1], cfg)
        np.testing.assert_allclose(eng._prefill_exec.logits[slot], want,
                                   rtol=RTOL, atol=ATOL)
        seq = np.concatenate([p, [first[slot]], toks[slot, :3]]).astype(np.int32)
        want = ref.logits_at(w, seq, len(p) + np.arange(4), cfg)
        got = np.stack([t[slot] for t in eng._exec.logits])
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    rep = eng.stitch_report()
    assert rep["calls"]["stitched"] >= 4 and rep["calls"]["fallback"] == 0
    assert eng.report()["prefill"]["calls"]["fallback"] == 0
    assert rep["attention"] == rep["paged_attention"] == "mla-gather"
    assert rep["moe"] == {"held": [0, 4], "experts": 16, "dropless": True}


def test_bfloat16_program_fails_the_tolerance():
    """The logit tolerance tells float32 from bfloat16 arithmetic."""
    cfg = TINY
    w = weights.make(cfg, 3)
    seq = np.random.default_rng(1).integers(0, cfg["vocab_size"], 24)
    want = np.asarray(ref.logits_at(w, seq, [23], cfg))
    tokens = jnp.asarray(seq[None], jnp.int32)
    got32, _ = _program(cfg).prefill(w, tokens)
    got16, _ = _program(cfg, "bfloat16").prefill(w, tokens)
    np.testing.assert_allclose(got32, want, rtol=RTOL, atol=ATOL)
    assert np.abs(np.asarray(got16) - want).max() > 10 * ATOL


def test_absorbed_decode_matches_expanded_prefill():
    """Decoding one token over the dense latent rectangle (absorbed form)
    gives the logits of a prefill over the longer sequence (expanded)."""
    cfg = TINY
    w = weights.make(cfg, 4)
    model = _program(cfg)
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 13)),
                       jnp.int32)
    _, cache = model.prefill(w, toks[:, :12], max_len=16)
    cache = {"lat": cache["lat"], "length": jnp.full((2,), 12, jnp.int32)}
    got, new = model.decode_step(w, cache, toks[:, 12:])
    want, full = model.prefill(w, toks)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new["lat"][:, :, :13], full["lat"],
                               rtol=1e-5, atol=1e-5)
    # every (token, held expert) pair of the 3 routed layers is counted
    load = np.asarray(new["moe_load"])
    assert 0 < load[0] <= 2 * 6 * 3 and 1 <= load[1] <= 2


def _ffn_params(cfg: dict, seed: int, e0: int, n: int):
    """One routed layer's weights, the held experts [e0, e0+n) sliced
    from the uncut layer's."""
    full = weights.make(_held(cfg, 0, cfg["deployment"]["experts"]), seed)
    ml = jax.tree.map(lambda a: a[0], full["layers"]["mlp"])
    for k in ("w_gate", "w_up", "w_down"):
        ml[k] = ml[k][e0:e0 + n]
    return ml


def test_expert_shares_sum_to_the_uncut_layer():
    """The four chips' routed parts, with the shared experts counted once,
    add up to the layer holding all 16 experts, which is the reference's
    uncut layer."""
    cfg = TINY
    x = jnp.asarray(np.random.default_rng(3).standard_normal((40, 64)),
                    jnp.float32)
    E = cfg["deployment"]["experts"]
    uncut_cfg = _held(cfg, 0, E)
    mcfg = arch.load(uncut_cfg).program(uncut_cfg, "t")
    mcfg = dataclasses.replace(mcfg, dtype="float32")
    full = _ffn_params(cfg, 6, 0, E)
    uncut, _, load = layers.apply_moe_share(full, x, mcfg)
    shared = layers.apply_mlp(full["shared"], x, mcfg)
    total, pairs = -3 * shared, 0
    for e0 in range(0, E, 4):
        share = dataclasses.replace(mcfg, moe=dataclasses.replace(
            mcfg.moe, held=(e0, 4)))
        y, _, ld = layers.apply_moe_share(_ffn_params(cfg, 6, e0, 4), x, share)
        total, pairs = total + y, pairs + int(ld[0])
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    assert pairs == int(load[0]) == 40 * cfg["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        want = ref.experts(full, x, uncut_cfg)
    np.testing.assert_allclose(uncut, want, rtol=RTOL, atol=ATOL)


def test_no_token_dropped_when_every_token_routes_alike():
    """A router that sends every token to experts 0-5 (all scores tie, and
    top-k takes the lowest) loads each held expert with every token; the
    share computes all of them, as the reference does, where the capacity
    dispatch (capacity factor 1.25: 16 rows an expert) drops half."""
    cfg = TINY
    mcfg = dataclasses.replace(arch.load(cfg).program(cfg, "t"), dtype="float32")
    ml = _ffn_params(cfg, 7, 0, 4)
    ml["router"] = jnp.zeros_like(ml["router"])
    x = jnp.asarray(np.random.default_rng(4).standard_normal((32, 64)),
                    jnp.float32)
    y, _, load = layers.apply_moe_share(ml, x, mcfg)
    assert list(np.asarray(load)) == [32 * 4, 32]
    with jax.default_matmul_precision("highest"):
        want = ref.experts(ml, x, cfg)
    np.testing.assert_allclose(y, want, rtol=RTOL, atol=ATOL)
    cap = dataclasses.replace(mcfg, moe=dataclasses.replace(mcfg.moe, held=None))
    _, aux = layers.apply_moe(
        dict(ml, w_gate=jnp.pad(ml["w_gate"], [(0, 12), (0, 0), (0, 0)]),
             w_up=jnp.pad(ml["w_up"], [(0, 12), (0, 0), (0, 0)]),
             w_down=jnp.pad(ml["w_down"], [(0, 12), (0, 0), (0, 0)])), x, cap)
    assert float(aux["moe_drop_frac"]) == 0.5


def test_slot_logits_do_not_depend_on_other_slots(tmp_path):
    """Slot 0 serves the same request beside two different pairs of
    neighbours: its prefill and decode logits are the same."""
    cfg = TINY
    w = weights.make(cfg, 8)
    rng = np.random.default_rng(5)
    p0 = rng.integers(0, 256, 17).astype(np.int32)
    got = []
    for seed in (6, 7):
        r = np.random.default_rng(seed)
        others = [r.integers(0, 256, n).astype(np.int32)
                  for n in r.integers(3, 40, 2)]
        eng = Engine(_program(cfg), w,
                     ServeConfig(batch=3, max_len=64, page_size=8, paged=True))
        eng._exec = _LogitTap(eng._exec)
        first, toks = _serve(eng, [p0, *others], 5)
        got.append((first[0], toks[0], np.stack([t[0] for t in eng._exec.logits])))
    assert got[0][0] == got[1][0]
    np.testing.assert_array_equal(got[0][1], got[1][1])
    # rows are computed independently; only the order in which XLA blocks
    # a batched matmul may differ
    np.testing.assert_allclose(got[0][2], got[1][2], rtol=1e-6, atol=1e-6)


def test_decode_span_carries_expert_load_and_latent_pages(tmp_path):
    cfg = TINY
    w = weights.make(cfg, 9)
    eng = Engine(_program(cfg), w,
                 ServeConfig(batch=2, max_len=64, page_size=8, paged=True))
    prompts = [np.arange(1, 20, dtype=np.int32), np.arange(5, 9, dtype=np.int32)]
    for slot, p in enumerate(prompts):
        eng.insert(eng.prefill(p), slot)
    obs.clear_trace()
    obs.enable_tracing()
    try:
        eng.generate_step(2)
    finally:
        obs.disable_tracing()
    (dec,) = [e for e in obs.tracer.events() if e["name"] == "serve.decode"]
    obs.clear_trace()
    args = dec["args"]
    k, routed = cfg["num_experts_per_tok"], cfg["num_hidden_layers"] - 1
    assert 0 < args["moe_pairs_here"] <= 2 * 2 * k * routed
    assert 1 <= args["moe_max_load"] <= 2
    # 19 + 4 tokens, then 20 + 5: pages of 8
    assert args["kv_latent_pages"] == (3 + 1) + (3 + 1)
    assert "kv_live_pages" not in args


def test_yarn_frequencies_match_transformers():
    tf = pytest.importorskip("transformers")
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    pub = dict(YARN, original_max_position_embeddings=4096)
    c = tf.DeepseekV2Config(hidden_size=2048, num_attention_heads=16,
                            qk_rope_head_dim=64, rope_theta=10000,
                            max_position_embeddings=163840, rope_scaling=pub)
    want, factor = _compute_yarn_parameters(c, "cpu")
    assert factor == 1.0                     # the cos/sin factor
    cfg = dict(TINY, qk_rope_head_dim=64, rope_scaling=pub)
    mcfg = arch.load(cfg).program(cfg, "t")
    got = layers.rope_inv_freq(64, 10000.0, mcfg.mla.yarn)
    np.testing.assert_allclose(got, want.numpy(), rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(cfg), want.numpy(), rtol=1e-6)
    # DeepSeek's softmax scale: 192^-1/2 * (0.1 * 0.707 * ln 40 + 1)^2
    assert ref.softmax_scale(dict(cfg, qk_nope_head_dim=128)) == pytest.approx(
        0.114721, abs=1e-6)
    assert layers.mla_scale(dataclasses.replace(
        mcfg, mla=dataclasses.replace(mcfg.mla, qk_nope_dim=128))) == \
        pytest.approx(0.114721, abs=1e-6)


@pytest.mark.parametrize("rope", ["off", "yarn"])
def test_attention_matches_transformers(rope):
    """The reference's and the program's latent attention against
    transformers' DeepseekV2Attention on the same weights.  With YaRN on,
    the port's scale is given DeepSeek's mscale^2 (the one departure, see
    ``reference/deepseek_v2.py``)."""
    tf = pytest.importorskip("transformers")
    import torch
    from transformers.models.deepseek_v2 import modeling_deepseek_v2 as hf

    cfg = dict(TINY, rope_scaling=YARN if rope == "yarn" else None)
    c = tf.DeepseekV2Config(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000,
        max_position_embeddings=163840, rms_norm_eps=1e-6,
        rope_scaling=dict(YARN) if rope == "yarn" else None,
        attn_implementation="eager")
    w = weights.make(cfg, 11)
    at = jax.tree.map(lambda a: np.asarray(a[0], np.float32),
                      w["layers"]["attn"])
    mod = hf.DeepseekV2Attention(c, layer_idx=0).eval()
    t = lambda a: torch.from_numpy(np.array(a))
    with torch.no_grad():
        mod.q_proj.weight.copy_(t(at["wq"].T))
        mod.kv_a_proj_with_mqa.weight.copy_(t(at["wkv_a"].T))
        mod.kv_a_layernorm.weight.copy_(t(at["kv_norm_g"]))
        mod.kv_b_proj.weight.copy_(t(at["wkv_b"].T))
        mod.o_proj.weight.copy_(t(at["wo"].T))
    mod.scaling = ref.softmax_scale(cfg)
    T = 20
    x = np.random.default_rng(12).standard_normal((T, 64)).astype(np.float32)
    pos = torch.arange(T)[None]
    rot = hf.DeepseekV2RotaryEmbedding(c)
    mask = torch.full((T, T), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want, _ = mod(t(x)[None], attention_mask=mask,
                      position_embeddings=rot(t(x)[None], pos),
                      position_ids=pos)
    want = want[0].numpy()
    with jax.default_matmul_precision("highest"):
        got_ref = ref.attention(at, jnp.asarray(x), cfg)
    np.testing.assert_allclose(got_ref, want, rtol=RTOL, atol=ATOL)
    mcfg = dataclasses.replace(arch.load(cfg).program(cfg, "t"), dtype="float32")
    got, _ = layers.apply_mla(at, jnp.asarray(x)[None], mcfg,
                              jnp.arange(T)[None])
    np.testing.assert_allclose(got[0], want, rtol=RTOL, atol=ATOL)


def test_loss_and_gradients_match_reference():
    """``backbone`` runs the dense layer, then the routed stack: the
    training loss and its gradients are the reference's."""
    cfg = TINY
    w = weights.make(cfg, 5)
    model = _program(cfg)
    rng = np.random.default_rng(13)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, 16)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    # the routers' load-balance term is the program's own, not the model's
    loss = lambda p: lm.train_forward(p, {"tokens": tokens, "labels": labels},
                                      model.cfg, aux_coef=0.0)[0]
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(loss)(w)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, labels, cfg))(w)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_latent_decode_path_follows_platform_and_pool(monkeypatch):
    """The paged latent kernel where Pallas compiles and the published
    widths tile its pages; the gather where Pallas interprets (the CPU),
    where the pages do not tile (the small config), and over the dense
    rectangle."""
    pub = json.loads((CHIP / "configs" / "deepseek-v2-lite-ep4.json")
                     .read_text())
    big = arch.load(pub).program(pub, "t")
    small = _program(TINY).cfg
    pool = lambda cfg, page: jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, 6145, page))
    dense = jax.eval_shape(lambda: lm.init_cache(big, 48, 2048))
    assert {k: v.shape for k, v in pool(big, 16).items()} == {
        "lat": (27, 6145, 16, 512), "rope": (27, 3073, 16, 128)}
    assert layers.attention_path(big, pool(big, 16)) == "mla-gather"
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    assert layers.attention_path(big, pool(big, 16)) == "mla-kernel"
    assert layers.attention_path(big, dense) == "mla-gather"
    assert layers.attention_path(small, pool(small, 8)) == "mla-gather"


def _slot_rows(kv: PagedKV, slot: int, n: int, rope: int) -> np.ndarray:
    """The first ``n`` latent rows (L, n, rank+rope) of ``slot``, read back
    from the pool's two leaves through the page table."""
    lat, pe = np.asarray(kv.pool["lat"]), np.asarray(kv.pool["rope"])
    pack, ps = pe.shape[-1] // rope, kv.page_size
    out = []
    for t in range(n):
        p, o = int(kv.table[slot, t // ps]), t % ps
        g = p % pack * rope
        out.append(np.concatenate([lat[:, p, o], pe[:, p // pack, o, g:g + rope]],
                                  axis=-1))
    return np.stack(out, axis=1)


def test_insert_and_prefix_tail_round_trip_the_latent_leaves():
    """A prefill's latent rows, spliced into private pages, or into shared
    full pages plus a copied tail page, read back from ``lat`` and the
    packed ``rope`` leaf as they were written; the other slot's pages are
    left as they were."""
    cfg = TINY
    model = _program(cfg)
    w = weights.make(cfg, 10)
    rope = cfg["qk_rope_head_dim"]
    kv = PagedKV(model, 3, 64, 8)
    toks = np.random.default_rng(14).integers(0, 256, (2, 21)).astype(np.int32)
    _, pre = model.prefill(w, jnp.asarray(toks), max_len=32)
    kv.allocator.alloc(5)                  # slot pages start mid-row
    kv.insert_kv(pre, 0, 21, 0)
    want0 = np.asarray(pre["lat"][:, 0, :21])
    np.testing.assert_array_equal(_slot_rows(kv, 0, 21, rope), want0)
    pages, tail = kv.materialize_prefix(pre, 1, 21)
    assert len(pages) == 2
    assert {k: v.shape for k, v in tail.items()} == {
        "lat": (4, 8, 32), "rope": (4, 8, rope)}
    kv.insert_shared(pages, tail, 21, 2)
    np.testing.assert_array_equal(_slot_rows(kv, 2, 21, rope),
                                  np.asarray(pre["lat"][:, 1, :21]))
    np.testing.assert_array_equal(_slot_rows(kv, 0, 21, rope), want0)


# sha256 (first 16 hex digits) of the lowered text of the dense family's
# paged decode, prefill and training steps at reduced size, as the program
# lowered them before the paged latent kernel: latent attention must leave
# the grouped-query path alone.  A change meant for the dense family
# updates these pins.
DENSE_HLO = {
    "qwen3_1_7b": {"decode": "3d6cb545b80b8fc6", "prefill": "79aeb514e6bfdab0",
                   "train": "f6d4f02270ece95c"},
    "qwen2_moe_a2_7b": {"decode": "e9b40a2d1a2ca9f3",
                        "prefill": "7c1dec06fdf47ef2",
                        "train": "10031b4b1ecfa103"},
}


@pytest.mark.parametrize("name", sorted(DENSE_HLO))
def test_dense_family_lowering_unchanged(name):
    model = build_model(get_reduced(name))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S, L, page = 2, 16, 32, 8
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    cache = dict(jax.eval_shape(
        lambda: model.init_paged_cache(B * L // page + 1, page)),
        page_table=jax.ShapeDtypeStruct((B, L // page), jnp.int32),
        length=jax.ShapeDtypeStruct((B,), jnp.int32))
    steps = {
        "decode": (lambda p, c, t: model.decode_step(p, c, t, kv_limit=L),
                   (params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32))),
        "prefill": (lambda p, t, n: model.prefill(p, t, max_len=L, true_len=n),
                    (params, tok, jax.ShapeDtypeStruct((B,), jnp.int32))),
        "train": (jax.grad(lambda p, b: model.train_forward(p, b)[0]),
                  (params, {"tokens": tok, "labels": tok})),
    }
    got = {k: hashlib.sha256(jax.jit(f).lower(*a).as_text().encode())
           .hexdigest()[:16] for k, (f, a) in steps.items()}
    assert got == DENSE_HLO[name]
