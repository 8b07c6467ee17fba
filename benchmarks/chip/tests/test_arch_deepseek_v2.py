"""The ``deepseek_v2`` architecture module: its weights layout, reference
and FLOP count are pinned, it maps what the program cannot honour to
``ProgramFault``, and a whole harness run of a small DeepSeek-V2 on the CPU
is ``correct``, and not once its latent pool stops being written.

The pin (``fixtures/deepseek_v2_pin.json``) holds the leaf table of the
published configuration and, at the small configuration below, checksums
of the weights, of the reference logits, of the fp8 control's logits and
the bits of the reference loss, recorded when the module was added.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import arch  # noqa: E402
import harness  # noqa: E402
import weights  # noqa: E402

PIN = json.loads((HERE / "tests" / "fixtures" / "deepseek_v2_pin.json").read_text())
PUBLISHED = "deepseek-v2-lite-ep4"
# q, kv_a, kv_b, o of 27 layers; the dense MLP; router, shared experts and
# 1.5 held experts of 26 layers; the head
MATMUL_PARAMS = 1_439_170_560
SMALL = {"model_type": "deepseek_v2", "hidden_size": 64,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
         "num_experts_per_tok": 6, "n_shared_experts": 2,
         "norm_topk_prob": False, "routed_scaling_factor": 1.0,
         "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": False,
         "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
         "max_position_embeddings": 163840,
         "rope_scaling": {"type": "yarn", "factor": 40,
                          "original_max_position_embeddings": 64,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                          "mscale_all_dim": 0.707},
         "deployment": {"experts": 16, "experts_held": [0, 4]},
         "serve_dtype": "bfloat16"}


def _published() -> dict:
    return json.loads((HERE / "configs" / f"{PUBLISHED}.json").read_text())


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


def test_leaf_table_matches_pin():
    cfg = _published()
    got = [[k, list(s), list(kind)]
           for k, (s, kind) in sorted(arch.load(cfg).shapes(cfg).items())]
    assert got == PIN["leaf_table"]
    n = sum(int(np.prod(s)) for _, s, _ in got)
    assert n == 4_910_345_728             # 9.82 GB in bfloat16


def test_weights_and_reference_match_pin():
    cfg = dict(SMALL, serve_dtype="float32")
    mod = arch.load(cfg)
    w = weights.make(cfg, 2 ** 31 + 5)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(w):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    pin = PIN["small"]
    assert h.hexdigest() == pin["weights_sha256"]
    seq = np.random.default_rng(4).integers(0, cfg["vocab_size"], 40).astype(np.int32)
    rows = np.arange(3, 40, 3)
    assert _sha(mod.logits_at(w, seq, rows, cfg)) == pin["logits_sha256"]
    assert _sha(mod.logits_at(w, seq, rows, cfg, "fp8")) == pin["fp8_logits_sha256"]
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg["vocab_size"], (2, 24)), jnp.int32)
    loss = np.asarray(mod.loss(w, toks, jnp.roll(toks, -1, axis=1), cfg))
    assert loss.astype(np.float32).tobytes().hex() == pin["loss_float32_hex"]


def test_mfu_counts_matmul_params():
    cfg = _published()
    assert arch.load(cfg).matmul_params_per_token(cfg) == MATMUL_PARAMS
    win = SimpleNamespace(cfg=cfg, seconds=2.0, t_open=10.0, t_close=12.0,
                          served=[SimpleNamespace(times=[10.5, 11.0, 13.0])],
                          peaks={"bf16_flops_per_s": 1e14})
    got = harness._reader("mfu.decode").read(win)
    assert got == pytest.approx(100.0 * 2 * MATMUL_PARAMS * 2 / 2.0 / 1e14,
                                rel=1e-12)


def test_program_maps_the_published_configuration():
    cfg = _published()
    m = arch.load(cfg).program(cfg, PUBLISHED)
    assert (m.n_layers, m.n_dense_layers, m.d_model, m.d_ff) == (27, 1, 2048, 10944)
    assert (m.mla.kv_rank, m.mla.qk_nope_dim, m.mla.qk_rope_dim,
            m.mla.v_dim) == (512, 128, 64, 128)
    assert (m.moe.n_experts, m.moe.held, m.moe.top_k, m.moe.d_expert,
            m.moe.d_shared, m.moe.renormalize) == (64, (0, 16), 6, 1408,
                                                   2816, False)
    assert m.mla.yarn.factor == 40 and m.mla.yarn.original_max == 4096


@pytest.mark.parametrize("change", [{"q_lora_rank": 1536},
                                    {"topk_method": "group_limited_greedy"},
                                    {"n_routed_experts": 64},
                                    {"rms_norm_eps": 1e-5}],
                         ids=lambda c: next(iter(c)))
def test_what_the_program_lacks_is_a_program_fault(change):
    cfg = dict(_published(), **change)
    with pytest.raises(arch.ProgramFault):
        arch.load(cfg).program(cfg, PUBLISHED)


MIX = {"loop": "closed", "requests": 4000,
       "prompt": {"dist": "uniform", "min": 30, "max": 60},
       "output": {"dist": "uniform", "min": 8, "max": 30}}
# sound runs of this size read widest gaps of a few hundredths (bfloat16
# serving against the float32 reference); a pool that is never written
# reads 1 or more
GAP_LIMIT = 0.15


def _run(fault=None) -> dict:
    wl = {"config": "small", "traffic": "closed", "chips": 1,
          "engine": {"stitch": True, "slots": 4, "max_len": 96,
                     "page_size": 16, "plan_budget": 10,
                     "stitch_cache_dir": str(HERE / ".cache" / "stitch-test")},
          "check": {"sample_requests": 6, "sample_tokens": 60,
                    "gap_limit": GAP_LIMIT, "drain_s": 0},
          "end_to_end": ["itl_p95_ms", "out_tok_s", "setup_s"]}
    return harness.run_cell(
        "small.closed", wl, SMALL, MIX,
        {"workloads": [], "end_to_end": [], "per_layer": []}, 2 ** 31 + 17,
        2.0, False, faults=fault,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def latent_pool_unchanged(eng):
    """The decode step hands back the latent pool it was given."""
    inner = eng._exec

    def step(params, cache, tok, extra):
        logits, new = inner(params, cache, tok, extra)
        return logits, {**new, "lat": cache["lat"]}

    eng._exec = step


def test_harness_run_is_correct_and_a_stale_pool_is_not():
    out = _run()
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["run"]["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "out_tok_s", "setup_s"}
    bad = _run(latent_pool_unchanged)
    assert not bad["correct"]
    assert bad["compared"]["widest_logit_gap"]["value"] > 5 * GAP_LIMIT
