"""The plain reference against the program, on the CPU at a small size.

Both run in float32 on the same seeded weights, so they agree to float32
rounding: prefill logits, decode logits after decoding through the paged
cache that ``Engine.insert`` filled, the training loss and its gradients.
One configuration has Qwen3's shape (qk_norm, grouped KV heads, tied head),
the other Phi-3's (head_dim 96, as many KV heads as query heads, untied
head, sliding window).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import serving  # noqa: E402
import weights  # noqa: E402
from reference import decoder  # noqa: E402

QWEN3_LIKE = {"hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
              "vocab_size": 300, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
              "tie_word_embeddings": True, "hidden_act": "silu", "qk_norm": True,
              "serve_dtype": "float32"}
PHI3_LIKE = {"hidden_size": 192, "intermediate_size": 256, "num_hidden_layers": 2,
             "num_attention_heads": 2, "num_key_value_heads": 2,
             "vocab_size": 320, "rms_norm_eps": 1e-6, "rope_theta": 1e4,
             "sliding_window": 2047, "tie_word_embeddings": False,
             "hidden_act": "silu", "serve_dtype": "float32"}
CONFIGS = {"qwen3-like": QWEN3_LIKE, "phi3-like": PHI3_LIKE}
# float32 on both sides; the two sum in different orders
RTOL, ATOL = 2e-4, 2e-4


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


def _program(cfg):
    from repro.models import build_model

    mcfg = dataclasses.replace(serving.model_config(cfg, "t"), dtype="float32")
    return build_model(mcfg)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_prefill_and_paged_decode_match_reference(name):
    from repro.serve import Engine, ServeConfig

    cfg = CONFIGS[name]
    w = weights.make(cfg, 3)
    model = _program(cfg)
    eng = Engine(model, w, ServeConfig(batch=2, max_len=64, page_size=16,
                                       paged=True))
    eng._exec, eng._prefill_exec = _LogitTap(eng._exec), _LogitTap(eng._prefill_exec)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (21, 9)]
    first = []
    for slot, p in enumerate(prompts):
        px = eng.prefill(p)
        ref = decoder.logits_at(w, p, [len(p) - 1], cfg)
        np.testing.assert_allclose(eng._prefill_exec.logits[-1], ref,
                                   rtol=RTOL, atol=ATOL)
        eng.insert(px, slot)
        first.append(int(px.first_tokens[0]))
    toks = eng.generate_step(3)
    for slot, p in enumerate(prompts):
        seq = np.concatenate([p, [first[slot]], toks[slot, :2]]).astype(np.int32)
        ref = decoder.logits_at(w, seq, len(p) + np.arange(3), cfg)
        got = np.stack([t[slot] for t in eng._exec.logits])
        np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_loss_and_gradients_match_reference(name):
    cfg = CONFIGS[name]
    w = weights.make(cfg, 5)
    model = _program(cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, 32)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    batch = {"tokens": tokens, "labels": labels}
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda p: model.train_forward(p, batch)[0])(w)
    want, g_want = jax.value_and_grad(
        lambda p: decoder.loss(p, tokens, labels, cfg))(w)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_fp8_control_departs_from_reference():
    """The control reads a different greedy token somewhere and moves the
    logits by far more than float32 rounding."""
    cfg = QWEN3_LIKE
    w = weights.make(cfg, 9)
    seq = np.random.default_rng(2).integers(0, cfg["vocab_size"], 40)
    rows = np.arange(40)
    ref = np.asarray(decoder.logits_at(w, seq, rows, cfg))
    low = np.asarray(decoder.logits_at(w, seq, rows, cfg, "fp8"))
    assert np.abs(low - ref).max() > 100 * ATOL
