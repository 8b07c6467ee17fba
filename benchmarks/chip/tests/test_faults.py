"""A whole harness run at a small size on the CPU, past the look for a
chip: sound, ``correct`` is true; with the timed path broken underneath,
``correct`` comes out false.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "tie_word_embeddings": True, "hidden_act": "silu", "qk_norm": True,
        "serve_dtype": "bfloat16"}
# sound runs of this size read widest gaps near 1e-3 (bfloat16 compute
# against the float32 reference); every fault below reads 0.1 or more
GAP_LIMIT = 0.02
MIXES = {
    "open": {"loop": "open", "rate_per_s": 6.0,
             "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                        "min": 8, "max": 64},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                        "min": 4, "max": 24}},
    "closed": {"loop": "closed", "requests": 4000,
               "prompt": {"dist": "uniform", "min": 30, "max": 60},
               "output": {"dist": "uniform", "min": 8, "max": 30}},
}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BENCH = {"workloads": [], "end_to_end": [], "per_layer": []}


def _run(loop: str, stitch: bool, fault=None, seconds: float = 2.0) -> dict:
    wl = {"config": "tiny", "traffic": loop, "chips": 1,
          "engine": {"stitch": stitch, "slots": 4, "max_len": 96,
                     "page_size": 16, "plan_budget": 10,
                     "stitch_cache_dir": str(HERE / ".cache" / "stitch-test")},
          "check": {"sample_requests": 6, "sample_tokens": 60,
                    "gap_limit": GAP_LIMIT, "drain_s": 30},
          "end_to_end": ["itl_p95_ms", "out_tok_s", "setup_s"]}
    return harness.run_cell(f"tiny.{loop}", wl, TINY, MIXES[loop], BENCH,
                            2 ** 31 + 17, seconds, False, faults=fault,
                            peaks=PEAKS)


def _wrap(eng, attr, fn):
    inner = getattr(eng, attr)
    calls = [0]

    def wrapped(*args, **kwargs):
        calls[0] += 1
        return fn(calls[0], inner, *args, **kwargs)

    setattr(eng, attr, wrapped)


def token_altered_in_decode(eng):
    """From the third decode step on, every slot's logits are rolled by one
    over the vocabulary: each decode step emits another token."""
    import jax.numpy as jnp

    def fn(n, inner, *a):
        logits, cache = inner(*a)
        return (jnp.roll(logits, 1, axis=-1) if n >= 3 else logits), cache

    _wrap(eng, "_exec", fn)


def token_altered_in_prefill(eng):
    """Every prefill emits the token after its greedy one."""
    import jax.numpy as jnp

    def fn(n, inner, *a):
        logits, cache = inner(*a)
        return jnp.roll(logits, 1, axis=-1), cache

    _wrap(eng, "_prefill_exec", fn)


def state_unchanged_in_decode(eng):
    """The decode step hands back the KV pool it was given: no token's keys
    and values are ever written to the cache."""
    def fn(n, inner, params, cache, tok, extra):
        logits, new = inner(params, cache, tok, extra)
        return logits, {**new, "k": cache["k"], "v": cache["v"]}

    _wrap(eng, "_exec", fn)


@pytest.mark.parametrize("loop", sorted(MIXES))
def test_sound_run_is_correct(loop):
    out = _run(loop, stitch=loop == "closed")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["run"]["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "out_tok_s", "setup_s"}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", [token_altered_in_decode,
                                   token_altered_in_prefill,
                                   state_unchanged_in_decode],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(fault):
    out = _run("open", stitch=False, fault=fault)
    assert not out["correct"], out["compared"]
    assert out["compared"]["widest_logit_gap"]["value"] > 5 * GAP_LIMIT
