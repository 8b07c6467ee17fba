"""Seeded random weights, made by the benchmark and handed to both the
system under test and the plain reference.

One jitted call builds every leaf on the device, in the dtype the
configuration serves (``serve_dtype``).  The layout is a plain nested dict
with the layers stacked on a leading axis:

    embed (V, D); final_norm.g (D,); lm_head (D, V) unless tied;
    layers.norm1.g / norm2.g (L, D);
    layers.attn.wq (L, D, Hq*dh), wk / wv (L, D, Hkv*dh), wo (L, Hq*dh, D),
        q_norm_g / k_norm_g (L, dh) with qk_norm;
    layers.mlp.w_gate / w_up (L, D, F), w_down (L, F, D).

Matrices are normal with standard deviation 1/sqrt(fan_in) (the embedding
0.02, as the published initializers use); norm gains are 1 + 0.1 * normal,
so that a gain that is skipped or misplaced shows in the logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    """The sizes the benchmark reads from a configuration file."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {"D": d, "F": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "Hq": hq, "Hkv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // hq, "V": cfg["vocab_size"],
            "tied": bool(cfg["tie_word_embeddings"]),
            "qk_norm": bool(cfg.get("qk_norm", False))}


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, init kind) in the layout above."""
    s = dims(cfg)
    D, F, L, Hq, Hkv, dh, V = (s[k] for k in ("D", "F", "L", "Hq", "Hkv", "dh", "V"))
    out = {
        "embed": ((V, D), ("normal", 0.02)),
        "final_norm.g": ((D,), ("gain",)),
        "layers.norm1.g": ((L, D), ("gain",)),
        "layers.norm2.g": ((L, D), ("gain",)),
        "layers.attn.wq": ((L, D, Hq * dh), ("normal", 1 / math.sqrt(D))),
        "layers.attn.wk": ((L, D, Hkv * dh), ("normal", 1 / math.sqrt(D))),
        "layers.attn.wv": ((L, D, Hkv * dh), ("normal", 1 / math.sqrt(D))),
        "layers.attn.wo": ((L, Hq * dh, D), ("normal", 1 / math.sqrt(Hq * dh))),
        "layers.mlp.w_gate": ((L, D, F), ("normal", 1 / math.sqrt(D))),
        "layers.mlp.w_up": ((L, D, F), ("normal", 1 / math.sqrt(D))),
        "layers.mlp.w_down": ((L, F, D), ("normal", 1 / math.sqrt(F))),
    }
    if s["qk_norm"]:
        out["layers.attn.q_norm_g"] = ((L, dh), ("gain",))
        out["layers.attn.k_norm_g"] = ((L, dh), ("gain",))
    if not s["tied"]:
        out["lm_head"] = ((D, V), ("normal", 1 / math.sqrt(D)))
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, val in flat.items():
        *path, leaf = name.split(".")
        node = tree
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = val
    return tree


def make(cfg: dict, seed: int, dtype=None):
    """All weights for ``cfg`` from ``seed``, in one jitted call on the
    default device."""
    dtype = jnp.dtype(dtype or cfg["serve_dtype"])
    spec = shapes(cfg)

    def build(key):
        flat = {}
        for i, (name, (shape, kind)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if kind[0] == "gain":
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = kind[1] * jax.random.normal(k, shape, jnp.float32)
            flat[name] = x.astype(dtype)
        return _nest(flat)

    return jax.jit(build)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any integer below 2**64.  ``jax.random.key`` keeps
    only the low 32 bits without 64-bit mode, so the high word is folded
    in: seeds that differ above bit 31 still give different keys."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed >> 32)
