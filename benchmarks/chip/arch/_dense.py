"""The dense decoder (Qwen3, Phi-3): grouped-query attention with rotary
embeddings, optional per-head q/k RMSNorm, a SwiGLU MLP and an LM head
that may be tied to the embedding.  Its reference is
``reference/decoder.py``.

Weights layout, a plain nested dict with the layers stacked on a leading
axis:

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

from arch import ProgramFault
from reference.decoder import dims, logits_at, loss  # noqa: F401


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


def program(cfg: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    if cfg["hidden_act"] != "silu":
        raise ProgramFault(f"hidden_act {cfg['hidden_act']!r}: only SwiGLU")
    # the program's RMSNorm has no eps argument and fixes it at 1e-6; a
    # configuration that states another eps (phi3: 1e-5) is compared, with
    # its own eps, by the reference
    s = dims(cfg)
    return ModelConfig(
        name=name, family="dense", n_layers=s["L"], d_model=s["D"],
        n_heads=s["Hq"], n_kv_heads=s["Hkv"], d_ff=s["F"], vocab=s["V"],
        head_dim=s["dh"], act="swiglu", norm="rms", qk_norm=s["qk_norm"],
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=s["tied"],
        dtype="bfloat16", param_dtype=cfg["serve_dtype"])


def check_traffic(cfg: dict, mix: dict, max_len: int) -> None:
    """A model whose attention has a sliding window runs here only where
    no position reaches past it, since the program attends to all."""
    longest = mix["prompt"]["max"] + mix["output"]["max"] - 1
    if longest > max_len:
        raise ValueError(f"prompt + output up to {longest} > max_len {max_len}")
    win = cfg.get("sliding_window")
    if win and longest > win:
        raise ValueError(f"contexts up to {longest} positions pass the "
                         f"sliding window {win}, which the program lacks")


def matmul_params_per_token(cfg: dict) -> int:
    """q, k, v, o and the three MLP matrices of every layer, and the LM
    head.  Attention over the context is not counted."""
    s = dims(cfg)
    D, F, L, Hq, Hkv, dh, V = (s[k] for k in ("D", "F", "L", "Hq", "Hkv", "dh", "V"))
    per_layer = D * (Hq + 2 * Hkv) * dh + Hq * dh * D + 3 * D * F
    return L * per_layer + D * V
