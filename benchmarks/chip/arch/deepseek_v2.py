"""DeepSeek-V2 (``model_type`` "deepseek_v2"; huggingface.co/deepseek-ai/
DeepSeek-V2-Lite): multi-head latent attention in every layer, leading
dense SwiGLU layers, then DeepSeekMoE layers of routed and shared experts,
served as one chip's share of an expert-parallel deployment
(``deployment``: the router's full width ``experts`` and the routed
experts ``experts_held`` = [first, end) on this chip).  Its reference is
``reference/deepseek_v2.py``.

Weights layout, a plain nested dict with each stack's layers on a leading
axis (Ld = first_k_dense_replace, Lm = the other layers, n = experts held,
E = the router's width):

    embed (V, D); final_norm.g (D,); lm_head (D, V);
    <stack>.norm1.g / norm2.g (L, D) for stack dense_layers (Ld) and
        layers (Lm);
    <stack>.attn.wq (L, D, H*(nope+rope)), wkv_a (L, D, rank+rope),
        kv_norm_g (L, rank), wkv_b (L, rank, H*(nope+v)), wo (L, H*v, D);
    dense_layers.mlp.w_gate / w_up (Ld, D, F), w_down (Ld, F, D);
    layers.mlp.router (Lm, D, E);
    layers.mlp.w_gate / w_up (Lm, n, D, Fe), w_down (Lm, n, Fe, D);
    layers.mlp.shared.w_gate / w_up (Lm, D, Fs), w_down (Lm, Fs, D).

Matrices are normal with standard deviation 1/sqrt(fan_in) (the embedding
0.02); norm gains are 1 + 0.1 * normal.
"""

from __future__ import annotations

import math

from arch import ProgramFault
from reference.deepseek_v2 import dims, logits_at, loss  # noqa: F401


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, init kind) in the layout above."""
    s = dims(cfg)
    D, V, H, r, dn, dr, dv = (s[k] for k in ("D", "V", "H", "r", "dn", "dr", "dv"))
    F, Fe, Fs, E, n = (s[k] for k in ("F", "Fe", "Fs", "E", "n"))
    nrm = lambda fan_in: ("normal", 1 / math.sqrt(fan_in))
    out = {
        "embed": ((V, D), ("normal", 0.02)),
        "final_norm.g": ((D,), ("gain",)),
        "lm_head": ((D, V), nrm(D)),
    }
    stacks = {"dense_layers": s["Ld"], "layers": s["L"] - s["Ld"]}
    for st, L in stacks.items():
        if not L:
            continue
        out.update({
            f"{st}.norm1.g": ((L, D), ("gain",)),
            f"{st}.norm2.g": ((L, D), ("gain",)),
            f"{st}.attn.wq": ((L, D, H * (dn + dr)), nrm(D)),
            f"{st}.attn.wkv_a": ((L, D, r + dr), nrm(D)),
            f"{st}.attn.kv_norm_g": ((L, r), ("gain",)),
            f"{st}.attn.wkv_b": ((L, r, H * (dn + dv)), nrm(r)),
            f"{st}.attn.wo": ((L, H * dv, D), nrm(H * dv)),
        })
    Ld, Lm = stacks["dense_layers"], stacks["layers"]
    if Ld:
        out.update({
            "dense_layers.mlp.w_gate": ((Ld, D, F), nrm(D)),
            "dense_layers.mlp.w_up": ((Ld, D, F), nrm(D)),
            "dense_layers.mlp.w_down": ((Ld, F, D), nrm(F)),
        })
    out.update({
        "layers.mlp.router": ((Lm, D, E), nrm(D)),
        "layers.mlp.w_gate": ((Lm, n, D, Fe), nrm(D)),
        "layers.mlp.w_up": ((Lm, n, D, Fe), nrm(D)),
        "layers.mlp.w_down": ((Lm, n, Fe, D), nrm(Fe)),
        "layers.mlp.shared.w_gate": ((Lm, D, Fs), nrm(D)),
        "layers.mlp.shared.w_up": ((Lm, D, Fs), nrm(D)),
        "layers.mlp.shared.w_down": ((Lm, Fs, D), nrm(Fs)),
    })
    return out


def program(cfg: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file; the keys it
    cannot honour raise ``ProgramFault``."""
    try:
        from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                         YarnConfig)
    except ImportError as e:
        raise ProgramFault(f"the program has no latent attention: {e}") from e
    need = {"hidden_act": "silu", "q_lora_rank": None, "topk_method": "greedy",
            "scoring_func": "softmax", "n_group": 1, "topk_group": 1,
            "moe_layer_freq": 1, "attention_bias": False,
            "tie_word_embeddings": False, "rms_norm_eps": 1e-6}
    for key, want in need.items():
        if cfg.get(key, want) != want:
            raise ProgramFault(f"{key} {cfg[key]!r}: the program takes {want!r}")
    s = dims(cfg)
    if cfg["n_routed_experts"] != s["n"]:
        raise ProgramFault(f"n_routed_experts {cfg['n_routed_experts']} is not "
                           f"the share experts_held gives ({s['n']})")
    if cfg["num_key_value_heads"] != s["H"]:
        raise ProgramFault("latent attention has one key per head")
    rs = cfg.get("rope_scaling")
    yarn = None
    if rs:
        if rs.get("type", rs.get("rope_type")) != "yarn":
            raise ProgramFault(f"rope_scaling {rs!r}: only YaRN")
        if rs.get("mscale", 1) != rs.get("mscale_all_dim", 0):
            raise ProgramFault("YaRN's cos/sin factor is not 1: the program "
                               "takes mscale == mscale_all_dim")
        yarn = YarnConfig(
            factor=float(rs["factor"]),
            original_max=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0)))
    return ModelConfig(
        name=name, family="moe", n_layers=s["L"], d_model=s["D"],
        n_heads=s["H"], n_kv_heads=s["H"], d_ff=s["F"], vocab=s["V"],
        head_dim=s["dn"] + s["dr"], act="swiglu", norm="rms",
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=False,
        n_dense_layers=s["Ld"],
        mla=MLAConfig(kv_rank=s["r"], qk_nope_dim=s["dn"], qk_rope_dim=s["dr"],
                      v_dim=s["dv"], yarn=yarn),
        moe=MoEConfig(n_experts=s["E"], top_k=s["k"], d_expert=s["Fe"],
                      n_shared=cfg["n_shared_experts"], d_shared=s["Fs"],
                      renormalize=bool(cfg["norm_topk_prob"]),
                      held=(s["e0"], s["n"]),
                      routed_scale=float(cfg["routed_scaling_factor"])),
        dtype="bfloat16", param_dtype=cfg["serve_dtype"])


def check_traffic(cfg: dict, mix: dict, max_len: int) -> None:
    """Runs before the weights are made, so a program that cannot take the
    configuration fails here, with ``ProgramFault``, before any device
    work."""
    program(cfg, "check")
    longest = mix["prompt"]["max"] + mix["output"]["max"] - 1
    if longest > max_len:
        raise ValueError(f"prompt + output up to {longest} > max_len {max_len}")


def matmul_params_per_token(cfg: dict) -> int:
    """What this chip multiplies one token by: per layer q, kv_a, kv_b (the
    absorbed W_UK and W_UV at decode are the same parameters) and o; the
    dense layers' MLP; per routed layer the router, the shared experts and
    the held experts at their expected count here, k * n / E experts of
    the token's k (1.5 of 6 for 16 of 64), as if routing were uniform; and
    the LM head.  Attention over the context is not counted."""
    s = dims(cfg)
    D, H, r, dn, dr, dv = (s[k] for k in ("D", "H", "r", "dn", "dr", "dv"))
    attn = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    routed = 3 * D * s["Fe"] * s["k"] * s["n"] // s["E"]
    moe = D * s["E"] + 3 * D * s["Fs"] + routed
    return (s["L"] * attn + s["Ld"] * 3 * D * s["F"]
            + (s["L"] - s["Ld"]) * moe + D * s["V"])
