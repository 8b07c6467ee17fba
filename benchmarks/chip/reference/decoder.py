"""Plain float32 dense decoder: the reference every serving cell is judged
against.

Straight ``jax.numpy`` from the published equations of the Qwen3 and Phi-3
decoders (Hugging Face ``modeling_qwen3`` / ``modeling_phi3``): token
embedding; per layer RMSNorm -> grouped-query attention with rotary
embeddings (rotate-half convention, optional per-head RMSNorm on q and k,
optional sliding window) -> residual -> RMSNorm -> SwiGLU MLP -> residual;
final RMSNorm; LM head (the embedding's transpose when tied).  No cache, no
batching, no kernels: one sequence at a time, full causal attention over
it, every matmul at ``precision="highest"``.

Departures from the published models: Phi-3 fuses q/k/v into one
``qkv_proj`` and gate/up into one ``gate_up_proj``; these are kept as
separate matrices, which is the same arithmetic.  Weights are whatever
``weights.make`` built from the seed, never a checkpoint.

``quant="fp8"`` is the control: every matrix multiplied by (q, k, v, o,
gate, up, down, head) is rounded to float8 e4m3 with one scale per output
channel before the float32 arithmetic.  It is the next precision below the
bfloat16 the cells serve in.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

# sequences are padded to a multiple of this, so that a handful of shapes
# compile once and come back from the persistent cache in later runs
PAD = 256


def dims(cfg: dict) -> dict:
    """The sizes the benchmark reads from a configuration file."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {"D": d, "F": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "Hq": hq, "Hkv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // hq, "V": cfg["vocab_size"],
            "tied": bool(cfg["tie_word_embeddings"]),
            "qk_norm": bool(cfg.get("qk_norm", False))}


def _fp8(w, axis):
    """Round ``w`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis is reduced over)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (T, H, dh); rotate-half convention."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _hidden(w, tokens, cfg: dict, quant: str | None):
    """tokens (T,) int32 -> final-normed hidden states (T, D) float32."""
    s = dims(cfg)
    Hq, Hkv, dh = s["Hq"], s["Hkv"], s["dh"]
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    window = cfg.get("sliding_window")
    T = tokens.shape[0]
    pos = jnp.arange(T)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask = mask & (pos[:, None] - pos[None, :] < window)
    f32 = lambda a: a.astype(jnp.float32)
    mat = (lambda a: _fp8(f32(a), 0)) if quant == "fp8" else f32
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown quant {quant!r}")

    def layer(h, lw):
        a = _rms(h, f32(lw["norm1"]["g"]), eps)
        at = lw["attn"]
        q = (a @ mat(at["wq"])).reshape(T, Hq, dh)
        k = (a @ mat(at["wk"])).reshape(T, Hkv, dh)
        v = (a @ mat(at["wv"])).reshape(T, Hkv, dh)
        if s["qk_norm"]:
            q = _rms(q, f32(at["q_norm_g"]), eps)
            k = _rms(k, f32(at["k_norm_g"]), eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        g = Hq // Hkv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
        sc = jnp.where(mask[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)
        h = h + o.reshape(T, Hq * dh) @ mat(at["wo"])
        m = _rms(h, f32(lw["norm2"]["g"]), eps)
        ml = lw["mlp"]
        u = jax.nn.silu(m @ mat(ml["w_gate"])) * (m @ mat(ml["w_up"]))
        return h + u @ mat(ml["w_down"]), None

    h = f32(w["embed"])[tokens]
    h, _ = jax.lax.scan(layer, h, w["layers"])
    return _rms(h, f32(w["final_norm"]["g"]), eps)


def _head(w, cfg: dict, quant: str | None):
    """(D, V) float32 LM head."""
    head = w["embed"].T if cfg["tie_word_embeddings"] else w["lm_head"]
    head = head.astype(jnp.float32)
    return _fp8(head, 0) if quant == "fp8" else head


def loss(w, tokens, labels, cfg: dict):
    """Mean next-token cross-entropy of a batch of sequences (B, T) in
    float32 at ``precision="highest"``; ``jax.grad`` of it is the reference
    gradient."""
    def one(t, y):
        logits = _hidden(w, t, cfg, None) @ _head(w, cfg, None)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])

    with jax.default_matmul_precision("highest"):
        return jnp.mean(jax.vmap(one)(tokens, labels))


_JIT: dict = {}


def _compiled(cfg: dict, quant: str | None):
    key = (json.dumps(cfg, sort_keys=True), quant)
    fn = _JIT.get(key)
    if fn is None:
        def run(w, tokens, rows):
            with jax.default_matmul_precision("highest"):
                h = _hidden(w, tokens, cfg, quant)
                return h[rows] @ _head(w, cfg, quant)
        fn = jax.jit(run)
        _JIT[key] = fn
    return fn


def logits_at(w, tokens, rows, cfg: dict, quant: str | None = None):
    """Float32 logits (len(rows), V) at positions ``rows`` of the sequence
    ``tokens`` (1-D ints): row r predicts token r + 1."""
    import numpy as np

    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    T = -(-len(tokens) // PAD) * PAD
    R = -(-len(rows) // PAD) * PAD
    tpad = np.zeros(T, np.int32)
    tpad[:len(tokens)] = tokens
    rpad = np.zeros(R, np.int32)
    rpad[:len(rows)] = rows
    out = _compiled(cfg, quant)(w, jnp.asarray(tpad), jnp.asarray(rpad))
    return out[:len(rows)]
