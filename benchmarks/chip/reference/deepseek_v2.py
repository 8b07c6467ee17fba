"""Plain float32 DeepSeek-V2: the reference a ``deepseek_v2`` serving cell
is judged against.

Straight ``jax.numpy`` from the published equations (arXiv:2405.04434;
DeepSeek's ``modeling_deepseek.py`` and transformers'
``modeling_deepseek_v2``): token embedding; per layer RMSNorm -> multi-head
latent attention -> residual -> RMSNorm -> MLP -> residual; final RMSNorm;
untied LM head.  No cache, no batching, no kernels: one sequence at a
time, every matmul at ``precision="highest"``.

Attention is the expanded form over the whole sequence: q = x Wq split
into q_nope and q_pe; [c, k_pe] = x Wkv_a, c RMS-normed; per head
[k_nope, v] = c Wkv_b; k = [k_nope, rope(k_pe)] with k_pe shared by every
head; softmax(q k / scale) v, then Wo.  RoPE rotates adjacent pairs of the
rope part (DeepSeek's complex-number form), at YaRN's frequencies when the
configuration has ``rope_scaling``; the cos/sin factor
mscale(factor, mscale) / mscale(factor, mscale_all_dim) is 1 for every
published DeepSeek-V2 and is applied as such.  The softmax scale is
(nope + rope)^-1/2 times mscale(factor, mscale_all_dim)^2, as DeepSeek's
own code sets it; transformers' port leaves the square out, so the two
agree only with ``rope_scaling`` off.

The first ``first_k_dense_replace`` layers have a SwiGLU MLP of width
``intermediate_size``.  The others route: a float32 softmax over all
``deployment["experts"]`` router outputs, greedy top ``num_experts_per_tok``
over all of them, renormalized only with ``norm_topk_prob``, times
``routed_scaling_factor``.  Of the routed experts only the chip's share
``deployment["experts_held"]`` = [first, end) exists; every token's gate on
each held expert weights that expert's SwiGLU output (a gate is 0 where the
expert is not among the token's top k), so what the absent experts would
add is left out, as on the chip.  The shared experts (one SwiGLU of width
``n_shared_experts * moe_intermediate_size``) run on every token.

Weights are the program's layout (``arch/deepseek_v2.py``), whatever
``weights.make`` built from the seed, never a checkpoint.

``quant="fp8"`` is the control: every matrix multiplied by (q, kv_a, kv_b,
o, the MLPs, the experts, the router, the head) is rounded to float8 e4m3
with one scale per output channel before the float32 arithmetic.  It is
the next precision below the bfloat16 the cell serves in.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp

# sequences are padded to a multiple of this, so that a handful of shapes
# compile once and come back from the persistent cache in later runs
PAD = 256


def dims(cfg: dict) -> dict:
    """The sizes the benchmark reads from a configuration file."""
    held = cfg["deployment"]["experts_held"]
    return {"D": cfg["hidden_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "Ld": cfg["first_k_dense_replace"],
            "H": cfg["num_attention_heads"], "r": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["deployment"]["experts"], "e0": held[0],
            "n": held[1] - held[0], "k": cfg["num_experts_per_tok"]}


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(cfg: dict) -> jnp.ndarray:
    """Rotary inverse frequencies of the rope part, YaRN's where the
    configuration scales them (DeepSeek's ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask``)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq_extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return freq_extra
    factor = rs["factor"]
    freq_inter = freq_extra / factor
    orig = rs["original_max_position_embeddings"]

    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_dim(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(find_dim(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def softmax_scale(cfg: dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs:
        s *= _mscale(rs["factor"], rs.get("mscale_all_dim", 0)) ** 2
    return s


def _cos_sin_factor(cfg: dict) -> float:
    rs = cfg.get("rope_scaling")
    if not rs:
        return 1.0
    return (_mscale(rs["factor"], rs.get("mscale", 1))
            / _mscale(rs["factor"], rs.get("mscale_all_dim", 0)))


def _fp8(w, axis):
    """Round ``w`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis is reduced over)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, freqs, factor):
    """x (T, ..., d): rotate the pairs (x0, x1), (x2, x3), ... by pos * freqs."""
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]        # (T, d/2)
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), ang.shape[1])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def attention(at, x, cfg: dict, mat=None):
    """Causal latent attention of one normed sequence x (T, D), expanded
    form; ``at`` holds wq, wkv_a, kv_norm_g, wkv_b, wo."""
    s = dims(cfg)
    H, r, dn, dr, dv = s["H"], s["r"], s["dn"], s["dr"], s["dv"]
    f32 = lambda a: a.astype(jnp.float32)
    mat = mat or f32
    T = x.shape[0]
    pos = jnp.arange(T)
    freqs, fac = inv_freq(cfg), _cos_sin_factor(cfg)
    q = (x @ mat(at["wq"])).reshape(T, H, dn + dr)
    kv = x @ mat(at["wkv_a"])
    # the latent's norm keeps RMSNorm's default eps in both published codes
    c = _rms(kv[:, :r], f32(at["kv_norm_g"]), 1e-6)
    k_pe = _rope(kv[:, r:], pos, freqs, fac)                      # (T, dr)
    kvb = (c @ mat(at["wkv_b"])).reshape(T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, freqs, fac)], -1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe[:, None], (T, H, dr))], -1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * softmax_scale(cfg)
    sc = jnp.where((pos[:, None] >= pos[None, :])[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), kvb[..., dn:])
    return o.reshape(T, H * dv) @ mat(at["wo"])


def _swiglu(x, ml, mat):
    return (jax.nn.silu(x @ mat(ml["w_gate"])) * (x @ mat(ml["w_up"]))) \
        @ mat(ml["w_down"])


def experts(ml, x, cfg: dict, mat=None):
    """The held experts' part of a routed layer on x (T, D), and the
    shared experts."""
    s = dims(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    mat = mat or f32
    probs = jax.nn.softmax(x @ mat(ml["router"]), axis=-1)          # (T, E)
    top, idx = jax.lax.top_k(probs, s["k"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)
    gates = gates[:, s["e0"]:s["e0"] + s["n"]]                       # (T, n)
    u = (jax.nn.silu(jnp.einsum("td,edf->tef", x, mat(ml["w_gate"])))
         * jnp.einsum("td,edf->tef", x, mat(ml["w_up"])))
    y = jnp.einsum("tef,efd,te->td", u, mat(ml["w_down"]), gates)
    return y + _swiglu(x, ml["shared"], mat)


def _hidden(w, tokens, cfg: dict, quant: str | None):
    """tokens (T,) int32 -> final-normed hidden states (T, D) float32."""
    eps = float(cfg["rms_norm_eps"])
    f32 = lambda a: a.astype(jnp.float32)
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown quant {quant!r}")
    # a matrix's contraction axis is its second to last
    mat = (lambda a: _fp8(f32(a), -2)) if quant == "fp8" else f32

    def layer(ffn):
        def step(h, lw):
            h = h + attention(lw["attn"], _rms(h, f32(lw["norm1"]["g"]), eps),
                              cfg, mat)
            return h + ffn(lw["mlp"], _rms(h, f32(lw["norm2"]["g"]), eps)), None
        return step

    h = f32(w["embed"])[tokens]
    if "dense_layers" in w:
        h, _ = jax.lax.scan(layer(lambda ml, x: _swiglu(x, ml, mat)), h,
                            w["dense_layers"])
    h, _ = jax.lax.scan(layer(lambda ml, x: experts(ml, x, cfg, mat)), h,
                        w["layers"])
    return _rms(h, f32(w["final_norm"]["g"]), eps)


def _head(w, quant: str | None):
    head = w["lm_head"].astype(jnp.float32)
    return _fp8(head, 0) if quant == "fp8" else head


def loss(w, tokens, labels, cfg: dict):
    """Mean next-token cross-entropy of a batch of sequences (B, T) in
    float32 at ``precision="highest"``."""
    def one(t, y):
        logits = _hidden(w, t, cfg, None) @ _head(w, None)
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
                return h[rows] @ _head(w, quant)
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
