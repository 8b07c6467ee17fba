"""Shared layer library: GQA attention (RoPE / qk-norm / bias / local
window / KV cache), MLP variants (SwiGLU / GeGLU / squared-ReLU), norms, and
a sort-based capacity MoE.

All functions are pure; parameters are nested dicts of jnp arrays.  Compute
runs in ``cfg.dtype`` (bf16), params live in ``cfg.param_dtype`` (f32),
reductions in f32.  Memory-bound chains route through
:mod:`repro.kernels.ops`, so the whole model flips between the Pallas
kernels and the clean-HLO reference path with ``kernel_mode``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention as _decode
from repro.kernels import latent_attention as _latent
from repro.kernels import ops
from .config import ModelConfig

Params = dict[str, Any]


def scan_or_unroll(body, carry, xs, length: int, use_scan: bool):
    """lax.scan when ``use_scan``; otherwise a python loop over leading-axis
    slices.  The dry-run probes unroll so ``compiled.cost_analysis()`` counts
    every iteration (XLA's HLO cost analysis counts while-loop bodies once).
    """
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda a: a[i], xs) if xs is not None else None
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_norm(key, cfg: ModelConfig, d: int | None = None) -> Params:
    d = d or cfg.d_model
    p = {"g": jnp.ones((d,), cfg.param_dtype)}
    if cfg.norm == "ln":
        p["b"] = jnp.zeros((d,), cfg.param_dtype)
    return p


def init_attention(key, cfg: ModelConfig) -> Params:
    D, dh, Hq, Hkv = cfg.d_model, cfg.dh, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    sc = 1.0 / math.sqrt(D)
    p = {
        "wq": _normal(ks[0], (D, Hq * dh), sc, cfg.param_dtype),
        "wk": _normal(ks[1], (D, Hkv * dh), sc, cfg.param_dtype),
        "wv": _normal(ks[2], (D, Hkv * dh), sc, cfg.param_dtype),
        "wo": _normal(ks[3], (Hq * dh, D), 1.0 / math.sqrt(Hq * dh), cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * dh,), cfg.param_dtype)
        p["bk"] = jnp.zeros((Hkv * dh,), cfg.param_dtype)
        p["bv"] = jnp.zeros((Hkv * dh,), cfg.param_dtype)
    if cfg.qk_norm:
        p["q_norm_g"] = jnp.ones((dh,), cfg.param_dtype)
        p["k_norm_g"] = jnp.ones((dh,), cfg.param_dtype)
    return p


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    sc_in, sc_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    if cfg.act in ("swiglu", "geglu"):
        return {
            "w_gate": _normal(ks[0], (D, F), sc_in, cfg.param_dtype),
            "w_up": _normal(ks[1], (D, F), sc_in, cfg.param_dtype),
            "w_down": _normal(ks[2], (F, D), sc_out, cfg.param_dtype),
        }
    return {  # sqrelu and friends: 2-matrix MLP
        "w_up": _normal(ks[0], (D, F), sc_in, cfg.param_dtype),
        "w_down": _normal(ks[1], (F, D), sc_out, cfg.param_dtype),
    }


def init_mla(key, cfg: ModelConfig) -> Params:
    """Latent attention: ``wq`` (D, H*(nope+rope)), ``wkv_a`` (D, rank +
    rope) whose latent part is RMS-normed by ``kv_norm_g``, ``wkv_b``
    (rank, H*(nope+v)) and ``wo`` (H*v, D)."""
    a, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 4)
    dq = a.qk_nope_dim + a.qk_rope_dim
    return {
        "wq": _normal(ks[0], (D, H * dq), 1.0 / math.sqrt(D), cfg.param_dtype),
        "wkv_a": _normal(ks[1], (D, a.latent_dim), 1.0 / math.sqrt(D),
                         cfg.param_dtype),
        "kv_norm_g": jnp.ones((a.kv_rank,), cfg.param_dtype),
        "wkv_b": _normal(ks[2], (a.kv_rank, H * (a.qk_nope_dim + a.v_dim)),
                         1.0 / math.sqrt(a.kv_rank), cfg.param_dtype),
        "wo": _normal(ks[3], (H * a.v_dim, D), 1.0 / math.sqrt(H * a.v_dim),
                      cfg.param_dtype),
    }


def init_moe(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    D = cfg.d_model
    E = m.n_held                  # the experts this layer holds
    ks = jax.random.split(key, 5)
    sc_in, sc_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(m.d_expert)
    p = {
        "router": _normal(ks[0], (D, m.n_experts), sc_in, cfg.param_dtype),
        "w_gate": _normal(ks[1], (E, D, m.d_expert), sc_in, cfg.param_dtype),
        "w_up": _normal(ks[2], (E, D, m.d_expert), sc_in, cfg.param_dtype),
        "w_down": _normal(ks[3], (E, m.d_expert, D), sc_out, cfg.param_dtype),
    }
    if m.n_shared:
        p["shared"] = init_mlp(ks[4], cfg, m.d_shared)
    return p


# ---------------------------------------------------------------------------
# norms / MLPs
# ---------------------------------------------------------------------------

def apply_norm(p: Params, x, cfg: ModelConfig):
    if cfg.norm == "ln":
        return ops.layernorm(x, p["g"].astype(cfg.dtype), p["b"].astype(cfg.dtype))
    return ops.rmsnorm(x, p["g"].astype(cfg.dtype))


def apply_mlp(p: Params, x, cfg: ModelConfig):
    dt = cfg.dtype
    if "w_gate" in p:
        gate = x @ p["w_gate"].astype(dt)
        up = x @ p["w_up"].astype(dt)
        h = ops.swiglu(gate, up) if cfg.act == "swiglu" else ops.geglu(gate, up)
    else:
        h = ops.squared_relu(x @ p["w_up"].astype(dt))
    return h @ p["w_down"].astype(dt)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

_CHUNK_Q = 512  # ref-path q-chunking threshold/size for long sequences


def _chunked_causal_attention(q, k, v, scale, window, use_scan: bool = True,
                              cfg: ModelConfig | None = None):
    """Memory-sane pure-jnp attention: lax.scan over q chunks so the logits
    tensor never exceeds (B, H, CHUNK, S).  Same math as
    kernels.ref.attention, with two structural optimizations (§Perf):

    * grouped-GQA einsum — K/V are contracted at their native Hkv width
      (no ``jnp.repeat`` materializing group x K/V copies);
    * with ``cfg.shard_activations``, K/V (and thus the logits) are
      sequence-sharded over the model axis (Megatron-SP-style attention):
      softmax reductions psum tiny (B,h,g,q) stats instead of XLA
      re-sharding head-misaligned logits tensors.
    """
    B, Lq, Hq, Dh = q.shape
    _, Lkv, Hkv, _ = k.shape
    group = Hq // Hkv
    nq = Lq // _CHUNK_Q
    qg = q.reshape(B, nq, _CHUNK_Q, Hkv, group, Dh).transpose(1, 0, 2, 3, 4, 5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if cfg is not None and cfg.shard_activations:
        from .sharding import hint
        kf = hint(kf, "data", "model", None, None)
        vf = hint(vf, "data", "model", None, None)
    kpos = jnp.arange(Lkv)

    def chunk(carry, inp):
        ci, qb = inp                       # qb: (B, qc, Hkv, group, Dh)
        qpos = ci * _CHUNK_Q + jnp.arange(_CHUNK_Q)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qb.astype(jnp.float32), kf) * scale
        mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
        return carry, out.astype(qb.dtype)

    _, outs = scan_or_unroll(chunk, None, (jnp.arange(nq), qg), nq, use_scan)
    # (nq, B, qc, Hkv, group, Dv) -> (B, Lq, Hq, Dv); Dv is v's own head
    # size, which latent attention sets apart from q's and k's
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Lq, Hq, v.shape[-1])


def apply_attention(
    p: Params,
    x,
    cfg: ModelConfig,
    positions,
    cache: Params | None = None,
    window: int | None = None,
    return_kv: bool = False,
):
    """x: (B, S, D). If ``cache`` is given (decode), S is the new-token count
    and attention runs against cache+new; returns (out, new_cache).
    With ``return_kv`` (prefill), the post-RoPE k/v are returned instead."""
    dt = cfg.dtype
    B, S, D = x.shape
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh

    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, Hq, dh)
    k = k.reshape(B, S, Hkv, dh)
    v = v.reshape(B, S, Hkv, dh)
    if cfg.qk_norm:
        q = ops.rmsnorm(q, p["q_norm_g"].astype(dt))
        k = ops.rmsnorm(k, p["k_norm_g"].astype(dt))
    q = ops.rope(q, positions, cfg.rope_theta)
    k = ops.rope(k, positions, cfg.rope_theta)

    scale = 1.0 / math.sqrt(dh)
    new_cache = {"k": k, "v": v} if return_kv else None
    if cache is not None:
        # static-shape serving: cache (B, Smax, Hkv, dh); `length` tokens valid.
        # A scalar `length` is the lock-step batch (every row at the same
        # position); a (B,) vector is the continuous-batching ragged batch —
        # each slot writes its new KV at its own offset (vmapped
        # dynamic_update_slice lowers to one batched scatter).
        length = cache["length"]
        if "page_table" in cache:
            # Paged serving: the layer's KV lives in a shared page pool
            # (n_pages, page_size, Hkv, dh); ``page_table`` (B, n) maps each
            # slot's logical pages to pool pages.  Scatter the new token
            # into each slot's current page.  Free slots' tables point at
            # the reserved sink page 0, so their ride-along writes never
            # touch a live page.
            if S != 1:
                raise NotImplementedError("paged decode is single-token")
            pt = cache["page_table"]                  # (B, n_pages) int32
            psz = cache["k"].shape[1]
            phys = jnp.take_along_axis(pt, (length // psz)[:, None],
                                       axis=1)[:, 0]
            off = length % psz
            kw = k[:, 0].astype(cache["k"].dtype)
            vw = v[:, 0].astype(cache["v"].dtype)
            pk = cache["k"].at[phys, off].set(kw)
            pv = cache["v"].at[phys, off].set(vw)
            new_cache = {"k": pk, "v": pv, "length": length + S}
            if "pool" in cache:
                # the kernel path (see attention_path): read each
                # slot's live pages of the stacked pool as the step received
                # it, this token's row folded in from kw/vw
                pool_k, pool_v, layer = cache["pool"]
                out = _decode.paged_decode_attention(
                    q[:, 0], pool_k, pool_v, layer, pt, length, kw, vw,
                    scale=scale, window=window)
                out = out.reshape(B, S, Hq * dh) @ p["wo"].astype(dt)
                return out, new_cache
            # the gather path: gather the slot-dense view back out — it is
            # value-identical to the dense cache at every unmasked position,
            # so the attention math below (and the tokens) match the dense
            # path bitwise
            n = pt.shape[1]
            flat = pt.reshape(-1)
            ck = jnp.take(pk, flat, axis=0).reshape(B, n * psz, Hkv, dh)
            cv = jnp.take(pv, flat, axis=0).reshape(B, n * psz, Hkv, dh)
            lim = cache.get("kv_limit")
            if lim is not None and lim < n * psz:
                # page capacity rounds max_len up to a page multiple; slice
                # back so the softmax reduction shape matches dense exactly
                ck, cv = ck[:, :lim], cv[:, :lim]
        elif jnp.ndim(length):
            row_upd = lambda c, u, l: jax.lax.dynamic_update_slice(
                c, u, (l, 0, 0))
            ck = jax.vmap(row_upd)(cache["k"], k.astype(cache["k"].dtype), length)
            cv = jax.vmap(row_upd)(cache["v"], v.astype(cache["v"].dtype), length)
            new_cache = {"k": ck, "v": cv, "length": length + S}
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, length, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, length, 0, 0))
            new_cache = {"k": ck, "v": cv, "length": length + S}
        Smax = ck.shape[1]
        group = Hq // Hkv
        if S == 1 and ops.get_mode() == "pallas" \
                and not cfg.shard_activations:
            # registered Pallas decode kernel: the whole masked-softmax
            # attention chain is ONE CUSTOM node (the position
            # mask covers length validity, so stale cache rows never
            # contribute — same semantics as the einsum path below)
            out = ops.decode_attention(q, ck, cv, positions[:, 0],
                                       scale=scale, window=window)
            out = out.reshape(B, S, Hq * dh) @ p["wo"].astype(dt)
            return out, new_cache
        # grouped-GQA einsum against the cache at native Hkv width: no
        # jnp.repeat copy, no f32 cache clone — bf16 dots accumulate in f32
        # (§Perf decode iteration)
        qg = q.reshape(B, S, Hkv, group, dh)
        if cfg.shard_activations:
            # contract over the cache's (sharded) head-dim: reshard the tiny
            # q instead of letting SPMD all-gather the 1 GB cache
            from .sharding import hint
            qg = hint(qg, "data", None, None, None, "model")
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck,
                            preferred_element_type=jnp.float32) * scale
        kpos = jnp.arange(Smax)[None, None, None, None, :]
        qpos = positions[:, None, None, :, None]
        mask = kpos <= qpos
        if window is not None:
            mask = mask & (qpos - kpos < window)
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(dt), cv,
                         preferred_element_type=jnp.float32)
        out = out.reshape(B, S, Hq, dh).astype(dt)
    elif ops.get_mode() == "pallas" and S % 128 == 0:
        out = ops.attention(q, k, v, causal=True, scale=scale, window=window)
    elif S > _CHUNK_Q and S % _CHUNK_Q == 0:
        out = _chunked_causal_attention(q, k, v, scale, window,
                                        use_scan=cfg.scan_layers, cfg=cfg)
    else:
        from repro.kernels import ref
        out = ref.attention(q, k, v, causal=True, scale=scale, window=window,
                            positions_q=positions)
    out = out.reshape(B, S, Hq * dh) @ p["wo"].astype(dt)
    return out, new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(dim: int, theta: float, yarn=None) -> np.ndarray:
    """Inverse frequencies of a ``dim``-wide rotary part: plain RoPE, or
    YaRN's blend of interpolated and extrapolated frequencies over a linear
    ramp between the correction dims of ``beta_fast`` and ``beta_slow``
    (floored / ceiled), as DeepSeek-V2's modelling code computes them."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra = 1.0 / pos
    if yarn is None:
        return extra.astype(np.float32)
    inter = 1.0 / (yarn.factor * pos)

    def corr(rot):
        return dim * math.log(yarn.original_max / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    ramp = (np.arange(dim // 2, dtype=np.float32) - lo) / max(hi - lo, 1e-3)
    keep = 1.0 - np.clip(ramp, 0.0, 1.0)     # 1: extrapolate (high freq)
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_pairs(x, positions, inv_freq):
    """Rotary embedding over adjacent pairs ``(x0, x1), (x2, x3), ...`` (the
    complex-number convention of DeepSeek's code), in float32.
    x: (B, S, H, d) or (B, S, d); positions (B, S)."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq   # (B, S, d/2)
    if x.ndim == positions.ndim + 2:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x0, x1 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: (nope + rope)^-1/2, times YaRN's mscale(all_dim)^2 as
    DeepSeek's own code sets it (transformers' port leaves the square
    out)."""
    a = cfg.mla
    s = (a.qk_nope_dim + a.qk_rope_dim) ** -0.5
    if a.yarn is not None:
        s *= yarn_mscale(a.yarn.factor, a.yarn.mscale_all_dim) ** 2
    return s


def _mla_project(p: Params, x, cfg: ModelConfig, positions):
    """(q_nope (B,S,H,nope), rotated q_pe (B,S,H,rope), latent row (B,S,
    rank+rope)): the row is what the cache keeps per token, the normed
    latent and the rotated key part shared by every head."""
    a, dt = cfg.mla, cfg.dtype
    B, S, _ = x.shape
    inv = rope_inv_freq(a.qk_rope_dim, cfg.rope_theta, a.yarn)
    q = (x @ p["wq"].astype(dt)).reshape(
        B, S, cfg.n_heads, a.qk_nope_dim + a.qk_rope_dim)
    kv = x @ p["wkv_a"].astype(dt)
    c = ops.rmsnorm(kv[..., :a.kv_rank], p["kv_norm_g"].astype(dt))
    k_pe = rope_pairs(kv[..., a.kv_rank:], positions, inv)
    q_pe = rope_pairs(q[..., a.qk_nope_dim:], positions, inv)
    return q[..., :a.qk_nope_dim], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def apply_mla(p: Params, x, cfg: ModelConfig, positions):
    """Full-sequence causal latent attention in the expanded form: per head
    [k_nope, v] = latent @ wkv_b, k = [k_nope, k_pe].  x: (B, S, D) ->
    (out (B, S, D), latent rows (B, S, rank+rope) for the cache)."""
    a, dt = cfg.mla, cfg.dtype
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_pe, lat = _mla_project(p, x, cfg, positions)
    kvb = (lat[..., :a.kv_rank] @ p["wkv_b"].astype(dt)).reshape(
        B, S, H, a.qk_nope_dim + a.v_dim)
    k_pe = jnp.broadcast_to(lat[:, :, None, a.kv_rank:],
                            (B, S, H, a.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kvb[..., :a.qk_nope_dim], k_pe], axis=-1)
    v = kvb[..., a.qk_nope_dim:]
    scale = mla_scale(cfg)
    if S > _CHUNK_Q and S % _CHUNK_Q == 0:
        out = _chunked_causal_attention(q, k, v, scale, None,
                                        use_scan=cfg.scan_layers, cfg=cfg)
    else:
        from repro.kernels import ref
        out = ref.attention(q, k, v, causal=True, scale=scale,
                            positions_q=positions)
    return out.reshape(B, S, H * a.v_dim) @ p["wo"].astype(dt), lat


def _latent_view(cache: Params, layer, rope_dim: int):
    """Every slot's whole latent view (B, n * page, rank+rope) at ``layer``
    of the paged pool, gathered through the page table: ``lat`` by (layer,
    page), ``rope`` by (layer, page // pack) and then the page's own lane
    group.  One gather each: slicing the layer out first would copy its
    whole pool each layer."""
    lat, rope, pt = cache["lat"], cache["rope"], cache["page_table"]
    B, n = pt.shape
    _, _, psz, lanes = rope.shape
    pack = lanes // rope_dim
    flat = pt.reshape(-1)
    c = lat[layer, flat]                                 # (B*n, page, rank)
    pe = rope[layer, flat // pack].reshape(B * n, psz, pack, rope_dim)
    pe = jnp.take_along_axis(pe, (flat % pack)[:, None, None, None],
                             axis=2)[:, :, 0]
    return jnp.concatenate([c, pe], axis=-1).reshape(B, n * psz, -1)


def latent_gather_attention(q_lat, q_pe, cache: Params, layer, row, *,
                            scale: float):
    """The gather path of :func:`mla_decode`: ``o_lat`` (B, H, rank) =
    softmax(scores) . c over each slot's whole view at ``layer`` -- gathered
    from the paged pool and sliced back to ``kv_limit``, or the dense
    rectangle's -- with this step's ``row`` (B, rank+rope) set at the
    slot's ``length`` and every later position masked.  q_lat (B, H, rank)
    and q_pe (B, H, rope) are in the compute dtype."""
    B, _, r = q_lat.shape
    length = cache["length"]
    if "page_table" in cache:
        view = _latent_view(cache, layer, q_pe.shape[-1])
        lim = cache.get("kv_limit")
        if lim is not None and lim < view.shape[1]:
            view = view[:, :lim]
    else:
        view = cache["lat"][layer]
    view = view.at[jnp.arange(B), length].set(row.astype(view.dtype))
    dt = q_lat.dtype
    q_full = jnp.concatenate([q_lat, q_pe], axis=-1)
    score = jnp.einsum("bhc,bkc->bhk", q_full, view.astype(dt),
                       preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(view.shape[1])[None, None, :]
    score = jnp.where(kpos <= length[:, None, None], score, -1e30)
    probs = jax.nn.softmax(score, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", probs.astype(dt),
                      view[..., :r].astype(dt),
                      preferred_element_type=jnp.float32)


def mla_decode(p: Params, x, cfg: ModelConfig, positions, cache: Params,
               layer):
    """One decode token of latent attention in the absorbed form, reading
    only cached latent rows: q_lat = q_nope @ W_UK, score = (q_lat . c +
    q_pe . k_pe) * scale, o = softmax(score) . c @ W_UV @ wo.

    The cache holds every layer's latent store, read at ``layer`` (a traced
    index) before this step's rows are written: a page pool of two leaves,
    ``lat`` (L, P, page, rank) and ``rope`` (L, P', page, lanes) (see
    :meth:`LatentAttention.init_paged_cache`), read through
    ``cache["page_table"]``; or a dense rectangle ``lat`` (L, B, max_len,
    rank+rope).  On the kernel path (:func:`attention_path`) each slot's
    live pages are read in place and the kernel folds this token's row in;
    otherwise :func:`latent_gather_attention`.  Returns (out (B, 1, D), the
    row (B, rank+rope) that the step writes)."""
    a, dt = cfg.mla, cfg.dtype
    B, S, _ = x.shape
    H, r = cfg.n_heads, a.kv_rank
    if S != 1:
        raise NotImplementedError("latent decode is single-token")
    q_nope, q_pe, lat = _mla_project(p, x, cfg, positions)
    row = lat[:, 0]
    wkv_b = p["wkv_b"].astype(dt).reshape(r, H, a.qk_nope_dim + a.v_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wkv_b[..., :a.qk_nope_dim],
                       preferred_element_type=jnp.float32).astype(dt)
    if attention_path(cfg, cache) == "mla-kernel":
        o_lat = _latent.paged_latent_decode_attention(
            q_lat, q_pe[:, 0], cache["lat"], cache["rope"], layer,
            cache["page_table"], cache["length"], row, scale=mla_scale(cfg))
    else:
        o_lat = latent_gather_attention(q_lat, q_pe[:, 0], cache, layer, row,
                                        scale=mla_scale(cfg))
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), wkv_b[..., a.qk_nope_dim:],
                   preferred_element_type=jnp.float32).astype(dt)
    return o.reshape(B, 1, H * a.v_dim) @ p["wo"].astype(dt), row


def _scatter_rows(leaf, rows, at):
    """``leaf[at[..., :-1], at[-1]:at[-1] + w] = rows`` for rows (L, B, w)
    and (L, B, leaf.ndim) start indices: one scatter of L * B rows."""
    nd = leaf.ndim
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(2,), inserted_window_dims=tuple(range(nd - 1)),
        scatter_dims_to_operand_dims=tuple(range(nd)))
    return jax.lax.scatter(leaf, at, rows.astype(leaf.dtype), dnums,
                           mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def write_latent(cache: Params, rows) -> Params:
    """Write one decode step's latent rows (L, B, rank+rope) of every
    layer at each slot's ``length``: through the page table into the
    slot's current page of the ``lat`` and ``rope`` leaves (free slots'
    tables point at the sink page 0), or into the dense rectangle.
    Returns the written leaves."""
    length = cache["length"]
    if "page_table" not in cache:
        lat = cache["lat"]
        return {"lat": lat.at[:, jnp.arange(rows.shape[1]), length].set(
            rows.astype(lat.dtype))}
    lat, rope = cache["lat"], cache["rope"]
    L, B, _ = rows.shape
    r, psz, lanes = lat.shape[-1], lat.shape[2], rope.shape[-1]
    w = rows.shape[-1] - r
    pack = lanes // w
    phys = jnp.take_along_axis(cache["page_table"],
                               (length // psz)[:, None], axis=1)[:, 0]

    def at(*idx):
        return jnp.stack([jnp.broadcast_to(i, (L, B)) for i in idx], axis=-1)

    layer = jnp.arange(L, dtype=phys.dtype)[:, None]
    off, zero = length % psz, jnp.zeros_like(phys)
    return {"lat": _scatter_rows(lat, rows[..., :r],
                                 at(layer, phys, off, zero)),
            "rope": _scatter_rows(rope, rows[..., r:],
                                  at(layer, phys // pack, off,
                                     phys % pack * w))}


# ---------------------------------------------------------------------------
# attention kinds: what repro.models.lm's one layer walk leaves to the kind —
# the attention, what a decode layer reads from xs and returns in ys, and
# how the cache is made and written
# ---------------------------------------------------------------------------

def rope_pack(rope_dim: int) -> int:
    """Pages whose rope parts share one 128-lane row of the latent pool's
    ``rope`` leaf (1 where ``rope_dim`` does not divide 128)."""
    return 128 // rope_dim if 128 % rope_dim == 0 else 1


class GroupedQueryAttention:
    """:func:`apply_attention`, caching each layer's post-RoPE ``k`` and
    ``v``: (L, B, max_len, Hkv, dh), or pages (L, P, page, Hkv, dh).  A
    decode layer takes its layer of both from xs, writes its token there
    and returns them in ys: the scan restacks the cache."""

    live_pages = "kv_live_pages"          # the serve.decode span's count
    init = staticmethod(init_attention)

    def forward(self, p, x, cfg, positions, rows: bool):
        """Full sequence: (out, ``{"k", "v"}`` where ``rows``, else None)."""
        return apply_attention(p, x, cfg, positions, return_kv=rows)

    def seq_ys(self, rows, aux):
        """A full-sequence layer's ys: its rows in prefill, aux in train."""
        return (rows, None) if rows is not None else (None, aux)

    def init_cache(self, cfg, batch: int, max_len: int) -> Params:
        # the rectangle is the pool's layout: a page of max_len a slot
        return {**self.init_paged_cache(cfg, batch, max_len),
                "length": jnp.zeros((), jnp.int32)}

    def init_paged_cache(self, cfg, num_pages: int, page_size: int) -> Params:
        shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.dh)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}

    def path(self, cfg, cache) -> str | None:
        """``"kernel"`` reads each slot's live pages through the page table
        (:func:`repro.kernels.decode_attention.paged_decode_attention`),
        where Pallas compiles and the pool tiles; ``"gather"`` gathers every
        slot's whole view and masks it, bitwise equal to the dense layout;
        None over the dense rectangle."""
        if cache is None or "page_table" not in cache:
            return None
        if _decode.paged_kernel_platform() and \
                _decode.paged_decode_fits(cache["k"].shape, cfg.n_heads):
            return "kernel"
        return "gather"

    def positions(self, cache: Params, B: int, S: int):
        """(positions (B, S), length): a scalar ``length`` is the lock-step
        batch, a (B,) one the ragged batch."""
        length = cache["length"]
        base = length[:, None] if jnp.ndim(length) else length
        return base + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                       (B, S)), length

    def decode_xs(self, cfg, view: Params, first: int, n: int):
        """Layers [first, first + n) of k and v; on the kernel path also
        their indices, at which each reads the stacked pool, a loop
        invariant."""
        xs = (view["k"][first:first + n], view["v"][first:first + n])
        if self.path(cfg, view) == "kernel":
            xs += (jnp.arange(first, first + n, dtype=jnp.int32),)
        return xs

    def decode(self, p, x, cfg, positions, view: Params, xs):
        """One layer's token: (out, its written ``{"k", "v"}``)."""
        ck, cv, *layer = xs
        cache = {**view, "k": ck, "v": cv}
        if layer:
            cache["pool"] = (view["k"], view["v"], layer[0])
        out, new = apply_attention(p, x, cfg, positions, cache=cache)
        return out, {"k": new["k"], "v": new["v"]}

    def write(self, view: Params, rows) -> Params:
        return rows                       # each layer wrote its own


class LatentAttention:
    """:func:`apply_mla` and :func:`mla_decode` (DeepSeek-V2), caching each
    token's rank+rope row: ``lat`` (L, B, max_len, rank+rope), or pages
    (:meth:`init_paged_cache`).  A decode layer reads the whole cache, a
    loop invariant, at its layer index from xs and returns its row in ys;
    one write after the walk puts the rows in place."""

    live_pages = "kv_latent_pages"
    init = staticmethod(init_mla)

    def forward(self, p, x, cfg, positions, rows: bool):
        """Full sequence: (out, ``{"lat"}``), kept or not."""
        out, lat = apply_mla(p, x, cfg, positions)
        return out, {"lat": lat}

    def seq_ys(self, rows, aux):
        """Both, in train and prefill alike (jit drops the unused one)."""
        return rows, aux

    def init_cache(self, cfg, batch: int, max_len: int) -> Params:
        return {"lat": jnp.zeros((cfg.n_layers, batch, max_len,
                                  cfg.mla.latent_dim), cfg.dtype),
                "length": jnp.zeros((), jnp.int32)}

    def init_paged_cache(self, cfg, num_pages: int, page_size: int) -> Params:
        """Two leaves, so that a page of each is whole (sublane, lane)
        tiles that one DMA copies and no dimension is the odd rank+rope:
        ``lat`` (L, P, page, rank) and ``rope`` (L, ceil(P / pack), page,
        pack * rope), where ``pack`` pages' rope parts lie side by side in a
        128-lane row (page p at row p // pack, lanes (p % pack) * rope on).
        Together they hold exactly rank+rope values a token."""
        a = cfg.mla
        pack = rope_pack(a.qk_rope_dim)
        return {"lat": jnp.zeros((cfg.n_layers, num_pages, page_size,
                                  a.kv_rank), cfg.dtype),
                "rope": jnp.zeros((cfg.n_layers, -(-num_pages // pack),
                                   page_size, pack * a.qk_rope_dim),
                                  cfg.dtype)}

    def path(self, cfg, cache) -> str:
        """``"mla-kernel"`` reads each slot's live pages of the pool's
        ``lat`` and ``rope`` leaves in place
        (:func:`repro.kernels.latent_attention.paged_latent_decode_attention`),
        where Pallas compiles and the pages tile; ``"mla-gather"`` gathers
        every slot's whole view (or reads the dense rectangle) and masks
        it, bitwise equal to the dense layout."""
        if cache is not None and "rope" in cache and \
                _decode.paged_kernel_platform() and \
                _latent.paged_latent_fits(cache["lat"].shape,
                                          cache["rope"].shape, cfg.n_heads):
            return "mla-kernel"
        return "mla-gather"

    def positions(self, cache: Params, B: int, S: int):
        """(positions (B, S), length), one length per slot."""
        lengths = jnp.broadcast_to(cache["length"], (B,))
        return lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None], lengths

    def decode_xs(self, cfg, view: Params, first: int, n: int):
        return (jnp.arange(first, first + n, dtype=jnp.int32),)

    def decode(self, p, x, cfg, positions, view: Params, xs):
        """One layer's token: (out, its row (B, rank+rope))."""
        return mla_decode(p, x, cfg, positions, view, xs[0])

    def write(self, view: Params, rows) -> Params:
        return write_latent(view, rows)


_KINDS = (GroupedQueryAttention(), LatentAttention())


def attention_kind(cfg: ModelConfig):
    """The decoder's attention kind: latent where ``cfg.mla`` is set, else
    grouped-query."""
    return _KINDS[cfg.mla is not None]


def attention_path(cfg: ModelConfig, cache: Params | None = None):
    """How the decode step attends over ``cache`` (None: the dense
    rectangle), as the platform and the pool's shapes decide:
    ``"kernel"`` | ``"gather"`` (grouped-query over a page pool, None over
    the rectangle) or ``"mla-kernel"`` | ``"mla-gather"`` (latent)."""
    return attention_kind(cfg).path(cfg, cache)


# ---------------------------------------------------------------------------
# sort-based capacity MoE (dropless up to capacity, GShard semantics)
# ---------------------------------------------------------------------------

def _moe_group_dispatch(xg, wg, ig, p, cfg: ModelConfig, C: int):
    """Dispatch ONE token group: xg (Tg, D), router weights wg (Tg, k),
    expert ids ig (Tg, k) -> (yg (Tg, D), counts (E,), n_dropped ())."""
    m = cfg.moe
    dt = cfg.dtype
    Tg, D = xg.shape
    E, k = m.n_experts, m.top_k

    flat_e = ig.reshape(-1)                                    # (Tg*k,)
    flat_w = wg.reshape(-1).astype(dt)
    flat_tok = jnp.arange(Tg * k, dtype=jnp.int32) // k

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]

    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(Tg * k, dtype=jnp.int32) - offsets[sorted_e]
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)     # E*C = drop bin

    buf_tok = jnp.full((E * C + 1,), -1, jnp.int32).at[slot].set(sorted_tok)
    buf_tok = buf_tok[:-1]
    gathered = jnp.where(
        (buf_tok >= 0)[:, None],
        xg[jnp.clip(buf_tok, 0, Tg - 1)],
        jnp.zeros((), dt),
    ).reshape(E, C, D)

    gate = jnp.einsum("ecd,edf->ecf", gathered, p["w_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", gathered, p["w_up"].astype(dt))
    h = ops.swiglu(gate, up)
    yexp = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt)).reshape(E * C, D)
    yexp = jnp.concatenate([yexp, jnp.zeros((1, D), dt)], axis=0)  # drop bin

    slot_of_flat = jnp.full((Tg * k,), E * C, jnp.int32).at[order].set(slot)
    contrib = flat_w[:, None] * yexp[slot_of_flat]
    yg = jnp.zeros((Tg, D), dt).at[flat_tok].add(contrib)
    return yg, counts, jnp.sum(~keep)


def _moe_groups(cfg: ModelConfig, T: int) -> int:
    m = cfg.moe
    G = m.n_groups if m.n_groups else 16
    while T % G:
        G //= 2
    return max(G, 1)


def apply_moe(p: Params, x2d, cfg: ModelConfig):
    """x2d: (T, D) -> (T, D), aux metrics dict.

    Sort-based capacity dispatch: token-expert assignments are sorted by
    expert, packed into (E, C, D) buffers (overflow dropped — GShard
    token-choice semantics), run through batched expert FFNs (EP-shardable
    einsum), and combined back with router weights.  No (T, E, C) one-hot is
    ever materialized, so the HLO stays memory-sane at 1M tokens.

    With ``moe.n_groups > 1`` the dispatch runs independently per token
    group (vmap); groups align with the DP shards so the sort/gather/scatter
    never crosses devices — only the expert einsums communicate (§Perf).
    """
    m = cfg.moe
    T, D = x2d.shape
    E, k = m.n_experts, m.top_k

    logits = (x2d @ p["router"].astype(cfg.dtype)).astype(jnp.float32)
    weights, idx = ops.topk_router(logits, k, m.renormalize)   # (T, k)

    G = _moe_groups(cfg, T)
    Tg = T // G
    C = int(math.ceil(m.capacity_factor * Tg * k / E))
    C = max(8, -(-C // 8) * 8)  # round up to sublane multiple

    if G == 1:
        y, counts, n_drop = _moe_group_dispatch(x2d, weights, idx, p, cfg, C)
    else:
        from .sharding import hint_rows
        xg = x2d.reshape(G, Tg, D)
        if cfg.shard_activations:
            xg = hint_rows(xg)
        yg, counts_g, drop_g = jax.vmap(
            lambda xa, wa, ia: _moe_group_dispatch(xa, wa, ia, p, cfg, C)
        )(xg, weights.reshape(G, Tg, k), idx.reshape(G, Tg, k))
        if cfg.shard_activations:
            yg = hint_rows(yg)
        y = yg.reshape(T, D)
        counts = jnp.sum(counts_g, axis=0)
        n_drop = jnp.sum(drop_g)

    if m.n_shared:
        y = y + apply_mlp(p["shared"], x2d, cfg)

    # load-balance aux (Switch): E * sum_e f_e * p_e
    probs = jax.nn.softmax(logits, axis=-1)
    frac = counts.astype(jnp.float32) / (T * k)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    dropped = n_drop / (T * k)
    return y, {"moe_aux": aux, "moe_drop_frac": dropped.astype(jnp.float32)}


def apply_moe_share(p: Params, x2d, cfg: ModelConfig):
    """Dropless expert share: x2d (T, D) -> (y (T, D), aux, load (2,)).

    The router keeps all ``n_experts`` outputs: a float32 softmax over
    them, greedy top-k over all (renormalized only with ``renormalize``),
    times ``routed_scale``.  The layer holds experts ``[e0, e0 + n)``
    (``moe.held``) and computes, for every (token, expert) pair routed to
    one of them, that expert's SwiGLU output weighted by its gate: the pairs
    are sorted by held expert into a grouped matmul
    (``jax.lax.ragged_dot``) with a static bound of T * k rows, and pairs
    routed elsewhere sort last and contribute nothing.  No capacity, so no
    token is dropped and no row depends on another.  The shared experts
    run on every token.  ``load`` is (pairs routed here, most pairs on one
    held expert)."""
    m = cfg.moe
    dt = cfg.dtype
    T, D = x2d.shape
    e0, n = m.held
    k = m.top_k
    logits = x2d.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gates, idx = jax.lax.top_k(probs, k)
    if m.renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * m.routed_scale
    local = idx - e0
    here = (local >= 0) & (local < n)
    flat_e = jnp.where(here, local, n).reshape(-1)             # n: elsewhere
    order = jnp.argsort(flat_e, stable=True)
    tok = order // k
    sizes = jnp.zeros((n + 1,), jnp.int32).at[flat_e].add(1)[:n]
    xs = x2d[tok]                                               # (T*k, D)
    gate = jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), sizes)
    up = jax.lax.ragged_dot(xs, p["w_up"].astype(dt), sizes)
    ys = jax.lax.ragged_dot(ops.swiglu(gate, up), p["w_down"].astype(dt), sizes)
    # rows past the held pairs are not computed: select, never multiply
    keep = here.reshape(-1)[order][:, None]
    contrib = jnp.where(keep, ys.astype(jnp.float32)
                        * gates.reshape(-1)[order][:, None], 0.0)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(contrib)
    y = y.astype(dt)
    if m.n_shared:
        y = y + apply_mlp(p["shared"], x2d, cfg)
    # load-balance aux over the whole router, as apply_moe reports it
    counts = jnp.zeros((m.n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    aux = m.n_experts * jnp.sum(counts / (T * k) * jnp.mean(probs, axis=0))
    load = jnp.stack([jnp.sum(sizes), jnp.max(sizes)])
    return y, {"moe_aux": aux, "moe_drop_frac": jnp.zeros((), jnp.float32)}, load


def apply_moe_dense(p: Params, x2d, cfg: ModelConfig):
    """Dense (soft) MoE: every expert runs on every token, combined by the
    full router-softmax gates.  x2d: (T, D) -> (T, D).

    Unlike :func:`apply_moe` there is no sort/gather dispatch, so the HLO is
    E structurally-identical, mutually-independent FFN chains hanging off the
    shared input — exactly the shape the horizontal packer
    (:func:`repro.core.fusiongen.packing_fusion`) bins into shared stitched
    kernels.  This is the block-level stitching form (``Model.block_fn``) and
    the packing benchmark workload; train/serve keep the sparse dispatch.
    """
    m = cfg.moe
    dt = cfg.dtype
    logits = (x2d @ p["router"].astype(dt)).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1).astype(dt)        # (T, E)
    y = jnp.zeros_like(x2d)
    for e in range(m.n_experts):
        gt = x2d @ p["w_gate"][e].astype(dt)
        up = x2d @ p["w_up"][e].astype(dt)
        h = ops.swiglu(gt, up)
        ye = h @ p["w_down"][e].astype(dt)
        y = y + gates[:, e:e + 1] * ye
    if m.n_shared:
        y = y + apply_mlp(p["shared"], x2d, cfg)
    return y
