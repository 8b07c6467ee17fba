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


def init_moe(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    D = cfg.d_model
    ks = jax.random.split(key, 5)
    sc_in, sc_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(m.d_expert)
    p = {
        "router": _normal(ks[0], (D, m.n_experts), sc_in, cfg.param_dtype),
        "w_gate": _normal(ks[1], (m.n_experts, D, m.d_expert), sc_in, cfg.param_dtype),
        "w_up": _normal(ks[2], (m.n_experts, D, m.d_expert), sc_in, cfg.param_dtype),
        "w_down": _normal(ks[3], (m.n_experts, m.d_expert, D), sc_out, cfg.param_dtype),
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
    # (nq, B, qc, Hkv, group, Dh) -> (B, Lq, Hq, Dh)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Lq, Hq, Dh)


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
            # into each slot's current page, then gather the slot-dense
            # view back out — the gathered view is value-identical to the
            # dense cache at every unmasked position, so the attention math
            # below (and the tokens) match the dense path bitwise.  Free
            # slots' tables point at the reserved sink page 0, so their
            # ride-along writes never touch a live page.
            if S != 1:
                raise NotImplementedError("paged decode is single-token")
            pt = cache["page_table"]                  # (B, n_pages) int32
            psz = cache["k"].shape[1]
            phys = jnp.take_along_axis(pt, (length // psz)[:, None],
                                       axis=1)[:, 0]
            off = length % psz
            pk = cache["k"].at[phys, off].set(k[:, 0].astype(cache["k"].dtype))
            pv = cache["v"].at[phys, off].set(v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": pk, "v": pv, "length": length + S}
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


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=None):
    dtype = dtype or cfg.dtype
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((), jnp.int32),
    }


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
