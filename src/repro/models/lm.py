"""Decoder-only transformer LM — dense, MoE, and VLM-backbone families.

Layers are scanned (``jax.lax.scan`` over stacked parameters) so HLO size is
O(1) in depth; remat policy comes from the config.  The same forward serves:

* ``train_forward``  — full-sequence causal, returns mean-token CE loss;
* ``prefill``        — full-sequence causal, fills a KV cache;
* ``decode_step``    — single-token step against a static-shape KV cache.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .config import ModelConfig
from .layers import (
    Params,
    scan_or_unroll,
    apply_attention,
    apply_mlp,
    apply_moe,
    apply_moe_dense,
    apply_norm,
    init_attention,
    init_mlp,
    init_moe,
    init_norm,
    paged_attention_path,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "norm1": init_norm(k1, cfg),
        "attn": init_attention(k2, cfg),
        "norm2": init_norm(k3, cfg),
    }
    if cfg.family == "moe":
        p["mlp"] = init_moe(k4, cfg)
    else:
        p["mlp"] = init_mlp(k4, cfg)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    p = {
        "embed": (jax.random.normal(ke, (cfg.vocab, cfg.d_model), jnp.float32)
                  * 0.02).astype(cfg.param_dtype),
        "layers": layers,
        "final_norm": init_norm(kh, cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(kh, (cfg.d_model, cfg.vocab), jnp.float32)
                        / math.sqrt(cfg.d_model)).astype(cfg.param_dtype)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _empty_aux():
    return {"moe_aux": jnp.zeros((), jnp.float32),
            "moe_drop_frac": jnp.zeros((), jnp.float32)}


def _layer_fn(h, lp, cfg: ModelConfig, positions):
    if cfg.shard_activations:
        from .sharding import hint_rows
        h = hint_rows(h)
    a_in = apply_norm(lp["norm1"], h, cfg)
    attn_out, _ = apply_attention(lp["attn"], a_in, cfg, positions)
    h = h + attn_out
    m_in = apply_norm(lp["norm2"], h, cfg)
    if cfg.family == "moe":
        B, S, D = m_in.shape
        y2d, aux = apply_moe(lp["mlp"], m_in.reshape(B * S, D), cfg)
        mlp_out = y2d.reshape(B, S, D)
    else:
        mlp_out = apply_mlp(lp["mlp"], m_in, cfg)
        aux = _empty_aux()
    return h + mlp_out, aux


def block_forward(lp: Params, x, cfg: ModelConfig):
    """Pure single-block forward — the ``Model.block_fn`` stitching entry
    (see examples/stitch_fn.py).  x: (B, S, D) -> (B, S, D).

    MoE blocks use the *dense* expert form (:func:`apply_moe_dense`): the
    sort-based capacity dispatch is gather/scatter-partitioned anyway, while
    the dense form exposes E independent per-expert chains for the
    horizontal packer."""
    B, S, D = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    a_in = apply_norm(lp["norm1"], x, cfg)
    attn_out, _ = apply_attention(lp["attn"], a_in, cfg, positions)
    x = x + attn_out
    m_in = apply_norm(lp["norm2"], x, cfg)
    if cfg.family == "moe":
        y2d = apply_moe_dense(lp["mlp"], m_in.reshape(B * S, D), cfg)
        mlp_out = y2d.reshape(B, S, D)
    else:
        mlp_out = apply_mlp(lp["mlp"], m_in, cfg)
    return x + mlp_out


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def backbone(params: Params, h, cfg: ModelConfig, positions):
    """h: (B, S, D) embeddings -> (B, S, D) final-normed hidden, aux."""
    body = _maybe_remat(
        lambda carry, lp: _layer_fn(carry, lp, cfg, positions), cfg)
    h, auxs = scan_or_unroll(body, h, params["layers"], cfg.n_layers,
                             cfg.scan_layers)
    h = apply_norm(params["final_norm"], h, cfg)
    aux = jax.tree.map(jnp.mean, auxs)
    return h, aux


def embed_tokens(params: Params, tokens, cfg: ModelConfig, patch_embeds=None):
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if patch_embeds is not None:
        # VLM frontend stub: precomputed patch embeddings occupy the first
        # n_patch_tokens positions of the sequence.
        P = patch_embeds.shape[1]
        h = jnp.concatenate([patch_embeds.astype(cfg.dtype), h[:, P:]], axis=1)
    return h


def _head_matrix(params: Params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T.astype(cfg.dtype)
    return params["lm_head"].astype(cfg.dtype)


def lm_loss(params: Params, h, labels, cfg: ModelConfig, n_chunks: int = 16):
    """Chunked CE: the (tokens, vocab) logits tensor is produced one chunk at
    a time inside a scan, never materialized whole.

    ``cfg.loss_groups > 1``: tokens are first split into G groups aligned
    with the DP shards, and chunking slices WITHIN each group — every chunk
    matmul then carries all G shards (stays DP-parallel) instead of mapping
    one contiguous token range (= one DP shard) per chunk (§Perf)."""
    B, S, D = h.shape
    W = _head_matrix(params, cfg)
    h2 = h.reshape(B * S, D)
    if cfg.shard_activations:
        from .sharding import hint_rows
        h2 = hint_rows(h2)
    y2 = labels.reshape(B * S)
    T = B * S
    G = cfg.loss_groups
    while T % G:
        G //= 2
    G = max(G, 1)
    Tg = T // G
    while Tg % n_chunks:
        n_chunks -= 1
    Tc = Tg // n_chunks

    hg = h2.reshape(G, Tg, D)
    yg = y2.reshape(G, Tg)
    if cfg.shard_activations and G > 1:
        from .sharding import hint_rows
        hg = hint_rows(hg)

    def chunk(carry, j):
        hcb = jax.lax.dynamic_slice_in_dim(hg, j * Tc, Tc, axis=1)  # (G,Tc,D)
        ycb = jax.lax.dynamic_slice_in_dim(yg, j * Tc, Tc, axis=1)
        logits = jnp.einsum("gtd,dv->gtv", hcb, W).astype(jnp.float32)
        m = jnp.max(logits, axis=-1)
        lse = jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)) + m
        gold = jnp.take_along_axis(logits, ycb[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = scan_or_unroll(chunk, jnp.zeros((), jnp.float32),
                              jnp.arange(n_chunks), n_chunks, cfg.scan_layers)
    return total / T


def train_forward(params: Params, batch: dict, cfg: ModelConfig,
                  aux_coef: float = 1e-2):
    tokens = batch["tokens"]
    labels = batch["labels"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h = embed_tokens(params, tokens, cfg, batch.get("patch_embeds"))
    h, aux = backbone(params, h, cfg, positions)
    loss = lm_loss(params, h, labels, cfg)
    if cfg.family == "moe":
        loss = loss + aux_coef * aux["moe_aux"]
    return loss, aux


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prefill(params: Params, tokens, cfg: ModelConfig, max_len: int | None = None,
            patch_embeds=None, true_len=None):
    """Full-sequence causal forward that also fills a KV cache.
    Returns (last-position logits, cache).

    ``true_len`` (B,) enables bucketed ragged prefill: ``tokens`` may be
    right-padded to a shape bucket, logits are gathered at each row's true
    last position, and ``cache["length"]`` comes back as a per-row vector.
    Causal masking makes the pad positions inert for every real position,
    so a bucketed prefill is numerically identical to an exact-length one
    at the real positions."""
    B, S = tokens.shape
    max_len = max_len or S
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h = embed_tokens(params, tokens, cfg, patch_embeds)

    def body(carry, lp):
        x = carry
        a_in = apply_norm(lp["norm1"], x, cfg)
        attn_out, kv = apply_attention(lp["attn"], a_in, cfg, positions,
                                       return_kv=True)
        x = x + attn_out
        m_in = apply_norm(lp["norm2"], x, cfg)
        if cfg.family == "moe":
            Bq, Sq, D = m_in.shape
            y2d, _ = apply_moe(lp["mlp"], m_in.reshape(Bq * Sq, D), cfg)
            mlp_out = y2d.reshape(Bq, Sq, D)
        else:
            mlp_out = apply_mlp(lp["mlp"], m_in, cfg)
        return x + mlp_out, (kv["k"].astype(cfg.dtype), kv["v"].astype(cfg.dtype))

    body = _maybe_remat(body, cfg)
    h, (ks, vs) = scan_or_unroll(body, h, params["layers"], cfg.n_layers,
                                 cfg.scan_layers)
    h = apply_norm(params["final_norm"], h, cfg)
    if true_len is None:
        last = h[:, -1]
        length = jnp.asarray(S, jnp.int32)
    else:
        tl = jnp.asarray(true_len, jnp.int32)           # (B,)
        last = jnp.take_along_axis(h, (tl - 1)[:, None, None], axis=1)[:, 0]
        length = tl
    logits = (last @ _head_matrix(params, cfg)).astype(jnp.float32)
    if max_len > S:
        pad = [(0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0)]
        ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
    cache = {"k": ks, "v": vs, "length": length}
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> Params:
    """KV page pool shared by all slots: per layer, ``num_pages`` fixed-size
    pages.  The serving layer owns the page tables (see
    :class:`repro.serve.kv.PagedKV`); the decode step only consumes them."""
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.dh)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _decode_layer(h, xs, cfg: ModelConfig, positions, length,
                  page_table=None, kv_limit=None, pool=None):
    lp, ck, cv, *layer = xs
    a_in = apply_norm(lp["norm1"], h, cfg)
    layer_cache = {"k": ck, "v": cv, "length": length}
    if page_table is not None:
        layer_cache["page_table"] = page_table
        layer_cache["kv_limit"] = kv_limit
    if pool is not None:
        layer_cache["pool"] = (*pool, layer[0])
    attn_out, new_cache = apply_attention(lp["attn"], a_in, cfg, positions,
                                          cache=layer_cache)
    h = h + attn_out
    m_in = apply_norm(lp["norm2"], h, cfg)
    if cfg.family == "moe":
        B, S, D = m_in.shape
        y2d, _ = apply_moe(lp["mlp"], m_in.reshape(B * S, D), cfg)
        mlp_out = y2d.reshape(B, S, D)
    else:
        mlp_out = apply_mlp(lp["mlp"], m_in, cfg)
    return h + mlp_out, (new_cache["k"], new_cache["v"])


def decode_step(params: Params, cache: Params, tokens, cfg: ModelConfig,
                patch_embeds=None, kv_limit=None):
    """tokens: (B, S_new) — S_new=1 for pure decode; larger for prefill.
    Returns (logits_last, new_cache).

    A cache carrying ``page_table`` selects the paged path: ``k``/``v`` are
    the shared page pool and each slot reads/writes through its page-table
    row.  On the gather path (:func:`paged_attention_path`) ``kv_limit``
    (python int) slices the gathered per-slot view back to the engine's
    max_len so the attention reduction shape — and hence the tokens — match
    the dense layout bitwise.  On the kernel path each layer reads the
    whole stacked pool, a loop invariant, at its own layer index."""
    B, S = tokens.shape
    length = cache["length"]
    page_table = cache.get("page_table")
    base = length[:, None] if jnp.ndim(length) else length   # ragged: (B,) offsets
    positions = base + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h = embed_tokens(params, tokens, cfg, patch_embeds)

    xs = (params["layers"], cache["k"], cache["v"])
    pool = None
    if page_table is not None and \
            paged_attention_path(cfg, cache["k"].shape) == "kernel":
        pool = (cache["k"], cache["v"])
        xs += (jnp.arange(cfg.n_layers, dtype=jnp.int32),)

    def body(carry, xs):
        return _decode_layer(carry, xs, cfg, positions, length,
                             page_table=page_table, kv_limit=kv_limit,
                             pool=pool)

    h, (nk, nv) = scan_or_unroll(body, h, xs, cfg.n_layers, cfg.scan_layers)
    h = apply_norm(params["final_norm"], h, cfg)
    logits = (h[:, -1] @ _head_matrix(params, cfg)).astype(jnp.float32)
    new_cache = {"k": nk, "v": nv, "length": length + S}
    if page_table is not None:
        new_cache["page_table"] = page_table
    return logits, new_cache
