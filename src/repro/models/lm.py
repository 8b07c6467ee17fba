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
    apply_mla,
    apply_mlp,
    apply_moe,
    apply_moe_dense,
    apply_moe_share,
    apply_norm,
    init_attention,
    init_mla,
    init_mlp,
    init_moe,
    init_norm,
    mla_decode,
    paged_attention_path,
    write_latent,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, dense: bool = False) -> Params:
    """One layer; ``dense`` gives a MoE model's leading dense-MLP layer."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "norm1": init_norm(k1, cfg),
        "attn": (init_mla(k2, cfg) if cfg.mla is not None
                 else init_attention(k2, cfg)),
        "norm2": init_norm(k3, cfg),
    }
    if cfg.family == "moe" and not dense:
        p["mlp"] = init_moe(k4, cfg)
    else:
        p["mlp"] = init_mlp(k4, cfg)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    nd = cfg.n_dense_layers
    layers = jax.vmap(lambda k: init_layer(k, cfg))(
        layer_keys[nd:] if nd else layer_keys)
    p = {
        "embed": (jax.random.normal(ke, (cfg.vocab, cfg.d_model), jnp.float32)
                  * 0.02).astype(cfg.param_dtype),
        "layers": layers,
        "final_norm": init_norm(kh, cfg),
    }
    if nd:
        p["dense_layers"] = jax.vmap(
            lambda k: init_layer(k, cfg, dense=True))(layer_keys[:nd])
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
    if cfg.mla is not None:
        h, _, auxs = _mla_forward(params, h, cfg, positions)
        h = apply_norm(params["final_norm"], h, cfg)
        return h, jax.tree.map(jnp.mean, auxs)
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
    if cfg.mla is not None:
        return _mla_prefill(params, h, cfg, max_len, positions, true_len)

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
    logits, length = _last_logits(params, h, cfg, true_len)
    if max_len > S:
        pad = [(0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0)]
        ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
    cache = {"k": ks, "v": vs, "length": length}
    return logits, cache


def _last_logits(params: Params, h, cfg: ModelConfig, true_len):
    """Final norm, then float32 logits at each row's last position (its
    true one with ``true_len``) and the cache length."""
    S = h.shape[1]
    h = apply_norm(params["final_norm"], h, cfg)
    if true_len is None:
        last = h[:, -1]
        length = jnp.asarray(S, jnp.int32)
    else:
        tl = jnp.asarray(true_len, jnp.int32)           # (B,)
        last = jnp.take_along_axis(h, (tl - 1)[:, None, None], axis=1)[:, 0]
        length = tl
    return (last @ _head_matrix(params, cfg)).astype(jnp.float32), length


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    if cfg.mla is not None:
        return {"lat": jnp.zeros((cfg.n_layers, batch, max_len,
                                  cfg.mla.latent_dim), cfg.dtype),
                "length": jnp.zeros((), jnp.int32)}
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def rope_pack(rope_dim: int) -> int:
    """Pages whose rope parts share one 128-lane row of the latent pool's
    ``rope`` leaf (1 where ``rope_dim`` does not divide 128)."""
    return 128 // rope_dim if 128 % rope_dim == 0 else 1


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> Params:
    """KV page pool shared by all slots: per layer, ``num_pages`` fixed-size
    pages.  The serving layer owns the page tables (see
    :class:`repro.serve.kv.PagedKV`); the decode step only consumes them.
    Latent attention keeps its rows as two leaves, so that a page of each
    is whole (sublane, lane) tiles that one DMA copies and no dimension is
    the odd rank+rope: ``lat`` (L, P, page, rank) and ``rope`` (L, ceil(P /
    pack), page, pack * rope), where ``pack`` pages' rope parts lie side by
    side in a 128-lane row (page p at row p // pack, lanes (p % pack) *
    rope on).  Together they hold exactly rank+rope values a token."""
    if cfg.mla is not None:
        a = cfg.mla
        pack = rope_pack(a.qk_rope_dim)
        return {"lat": jnp.zeros((cfg.n_layers, num_pages, page_size,
                                  a.kv_rank), cfg.dtype),
                "rope": jnp.zeros((cfg.n_layers, -(-num_pages // pack),
                                   page_size, pack * a.qk_rope_dim),
                                  cfg.dtype)}
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
    if cfg.mla is not None:
        return _mla_decode_step(params, cache, tokens, cfg, kv_limit)
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


# ---------------------------------------------------------------------------
# latent attention and leading dense layers (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _stacks(params: Params, cfg: ModelConfig):
    """The layer stacks in order, as (params, first layer, count, dense
    MLP): the leading dense layers, then the rest."""
    nd = cfg.n_dense_layers
    first = [(params["dense_layers"], 0, nd, True)] if nd else []
    return first + [(params["layers"], nd, cfg.n_layers - nd, False)]


def _ffn(p: Params, x, cfg: ModelConfig, dense: bool):
    """A layer's MLP on x (B, S, D): (out, aux, load (2,) or None)."""
    if dense or cfg.family != "moe":
        return apply_mlp(p, x, cfg), _empty_aux(), None
    B, S, D = x.shape
    if cfg.moe.held is not None:
        y, aux, load = apply_moe_share(p, x.reshape(B * S, D), cfg)
    else:
        (y, aux), load = apply_moe(p, x.reshape(B * S, D), cfg), None
    return y.reshape(B, S, D), aux, load


def _mla_forward(params: Params, h, cfg: ModelConfig, positions):
    """Every stack over h (B, S, D), full-sequence: (h before the final
    norm, latent rows (L, B, S, rank+rope), aux per layer)."""
    lats, auxs = [], []
    for stack, _, n, dense in _stacks(params, cfg):
        def body(carry, lp, dense=dense):
            a_in = apply_norm(lp["norm1"], carry, cfg)
            attn_out, lat = apply_mla(lp["attn"], a_in, cfg, positions)
            carry = carry + attn_out
            m_in = apply_norm(lp["norm2"], carry, cfg)
            mlp_out, aux, _ = _ffn(lp["mlp"], m_in, cfg, dense)
            return carry + mlp_out, (lat.astype(cfg.dtype), aux)

        h, (lat, aux) = scan_or_unroll(_maybe_remat(body, cfg), h, stack, n,
                                       cfg.scan_layers)
        lats.append(lat)
        auxs.append(aux)
    return (h, jnp.concatenate(lats),
            jax.tree.map(lambda *a: jnp.concatenate(a), *auxs))


def _mla_prefill(params: Params, h, cfg: ModelConfig, max_len: int,
                 positions, true_len):
    """:func:`prefill` of a latent-attention model: the cache holds the
    latent rows, ``lat`` (L, B, max_len, rank+rope)."""
    S = h.shape[1]
    h, lat, _ = _mla_forward(params, h, cfg, positions)
    logits, length = _last_logits(params, h, cfg, true_len)
    if max_len > S:
        lat = jnp.pad(lat, [(0, 0), (0, 0), (0, max_len - S), (0, 0)])
    return logits, {"lat": lat, "length": length}


def _mla_decode_step(params: Params, cache: Params, tokens, cfg: ModelConfig,
                     kv_limit=None):
    """:func:`decode_step` of a latent-attention model.  Each layer reads
    the latent store's leaves as loop invariants (:func:`mla_decode`, at
    its own layer index, counted across the stacks) and returns its row;
    one write after the stacks puts every layer's row in place
    (:func:`write_latent`).  With a dropless expert share the new cache
    also carries ``moe_load`` (2,): the (token, expert) pairs routed to
    held experts, summed over layers, and the most pairs on one held
    expert in one layer."""
    B, S = tokens.shape
    length = cache["length"]
    lengths = jnp.broadcast_to(length, (B,))
    positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    h = embed_tokens(params, tokens, cfg)
    view = {name: cache[name] for name in ("lat", "rope", "page_table")
            if name in cache}
    view["length"] = lengths
    if "page_table" in cache:
        view["kv_limit"] = kv_limit
    rows, loads = [], []
    for stack, first, n, dense in _stacks(params, cfg):
        def body(carry, xs, dense=dense):
            lp, layer = xs
            a_in = apply_norm(lp["norm1"], carry, cfg)
            attn_out, row = mla_decode(lp["attn"], a_in, cfg, positions,
                                       view, layer)
            carry = carry + attn_out
            m_in = apply_norm(lp["norm2"], carry, cfg)
            mlp_out, _, load = _ffn(lp["mlp"], m_in, cfg, dense)
            return carry + mlp_out, (row, load)

        layers = jnp.arange(first, first + n, dtype=jnp.int32)
        h, (row, load) = scan_or_unroll(body, h, (stack, layers), n,
                                        cfg.scan_layers)
        rows.append(row)
        if load is not None:
            loads.append(load)
    h = apply_norm(params["final_norm"], h, cfg)
    logits = (h[:, -1] @ _head_matrix(params, cfg)).astype(jnp.float32)
    new_cache = {**write_latent(view, jnp.concatenate(rows)),
                 "length": length + S}
    if "page_table" in cache:
        new_cache["page_table"] = cache["page_table"]
    if loads:
        load = jnp.concatenate(loads)
        new_cache["moe_load"] = jnp.stack([jnp.sum(load[:, 0]),
                                           jnp.max(load[:, 1])])
    return logits, new_cache
