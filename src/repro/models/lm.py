"""Decoder-only transformer LM — dense, MoE, and VLM-backbone families.

Layers are scanned (``jax.lax.scan`` over stacked parameters) so HLO size is
O(1) in depth; remat policy comes from the config.  One layer body
(:func:`_block`) and one walk over the layer stacks (:func:`_walk`) serve:

* ``train_forward``  — full-sequence causal, returns mean-token CE loss;
* ``prefill``        — full-sequence causal, fills a KV cache;
* ``decode_step``    — single-token step against a static-shape KV cache.

What differs between grouped-query and latent attention (the attention,
what a decode layer reads from xs and returns in ys, how the cache is made
and written) sits behind one seam, :func:`repro.models.layers.attention_kind`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    Params,
    scan_or_unroll,
    apply_mlp,
    apply_moe,
    apply_moe_dense,
    apply_moe_share,
    apply_norm,
    attention_kind,
    init_mlp,
    init_moe,
    init_norm,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, dense: bool = False) -> Params:
    """One layer; ``dense`` gives a MoE model's leading dense-MLP layer."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "norm1": init_norm(k1, cfg),
        "attn": attention_kind(cfg).init(k2, cfg),
        "norm2": init_norm(k3, cfg),
        "mlp": init_moe(k4, cfg) if _routed(cfg, dense) else init_mlp(k4, cfg),
    }


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
# the layer body and the walk over the stacks
# ---------------------------------------------------------------------------

def _empty_aux():
    return {"moe_aux": jnp.zeros((), jnp.float32),
            "moe_drop_frac": jnp.zeros((), jnp.float32)}


def _routed(cfg: ModelConfig, dense: bool = False) -> bool:
    """Whether a layer's FFN routes tokens to experts: a MoE model's, past
    its leading dense layers."""
    return cfg.family == "moe" and not dense


def _ffn(p: Params, x, cfg: ModelConfig, dense: bool = False,
         soft: bool = False):
    """A layer's FFN on x (B, S, D): (out, aux, load (2,) or None).  The
    MLP of a dense model or layer; else the experts: the dropless share
    (``moe.held``), or with ``soft`` every expert on every token
    (:func:`apply_moe_dense`, whose independent per-expert chains the
    horizontal packer bins), or the capacity dispatch."""
    if not _routed(cfg, dense):
        return apply_mlp(p, x, cfg), _empty_aux(), None
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    load = None
    if cfg.moe.held is not None:
        y, aux, load = apply_moe_share(p, x2, cfg)
    elif soft:
        y, aux = apply_moe_dense(p, x2, cfg), _empty_aux()
    else:
        y, aux = apply_moe(p, x2, cfg)
    return y.reshape(B, S, D), aux, load


def _block(h, lp: Params, cfg: ModelConfig, attend, ffn):
    """One layer: norm, ``attend(p, x) -> (out, y)``, residual, norm,
    ``ffn(p, x) -> (out, aux, load)``, residual: (h, y, aux, load)."""
    a_in = apply_norm(lp["norm1"], h, cfg)
    attn_out, y = attend(lp["attn"], a_in)
    h = h + attn_out
    m_in = apply_norm(lp["norm2"], h, cfg)
    mlp_out, aux, load = ffn(lp["mlp"], m_in)
    return h + mlp_out, y, aux, load


def block_forward(lp: Params, x, cfg: ModelConfig):
    """Pure single-block forward — the ``Model.block_fn`` stitching entry
    (see examples/stitch_fn.py).  x: (B, S, D) -> (B, S, D).

    MoE blocks use the *dense* expert form (:func:`apply_moe_dense`): the
    sort-based capacity dispatch is gather/scatter-partitioned anyway, while
    the dense form exposes E independent per-expert chains for the
    horizontal packer."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    attend = functools.partial(attention_kind(cfg).forward, cfg=cfg,
                               positions=positions, rows=False)
    x, *_ = _block(x, lp, cfg, attend,
                   functools.partial(_ffn, cfg=cfg, soft=True))
    return x


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def _stacks(params: Params, cfg: ModelConfig):
    """The layer stacks in order, as (params, first layer, count, dense
    MLP): the leading dense layers, then the rest."""
    nd = cfg.n_dense_layers
    first = [(params["dense_layers"], 0, nd, True)] if nd else []
    return first + [(params["layers"], nd, cfg.n_layers - nd, False)]


def _walk(params: Params, h, cfg: ModelConfig, layer, xs=lambda first, n: (),
          remat: bool = False):
    """Scan ``layer(h, (lp, *x), dense) -> (h, y)`` over each stack in
    turn, ``x`` from ``xs(first, n)``: (h, [each stack's ys])."""
    ys = []
    for stack, first, n, dense in _stacks(params, cfg):
        body = functools.partial(layer, dense=dense)
        if remat:
            body = _maybe_remat(body, cfg)
        h, y = scan_or_unroll(body, h, (stack, *xs(first, n)), n,
                              cfg.scan_layers)
        ys.append(y)
    return h, ys


def _cat(ys):                   # the stacks' ys, joined along the layers
    return jax.tree.map(lambda *a: jnp.concatenate(a), *ys)


def _sequence(params: Params, h, cfg: ModelConfig, positions, train: bool):
    """Every layer over h (B, S, D), causal: (h before the final norm,
    (cache rows {leaf: (L, B, S, ...)}, aux per layer)), either None where
    the attention kind does not keep it.  Only training hints shardings."""
    kind = attention_kind(cfg)
    attend = functools.partial(kind.forward, cfg=cfg, positions=positions,
                               rows=not train)

    def layer(h, xs, dense):
        (lp,) = xs
        if train and cfg.shard_activations:
            from .sharding import hint_rows
            h = hint_rows(h)
        h, rows, aux, _ = _block(h, lp, cfg, attend,
                                 functools.partial(_ffn, cfg=cfg, dense=dense))
        rows = jax.tree.map(lambda r: r.astype(cfg.dtype), rows)
        return h, kind.seq_ys(rows, aux)

    h, ys = _walk(params, h, cfg, layer, remat=True)
    return h, _cat(ys)


def backbone(params: Params, h, cfg: ModelConfig, positions):
    """h: (B, S, D) embeddings -> (B, S, D) final-normed hidden, aux."""
    h, (_, auxs) = _sequence(params, h, cfg, positions, train=True)
    h = apply_norm(params["final_norm"], h, cfg)
    return h, jax.tree.map(jnp.mean, auxs)


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
    if _routed(cfg):
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
    h, (rows, _) = _sequence(params, h, cfg, positions, train=False)
    logits, length = _last_logits(params, h, cfg, true_len)
    if max_len > S:
        rows = jax.tree.map(lambda r: jnp.pad(
            r, [(0, 0), (0, 0), (0, max_len - S)] + [(0, 0)] * (r.ndim - 3)),
            rows)
    return logits, {**rows, "length": length}


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
    return attention_kind(cfg).init_cache(cfg, batch, max_len)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> Params:
    """KV page pool shared by all slots: per layer, ``num_pages`` fixed-size
    pages, in the attention kind's leaves.  The serving layer owns the page
    tables (see :class:`repro.serve.kv.PagedKV`); the decode step only
    consumes them."""
    return attention_kind(cfg).init_paged_cache(cfg, num_pages, page_size)


def decode_step(params: Params, cache: Params, tokens, cfg: ModelConfig,
                patch_embeds=None, kv_limit=None):
    """tokens: (B, S_new) — S_new=1 for pure decode; larger for prefill.
    Returns (logits_last, new_cache).

    A cache carrying ``page_table`` selects the paged path: its leaves are
    the shared page pool and each slot reads/writes through its page-table
    row.  On the gather path (:func:`repro.models.layers.attention_path`)
    ``kv_limit`` (python int) slices the gathered per-slot view back to the
    engine's max_len so the attention reduction shape — and hence the
    tokens — match the dense layout bitwise.  The attention kind decides
    what each layer reads and returns and how the cache is written after
    the walk.  With a dropless expert share the new cache also carries
    ``moe_load`` (2,): the (token, expert) pairs routed to held experts,
    summed over layers, and the most pairs on one held expert in one
    layer."""
    kind = attention_kind(cfg)
    B, S = tokens.shape
    positions, length = kind.positions(cache, B, S)
    view = {**cache, "length": length, "kv_limit": kv_limit}
    h = embed_tokens(params, tokens, cfg, patch_embeds)

    def layer(h, xs, dense):
        lp, *at = xs
        h, row, _, load = _block(
            h, lp, cfg, lambda p, x: kind.decode(p, x, cfg, positions, view, at),
            functools.partial(_ffn, cfg=cfg, dense=dense))
        return h, (row, load)

    h, ys = _walk(params, h, cfg, layer,
                  functools.partial(kind.decode_xs, cfg, view))
    h = apply_norm(params["final_norm"], h, cfg)
    logits = (h[:, -1] @ _head_matrix(params, cfg)).astype(jnp.float32)
    new_cache = {**kind.write(view, _cat([row for row, _ in ys])),
                 "length": cache["length"] + S}
    if "page_table" in cache:
        new_cache["page_table"] = cache["page_table"]
    loads = [load for _, load in ys if load is not None]
    if loads:
        load = jnp.concatenate(loads)
        new_cache["moe_load"] = jnp.stack([jnp.sum(load[:, 0]),
                                           jnp.max(load[:, 1])])
    return logits, new_cache
