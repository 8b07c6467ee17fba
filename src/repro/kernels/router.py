"""MoE router kernel (Pallas TPU): fused softmax + top-k + renormalize.

Router logits are tiny per token but the op chain (softmax -> top-k ->
renorm -> scatter metadata) dispatches 4+ kernels in the unfused path, and
at MoE train batch sizes (256 x 4096 tokens) the intermediates are hundreds
of MB.  One kernel, one read, two small writes.  Top-k (k <= 8, E <= 64)
is k rounds of max+mask on the VPU — argmax via iota compare, no sort.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _router_kernel(x_ref, w_ref, i_ref, *, k: int, renorm: bool):
    x = x_ref[...].astype(jnp.float32)                    # (br, E)
    br, E = x.shape
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)

    cols = jax.lax.broadcasted_iota(jnp.int32, (br, E), 1)
    ws = []
    ids = []
    for _ in range(k):
        w = jnp.max(probs, axis=-1)                        # (br,)
        idx = jnp.argmax(probs, axis=-1).astype(jnp.int32)  # (br,)
        ws.append(w)
        ids.append(idx)
        probs = jnp.where(cols == idx[:, None], -1.0, probs)
    W = jnp.stack(ws, axis=-1)                             # (br, k)
    I = jnp.stack(ids, axis=-1)
    if renorm:
        W = W / jnp.maximum(jnp.sum(W, axis=-1, keepdims=True), 1e-30)
    w_ref[...] = W.astype(w_ref.dtype)
    i_ref[...] = I


def topk_router(logits, k: int, renormalize: bool = True, *,
                block_rows: int = 1024):
    """logits (T, E) -> (weights (T, k), indices (T, k) int32)."""
    T, E = logits.shape
    br = min(block_rows, T)
    while T % br:
        br -= 1
    weights, idx = pl.pallas_call(
        functools.partial(_router_kernel, k=k, renorm=renormalize),
        grid=(T // br,),
        in_specs=[pl.BlockSpec((br, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), logits.dtype),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
        ],
        interpret=kernels.interpret_mode(),
    )(logits)
    return weights, idx
