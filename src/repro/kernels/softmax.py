"""Fused scaled/masked softmax kernel (Pallas TPU).

The classic warp-composition pattern (paper Fig. 5(c)): scale + mask + max +
exp + sum + div in one kernel; row statistics stay in VREG.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

from .norms import DEFAULT_BLOCK_ROWS, _row_grid


def _softmax_kernel(x_ref, o_ref, *, scale: float):
    x = x_ref[...].astype(jnp.float32) * scale
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _softmax_masked_kernel(x_ref, m_ref, o_ref, *, scale: float):
    x = x_ref[...].astype(jnp.float32) * scale
    x = jnp.where(m_ref[...], x, -jnp.inf)
    mx = jnp.max(x, axis=-1, keepdims=True)
    # rows that are fully masked: keep exp(-inf - -inf)=exp(nan) out
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    e = jnp.exp(x - mx)
    s = jnp.sum(e, axis=-1, keepdims=True)
    o_ref[...] = (e / jnp.maximum(s, 1e-30)).astype(o_ref.dtype)


def softmax(x, scale: float = 1.0, mask=None, *,
            block_rows: int = DEFAULT_BLOCK_ROWS):
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    grid, br = _row_grid(x2.shape, block_rows)
    if mask is None:
        out = pl.pallas_call(
            functools.partial(_softmax_kernel, scale=scale),
            grid=grid,
            in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            interpret=kernels.interpret_mode(),
        )(x2)
    else:
        m2 = jnp.broadcast_to(mask, orig_shape).reshape(-1, d)
        out = pl.pallas_call(
            functools.partial(_softmax_masked_kernel, scale=scale),
            grid=grid,
            in_specs=[
                pl.BlockSpec((br, d), lambda i: (i, 0)),
                pl.BlockSpec((br, d), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            interpret=kernels.interpret_mode(),
        )(x2, m2)
    return out.reshape(orig_shape)
