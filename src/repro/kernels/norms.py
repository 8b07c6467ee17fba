"""Fused normalization kernels (Pallas TPU).

The archetypal FusionStitching win: RMSNorm / LayerNorm are
elementwise+row-reduction chains (paper §5.1 "warp composition" — here VPU
sublane/lane composition).  The fused kernel reads the activation once from
HBM and writes once; the row statistics never leave VREG.

``rmsnorm_residual`` additionally stitches the residual add (the paper's
kernel-packing of the pre-norm transformer's ``x + attn_out`` with the norm
that follows), saving one full round-trip of the hidden tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

DEFAULT_BLOCK_ROWS = 256


def _row_grid(shape2d, block_rows):
    rows = shape2d[0]
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    return (rows // br,), br


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, gamma, eps: float = 1e-6, *, block_rows: int = DEFAULT_BLOCK_ROWS):
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    grid, br = _row_grid(x2.shape, block_rows)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=kernels.interpret_mode(),
    )(x2, gamma)
    return out.reshape(orig_shape)


def _rmsnorm_residual_kernel(x_ref, r_ref, g_ref, o_ref, res_ref, *, eps: float):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    res_ref[...] = s.astype(res_ref.dtype)
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    o_ref[...] = (s * jax.lax.rsqrt(var + eps) * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_residual(x, res, gamma, eps: float = 1e-6, *,
                     block_rows: int = DEFAULT_BLOCK_ROWS):
    orig_shape = x.shape
    d = x.shape[-1]
    x2, r2 = x.reshape(-1, d), res.reshape(-1, d)
    grid, br = _row_grid(x2.shape, block_rows)
    normed, new_res = pl.pallas_call(
        functools.partial(_rmsnorm_residual_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
        ],
        interpret=kernels.interpret_mode(),
    )(x2, r2, gamma)
    return normed.reshape(orig_shape), new_res.reshape(orig_shape)


def _layernorm_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    o_ref[...] = ((x - mu) * jax.lax.rsqrt(var + eps) * g_ref[...] + b_ref[...]).astype(o_ref.dtype)


def layernorm(x, gamma, beta, eps: float = 1e-5, *,
              block_rows: int = DEFAULT_BLOCK_ROWS):
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    grid, br = _row_grid(x2.shape, block_rows)
    out = pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=kernels.interpret_mode(),
    )(x2, gamma, beta)
    return out.reshape(orig_shape)
