"""Fused MLP activation kernels (Pallas TPU): SwiGLU, GeGLU, squared-ReLU.

Pure thread-composition (VREG) stitches: two reads + one write instead of the
unfused 4-5 HBM round-trips (silu -> mul; gelu -> mul; relu -> square).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

from .norms import DEFAULT_BLOCK_ROWS, _row_grid


def _glu_kernel(g_ref, u_ref, o_ref, *, act: str):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    if act == "silu":
        a = g * jax.nn.sigmoid(g)
    else:  # gelu (tanh approx is fine for both archs using GeGLU)
        a = jax.nn.gelu(g)
    o_ref[...] = (a * u).astype(o_ref.dtype)


def _glu(gate, up, act: str, block_rows: int):
    orig_shape = gate.shape
    d = gate.shape[-1]
    g2, u2 = gate.reshape(-1, d), up.reshape(-1, d)
    grid, br = _row_grid(g2.shape, block_rows)
    import functools
    out = pl.pallas_call(
        functools.partial(_glu_kernel, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(g2.shape, gate.dtype),
        interpret=kernels.interpret_mode(),
    )(g2, u2)
    return out.reshape(orig_shape)


def swiglu(gate, up, *, block_rows: int = DEFAULT_BLOCK_ROWS):
    return _glu(gate, up, "silu", block_rows)


def geglu(gate, up, *, block_rows: int = DEFAULT_BLOCK_ROWS):
    return _glu(gate, up, "gelu", block_rows)


def _sqrelu_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    r = jnp.maximum(x, 0.0)
    o_ref[...] = (r * r).astype(o_ref.dtype)


def squared_relu(x, *, block_rows: int = DEFAULT_BLOCK_ROWS):
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    grid, br = _row_grid(x2.shape, block_rows)
    out = pl.pallas_call(
        _sqrelu_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=kernels.interpret_mode(),
    )(x2)
    return out.reshape(orig_shape)
