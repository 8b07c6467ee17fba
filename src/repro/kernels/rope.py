"""Rotary position embedding kernel (Pallas TPU).

Stitches the cos/sin table computation with the rotation: the (L, half)
angle tables are recomputed in VREG from the position block (compute is
free; HBM traffic is the bottleneck), so the kernel reads q/k once and
writes once — vs. the unfused path that materializes cos/sin and the two
rotated halves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _rope_kernel(x_ref, pos_ref, o_ref, *, theta: float, half: int):
    x = x_ref[...].astype(jnp.float32)          # (br, H*Dh) flattened heads
    pos = pos_ref[...].astype(jnp.float32)      # (br,)
    n_heads = x.shape[-1] // (2 * half)
    x = x.reshape(x.shape[0], n_heads, 2 * half)
    freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos[:, None] * freq[None, :]          # (br, half)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def rope(x, positions, theta: float = 10000.0, *, block_rows: int = 256):
    """x: (B, L, H, Dh); positions: (B, L).  Returns rotated x."""
    B, L, H, Dh = x.shape
    half = Dh // 2
    x2 = x.reshape(B * L, H * Dh)
    p2 = positions.reshape(B * L)
    rows = B * L
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    out = pl.pallas_call(
        functools.partial(_rope_kernel, theta=theta, half=half),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, H * Dh), lambda i: (i, 0)),
            pl.BlockSpec((br,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((br, H * Dh), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=kernels.interpret_mode(),
    )(x2, p2)
    return out.reshape(B, L, H, Dh)
