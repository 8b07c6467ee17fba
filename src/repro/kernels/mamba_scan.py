"""Mamba-1 selective scan kernel (Pallas TPU).

The scan is the paper's "large-granularity dedicated implementation" case
(§6.5: granular enough ops go to the native library; here, to a hand
kernel), with its pointwise halo (softplus/gating) stitched around it by the
fusion planner.

Layout: grid (batch, channel_blocks); each body instance owns a (db,)
channel slab, scans L sequentially with ``lax.fori_loop`` carrying the
(db, N) SSM state in VREG.  Channels sit in lanes (minor dim), the per-step
(db, N) outer products and the (db,) output contraction are VPU-friendly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _mamba_kernel(x_ref, d_ref, A_ref, B_ref, C_ref, D_ref, o_ref, *, L: int):
    A = A_ref[...].astype(jnp.float32)            # (db, N)
    Dd = D_ref[...].astype(jnp.float32)           # (db,)
    db, N = A.shape

    def step(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)          # (db,)
        dt_t = d_ref[0, t, :].astype(jnp.float32)         # (db,)
        B_t = B_ref[0, t, :].astype(jnp.float32)          # (N,)
        C_t = C_ref[0, t, :].astype(jnp.float32)          # (N,)
        dA = jnp.exp(dt_t[:, None] * A)                   # (db, N)
        dBx = (dt_t * x_t)[:, None] * B_t[None, :]        # (db, N)
        h = dA * h + dBx
        y = jnp.sum(h * C_t[None, :], axis=-1)            # (db,)
        o_ref[0, t, :] = (y + Dd * x_t).astype(o_ref.dtype)
        return h

    h0 = jnp.zeros((db, N), jnp.float32)
    jax.lax.fori_loop(0, L, step, h0)


def mamba_scan(x, delta, A, B, C, D, *, block_channels: int = 256):
    """x, delta: (Bb, L, Dm); A: (Dm, N); B, C: (Bb, L, N); D: (Dm,)."""
    Bb, L, Dm = x.shape
    N = A.shape[1]
    db = min(block_channels, Dm)
    while Dm % db:
        db -= 1
    out = pl.pallas_call(
        functools.partial(_mamba_kernel, L=L),
        grid=(Bb, Dm // db),
        in_specs=[
            pl.BlockSpec((1, L, db), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, L, db), lambda b, c: (b, 0, c)),
            pl.BlockSpec((db, N), lambda b, c: (c, 0)),
            pl.BlockSpec((1, L, N), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, L, N), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((db,), lambda b, c: (c,)),
        ],
        out_specs=pl.BlockSpec((1, L, db), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=kernels.interpret_mode(),
    )(x, delta, A, B, C, D)
    return out
