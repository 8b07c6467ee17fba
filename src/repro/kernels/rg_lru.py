"""RG-LRU recurrence kernel (Pallas TPU) — RecurrentGemma's gated linear
recurrence.

The gate chain (two sigmoids, softplus, exp, sqrt) is stitched with the
recurrence itself: one read of (x, gates), one write of h, gates never
materialize in HBM.  Grid (batch, channel_blocks); diagonal recurrence means
each channel slab scans independently with a (db,) VREG carry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _rglru_kernel(x_ref, ig_ref, rg_ref, lam_ref, o_ref, *, L: int, c: float):
    lam = jax.nn.softplus(lam_ref[...].astype(jnp.float32))     # (db,)
    db = lam.shape[0]

    def step(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)
        ig_t = jax.nn.sigmoid(ig_ref[0, t, :].astype(jnp.float32))
        rg_t = jax.nn.sigmoid(rg_ref[0, t, :].astype(jnp.float32))
        log_a = -c * lam * rg_t
        a = jnp.exp(log_a)
        mult = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
        h = a * h + mult * (ig_t * x_t)
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    jax.lax.fori_loop(0, L, step, jnp.zeros((db,), jnp.float32))


def rg_lru(x, input_gate, rec_gate, Lambda, c: float = 8.0, *,
           block_channels: int = 512):
    """x, input_gate, rec_gate: (B, L, D); Lambda: (D,)."""
    B, L, D = x.shape
    db = min(block_channels, D)
    while D % db:
        db -= 1
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, L=L, c=c),
        grid=(B, D // db),
        in_specs=[
            pl.BlockSpec((1, L, db), lambda b, ch: (b, 0, ch)),
            pl.BlockSpec((1, L, db), lambda b, ch: (b, 0, ch)),
            pl.BlockSpec((1, L, db), lambda b, ch: (b, 0, ch)),
            pl.BlockSpec((db,), lambda b, ch: (ch,)),
        ],
        out_specs=pl.BlockSpec((1, L, db), lambda b, ch: (b, 0, ch)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=kernels.interpret_mode(),
    )(x, input_gate, rec_gate, Lambda)
    return out
