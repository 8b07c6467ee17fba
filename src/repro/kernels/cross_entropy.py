"""Fused cross-entropy kernel (Pallas TPU) — blocked online logsumexp.

For LM heads the logits tensor (tokens x vocab, vocab up to 256k here) is
the single largest activation in the step; the unfused path reads it 3-4x
(max, exp-sum, gather, grad). This kernel streams vocab blocks through VMEM
once, maintaining running (max, sumexp, gold-logit) per row in VMEM scratch
across the vocab-block grid dimension — the paper's block composition with
cross-block accumulation.

Returns per-row NLL; the (tiny) mean is taken by the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _xent_kernel(x_ref, lbl_ref, loss_ref, m_scr, l_scr, g_scr, *, nv: int, vb: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        g_scr[...] = jnp.zeros(g_scr.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)                       # (br, vb)
    lbl = lbl_ref[...]                                        # (br,)
    cols = iv * vb + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    local_m = jnp.max(x, axis=-1)
    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, local_m)
    l_scr[...] = l_scr[...] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(x - m_new[:, None]), axis=-1)
    m_scr[...] = m_new
    hit = cols == lbl[:, None].astype(jnp.int32)
    g_scr[...] = g_scr[...] + jnp.sum(jnp.where(hit, x, 0.0), axis=-1)

    @pl.when(iv == nv - 1)
    def _fin():
        loss_ref[...] = (m_scr[...] + jnp.log(l_scr[...]) - g_scr[...]).astype(
            loss_ref.dtype)


def cross_entropy(logits, labels, *, block_rows: int = 128,
                  block_vocab: int = 2048):
    """logits (B, V), labels (B,) -> mean NLL (scalar)."""
    B, V = logits.shape
    br = min(block_rows, B)
    while B % br:
        br -= 1
    vb = min(block_vocab, V)
    while V % vb:
        vb -= 1
    nv = V // vb
    per_row = pl.pallas_call(
        functools.partial(_xent_kernel, nv=nv, vb=vb),
        grid=(B // br, nv),
        in_specs=[
            pl.BlockSpec((br, vb), lambda i, j: (i, j)),
            pl.BlockSpec((br,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((br,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((B,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((br,), jnp.float32),
            pltpu.VMEM((br,), jnp.float32),
            pltpu.VMEM((br,), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
    )(logits, labels.astype(jnp.int32))
    return jnp.mean(per_row)
