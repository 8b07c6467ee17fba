"""Flash attention (Pallas TPU) with GQA, causal and local-window masking.

Online-softmax blocked attention: grid (batch, q_head, q_blocks, kv_blocks)
with the kv dimension innermost; running (m, l, acc) live in VMEM scratch
across kv blocks (paper block composition + cross-block accumulation — the
same mechanism the generic stitched emitter uses, hand-tuned for the MXU:
the two dots per block are (qb, dh) @ (dh, kb) and (qb, kb) @ (kb, dh),
both MXU-aligned for qb = kb = 128, dh in {64, 128}).

GQA is handled in the BlockSpec index maps: the kv block loaded for q-head h
is kv-head ``h // group`` — no repeat/materialization of K/V.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  qb: int, kb: int, nk: int, q_offset: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)                  # (qb, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (kb, dh)
    v = v_ref[0, 0].astype(jnp.float32)                  # (kb, dh)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (qb, kb)

    iq = pl.program_id(2)
    qpos = q_offset + iq * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    kpos = ik * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    mask = jnp.ones((qb, kb), dtype=bool)
    if causal:
        mask = mask & (qpos >= kpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_old - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    window: int | None = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q: (B, Lq, Hq, Dh); k, v: (B, Lkv, Hkv, Dh) -> (B, Lq, Hq, Dh).

    ``q_offset``: absolute position of q[0] (for chunked prefill / decode)."""
    B, Lq, Hq, Dh = q.shape
    _, Lkv, Hkv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(Dh))

    qb = min(block_q, Lq)
    while Lq % qb:
        qb -= 1
    kb = min(block_k, Lkv)
    while Lkv % kb:
        kb -= 1
    nk = Lkv // kb

    qt = q.transpose(0, 2, 1, 3)      # (B, Hq, Lq, Dh)
    kt = k.transpose(0, 2, 1, 3)      # (B, Hkv, Lkv, Dh)
    vt = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            qb=qb, kb=kb, nk=nk, q_offset=q_offset,
        ),
        grid=(B, Hq, Lq // qb, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, kb, Dh),
                         lambda b, h, iq, ik, _g=group: (b, h // _g, ik, 0)),
            pl.BlockSpec((1, 1, kb, Dh),
                         lambda b, h, iq, ik, _g=group: (b, h // _g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb, Dh), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
