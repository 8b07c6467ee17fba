"""Single-token decode attention (Pallas TPU) against a dense KV view.

Serving decode is one query row per sequence against the (possibly paged,
already gathered) KV cache: q (B, 1, Hq, Dh) vs k/v (B, Smax, Hkv, Dh)
with per-row validity ``positions`` (the new token's absolute position —
exactly the ``kpos <= qpos`` mask of the reference einsum path).  The
unfused chain is 8+ kernels per layer (two einsums, mask build, select,
softmax, casts); this kernel is the online-softmax flash loop with Lq = 1,
blocked over kv, GQA via the BlockSpec index maps like
:mod:`.flash_attention`.

Registered in :mod:`.registry` as ``_decode_attn_kernel``, so the cost
model prices the traced CUSTOM node by its FLOPs.
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


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *,
                        scale: float, window: int | None, kb: int, nk: int):
    b, ik = pl.program_id(0), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)                  # (1, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (kb, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (1, kb)

    qpos = pos_ref[b]
    kpos = ik * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_scr[...]                                   # (1, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_old - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(q, k, v, positions, *, scale: float | None = None,
                     window: int | None = None, block_k: int = 128):
    """q: (B, 1, Hq, Dh); k, v: (B, Smax, Hkv, Dh); positions: (B,) int32
    absolute position of each row's new token -> (B, 1, Hq, Dh).

    Cache rows past ``positions[b]`` are masked, so stale/sink pages in a
    gathered paged view never contribute."""
    B, Lq, Hq, Dh = q.shape
    if Lq != 1:
        raise ValueError(f"decode_attention is single-token (Lq={Lq})")
    _, Smax, Hkv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(Dh))

    kb = min(block_k, Smax)
    while Smax % kb:
        kb -= 1
    nk = Smax // kb

    pos = positions.astype(jnp.int32).reshape(B)
    qt = q.transpose(0, 2, 1, 3)      # (B, Hq, 1, Dh)
    kt = k.transpose(0, 2, 1, 3)      # (B, Hkv, Smax, Dh)
    vt = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, scale=scale, window=window, kb=kb, nk=nk,
        ),
        grid=(B, Hq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # all positions
            pl.BlockSpec((1, 1, 1, Dh), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, kb, Dh),
                         lambda b, h, ik, _g=group: (b, h // _g, ik, 0)),
            pl.BlockSpec((1, 1, kb, Dh),
                         lambda b, h, ik, _g=group: (b, h // _g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Dh), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, Dh), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
    )(pos, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
