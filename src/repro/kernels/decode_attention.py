"""Single-token decode attention (Pallas TPU): against a dense KV view, and
straight from the paged KV pool.

:func:`decode_attention` -- serving decode is one query row per sequence
against a dense (or already gathered) KV cache: q (B, 1, Hq, Dh) vs k/v
(B, Smax, Hkv, Dh) with per-row validity ``positions`` (the new token's
absolute position -- exactly the ``kpos <= qpos`` mask of the reference
einsum path).  The unfused chain is 8+ kernels per layer (two einsums, mask
build, select, softmax, casts); this kernel is the online-softmax flash
loop with Lq = 1, blocked over kv, GQA via the BlockSpec index maps like
:mod:`.flash_attention`.

:func:`paged_decode_attention` -- the same attention read through the page
table.  The stacked pool (L, P, page, Hkv, Dh) stays in HBM; for each slot
the kernel copies only the pages holding positions below the slot's length,
one contiguous (page, Hkv, Dh) DMA per page, double-buffered in blocks of
``pages_per_block`` pages, and folds this step's own key/value row in
itself.  It therefore never reads the pool's dead pages nor waits for this
step's pool write.  Against the gather path it agrees within float
tolerance, not bitwise: the online softmax sums in another order and
rounds the unnormalised probabilities to the value dtype.

Both are registered in :mod:`.registry` (``_decode_attn_kernel``,
``_paged_decode_kernel``), so the cost model prices the traced CUSTOM node
by its FLOPs.
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


# -- paged -------------------------------------------------------------------

# rows (positions x kv heads) of one block of pages; the block's scores are
# (Hq, rows) f32, one MXU pass against every kv head at once
_BLOCK_ROWS = 2048


def paged_kernel_platform() -> bool:
    """Whether the paged decode step uses :func:`paged_decode_attention`:
    where Pallas compiles (a TPU).  Under the interpreter the gather path
    is both faster and bitwise equal to the dense layout."""
    return not kernels.interpret_mode()


def paged_decode_fits(pool_shape, n_heads: int) -> bool:
    """Whether the kernel tiles for a stacked pool (L, P, page, Hkv, Dh)
    and ``n_heads`` query heads: kv heads fill whole sublane tiles, so a
    block of pages flattens to (positions * Hkv, Dh) in VMEM, and Dh fills
    whole lane tiles (Mosaic refuses a page copy whose minor dimension,
    such as phi3's Dh of 96, is not a multiple of 128)."""
    _, _, _, hkv, dh = pool_shape
    return hkv % 8 == 0 and dh % 128 == 0 and n_heads % hkv == 0


def pages_per_block(page: int, hkv: int, pages_per_slot: int) -> int:
    """Pages a slot's loop copies and scores per iteration."""
    return max(1, min(pages_per_slot, _BLOCK_ROWS // (page * hkv)))


def _paged_decode_kernel(layer_ref, pt_ref, len_ref,
                         q_ref, kn_ref, vn_ref, pool_k, pool_v,
                         o_ref,
                         kbuf, vbuf, sems, state, *,
                         scale: float, window: int | None, page: int,
                         ppb: int, n_pages: int, hkv: int, group: int,
                         n_slots: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    bk = ppb * page
    rows = bk * hkv
    hq, dh = q_ref.shape

    def copies(slot, blk, buf, j):
        phys = pt_ref[slot * n_pages + blk * ppb + j]
        return (pltpu.make_async_copy(pool_k.at[layer, phys], kbuf.at[buf, j],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(pool_v.at[layer, phys], vbuf.at[buf, j],
                                      sems.at[1, buf]))

    def live_pages(slot):
        return (len_ref[slot] + page - 1) // page

    def start(slot, blk, buf):
        live = live_pages(slot)
        for j in range(ppb):
            @pl.when(blk * ppb + j < live)
            def _():
                for c in copies(slot, blk, buf, j):
                    c.start()

    def wait(blk, buf):
        live = live_pages(b)
        for j in range(ppb):
            @pl.when(blk * ppb + j < live)
            def _():
                for c in copies(b, blk, buf, j):
                    c.wait()

    @pl.when(b == 0)
    def _init():
        # state: [slot whose first block is already in flight, its buffer].
        # Pages past a slot's length are never copied; zeroing once keeps
        # their buffer rows finite, so masked rows add exactly 0 * v.
        state[0] = -1
        state[1] = 0
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)

    length = len_ref[b]
    nb = (length + bk - 1) // bk
    par = state[1]

    @pl.when(state[0] != b)
    def _first():
        start(b, 0, par)

    q = q_ref[...]                                       # (Hq, dh)
    qf = q.astype(jnp.float32)
    # this step's own row (position ``length``) seeds the online softmax
    m0 = jnp.sum(qf * kn_ref[...].astype(jnp.float32), axis=-1,
                 keepdims=True) * scale                  # (Hq, 1)
    l0 = jnp.ones((hq, 1), jnp.float32)
    acc0 = vn_ref[...].astype(jnp.float32)               # (Hq, dh)

    # block rows are (position, kv head) pairs; query head h reads kv head
    # h // group, so every other row of its score row is masked
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    row_head = col % hkv
    row_pos = col // hkv
    q_head = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0) // group
    same_head = row_head == q_head                       # (Hq, rows)

    def body(i, carry):
        m, l, acc = carry
        buf = (par + i) % 2

        @pl.when(i + 1 < nb)
        def _next_block():
            start(b, i + 1, 1 - buf)

        @pl.when((i + 1 == nb) & (b + 1 < n_slots))
        def _next_slot():
            start(b + 1, 0, 1 - buf)
            state[0] = b + 1
            state[1] = 1 - buf

        wait(i, buf)
        k = kbuf[buf].reshape(rows, dh)
        v = vbuf[buf].reshape(rows, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = i * bk + row_pos
        valid = same_head & (kpos < length)
        if window is not None:
            valid = valid & (length - kpos < window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, acc0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(q, pool_k, pool_v, layer, page_table, lengths,
                           k_new, v_new, *, scale: float | None = None,
                           window: int | None = None):
    """Decode attention of one new token per slot, read through the page
    table.

    q: (B, Hq, Dh); pool_k, pool_v: the stacked pools (L, P, page, Hkv, Dh);
    layer: int32 scalar, the pool layer to read; page_table: (B, n) int32;
    lengths: (B,) int32 positions already in each slot's pages; k_new,
    v_new: (B, Hkv, Dh), this step's row at position ``lengths[b]``.
    Returns (B, Hq, Dh) in q's dtype.

    Attends over positions ``< lengths[b]`` (within ``window`` of the new
    one) plus the new row; the pool need not hold the new row yet."""
    B, Hq, Dh = q.shape
    _, _, page, Hkv, _ = pool_k.shape
    n = page_table.shape[1]
    group = Hq // Hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(Dh))
    ppb = pages_per_block(page, Hkv, n)

    kn = jnp.repeat(k_new.astype(q.dtype), group, axis=1)   # (B, Hq, Dh)
    vn = jnp.repeat(v_new.astype(q.dtype), group, axis=1)
    row = pl.BlockSpec((None, Hq, Dh), lambda b, *_: (b, 0, 0))
    interpret = pltpu.InterpretParams() if kernels.interpret_mode() else False
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, window=window, page=page,
            ppb=ppb, n_pages=n, hkv=Hkv, group=group, n_slots=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row, row, row,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, Hkv, Dh), pool_k.dtype),
                pltpu.VMEM((2, ppb, page, Hkv, Dh), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dh), q.dtype),
        # slots run in order: each prefetches the next slot's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32).reshape(-1),
      lengths.astype(jnp.int32).reshape(B),
      q, kn, vn, pool_k, pool_v)
