"""Single-token latent decode attention (Pallas TPU), read straight from the
paged latent pool.

Multi-head latent attention caches one row a token, shared by every head:
the normed latent ``c`` (rank values) and the rotated key part ``k_pe``
(rope values).  Decode runs in the absorbed form: head h scores position t
as ``(q_lat[h] . c[t] + q_pe[h] . k_pe[t]) * scale`` with ``q_lat = q_nope
@ W_UK``, and reads back ``o_lat[h] = sum_t p[h, t] c[t]``; ``W_UV`` and
the output projection stay outside.

The pool keeps the two parts as two leaves, so that a page of each is a
whole number of (sublane, lane) tiles and one DMA copies it: ``lat`` (L, P,
page, rank) and ``rope`` (L, ceil(P / pack), page, pack * rope), where
``pack`` pages side by side fill a 128-lane row (page p's rope is pool row
p // pack, lanes (p % pack) * rope on).  The kernel copies the whole lane
row of each page's rope and scores it against a block-diagonal stack of
``pack`` copies of q_pe, keeping for each position the copy that matches
its page's lane group.

:func:`paged_latent_decode_attention` mirrors
:func:`repro.kernels.decode_attention.paged_decode_attention`: the grid
runs over slots, the stacked pool stays in HBM, each slot copies only the
pages below its length, double-buffered in blocks, the next slot's first
block is prefetched across grid steps, and the online softmax (float32) is
seeded with this step's own row, so the kernel never waits for the pool
write.  Against the gather path it agrees within float tolerance, not
bitwise.  Registered in :mod:`.registry` (``_paged_latent_kernel``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30

# positions of one block of pages: its latent copy is (positions, rank)
_BLOCK_POSITIONS = 512


def paged_latent_fits(lat_shape, rope_shape, n_heads: int) -> bool:
    """Whether the kernel tiles for the latent pool leaves ``lat`` (L, P,
    page, rank) and ``rope`` (L, P', page, lanes) and ``n_heads`` query
    heads: a page fills whole bf16 sublane tiles, the rank and the packed
    rope row whole lane tiles, and the heads whole f32 sublane tiles (the
    rope scores of each lane group are a row slice)."""
    page, rank = lat_shape[2], lat_shape[3]
    return (page % 16 == 0 and rank % 128 == 0 and rope_shape[-1] % 128 == 0
            and n_heads % 8 == 0)


def latent_pages_per_block(page: int, pages_per_slot: int) -> int:
    """Pages a slot's loop copies and scores per iteration."""
    return max(1, min(pages_per_slot, _BLOCK_POSITIONS // page))


def _paged_latent_kernel(layer_ref, pt_ref, len_ref,
                         ql_ref, qb_ref, cn_ref, pn_ref, pool_c, pool_r,
                         o_ref,
                         cbuf, rbuf, sems, state, *,
                         scale: float, page: int, ppb: int, n_pages: int,
                         pack: int, n_slots: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    bk = ppb * page
    hq, rank = ql_ref.shape
    lanes = qb_ref.shape[1]

    def phys(slot, blk, j):
        # clamped: a last block may run past the last slot's table, where
        # only masked columns read it
        i = jnp.minimum(slot * n_pages + blk * ppb + j, n_slots * n_pages - 1)
        return pt_ref[i]

    def copies(slot, blk, buf, j):
        p = phys(slot, blk, j)
        return (pltpu.make_async_copy(pool_c.at[layer, p], cbuf.at[buf, j],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(pool_r.at[layer, p // pack],
                                      rbuf.at[buf, j], sems.at[1, buf]))

    def each_live_page(slot, blk, fn):
        # a loop, not ppb unrolled copies: the kernel body (and so every
        # trace and lowering of a step that holds it) stays small
        live = (len_ref[slot] + page - 1) // page

        def body(j, carry):
            fn(j)
            return carry

        jax.lax.fori_loop(0, jnp.clip(live - blk * ppb, 0, ppb), body, 0)

    def start(slot, blk, buf):
        def one(j):
            for c in copies(slot, blk, buf, j):
                c.start()
        each_live_page(slot, blk, one)

    def wait(blk, buf):
        def one(j):
            for c in copies(b, blk, buf, j):
                c.wait()
        each_live_page(b, blk, one)

    @pl.when(b == 0)
    def _init():
        # state: [slot whose first block is already in flight, its buffer].
        # Pages past a slot's length are never copied; zeroing once keeps
        # their buffer rows finite, so masked rows add exactly 0 * c.
        state[0] = -1
        state[1] = 0
        cbuf[...] = jnp.zeros(cbuf.shape, cbuf.dtype)
        rbuf[...] = jnp.zeros(rbuf.shape, rbuf.dtype)

    length = len_ref[b]
    nb = (length + bk - 1) // bk
    par = state[1]

    @pl.when(state[0] != b)
    def _first():
        start(b, 0, par)

    ql = ql_ref[...]                                     # (H, rank)
    qb = qb_ref[...]                                     # (pack * H, lanes)
    # this step's own row (position ``length``) seeds the online softmax;
    # its rope sits in lane group 0, which the first H rows of qb score
    cn = cn_ref[...].astype(jnp.float32)                 # (1, rank)
    m0 = (jnp.sum(ql.astype(jnp.float32) * cn, axis=-1, keepdims=True)
          + jnp.sum(qb[:hq].astype(jnp.float32)
                    * pn_ref[...].astype(jnp.float32),
                    axis=-1, keepdims=True)) * scale     # (H, 1)
    l0 = jnp.ones((hq, 1), jnp.float32)
    acc0 = jnp.broadcast_to(cn, (hq, rank))

    col = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    col_page = col // page

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
        c = cbuf[buf].reshape(bk, rank)
        rp = rbuf[buf].reshape(bk, lanes)
        s = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        sr = jax.lax.dot_general(qb, rp, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        rope = sr[:hq]
        if pack > 1:
            # each column keeps the score of its page's own lane group
            group = jax.lax.fori_loop(
                0, ppb, lambda j, g: jnp.where(col_page == j,
                                               phys(b, i, j) % pack, g),
                jnp.zeros((1, bk), jnp.int32))
            for g in range(1, pack):
                rope = jnp.where(group == g, sr[g * hq:(g + 1) * hq], rope)
        s = (s + rope) * scale
        s = jnp.where(i * bk + col < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, acc0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_latent_decode_attention(q_lat, q_pe, pool_lat, pool_rope, layer,
                                  page_table, lengths, row, *, scale: float):
    """Absorbed latent attention of one new token per slot, read through
    the page table.

    q_lat: (B, H, rank), ``q_nope @ W_UK``; q_pe: (B, H, rope), rotated;
    pool_lat: (L, P, page, rank) and pool_rope: (L, ceil(P / pack), page,
    pack * rope), the stacked pool leaves; layer: int32 scalar, the pool
    layer to read; page_table: (B, n) int32; lengths: (B,) int32 positions
    already in each slot's pages; row: (B, rank + rope), this step's latent
    row at position ``lengths[b]``.  Returns ``o_lat`` (B, H, rank) =
    softmax(scores) . c in q_lat's dtype.

    Attends over positions ``< lengths[b]`` plus the new row; the pool
    need not hold the new row yet."""
    B, H, rank = q_lat.shape
    w = q_pe.shape[-1]
    _, _, page, lanes = pool_rope.shape
    pack = lanes // w
    n = page_table.shape[1]
    ppb = latent_pages_per_block(page, n)

    dt = q_lat.dtype
    # rows g*H..(g+1)*H score lane group g of a packed rope row
    eye = jnp.eye(pack, dtype=dt)
    qb = (eye[None, :, None, :, None] * q_pe.astype(dt)[:, None, :, None, :]
          ).reshape(B, pack * H, lanes)
    row = row.astype(dt)
    c_new = row[:, None, :rank]
    pe_new = jnp.pad(row[:, None, rank:], ((0, 0), (0, 0), (0, lanes - w)))
    block = lambda *shape: pl.BlockSpec((None, *shape), lambda b, *_: (b, 0, 0))
    interpret = pltpu.InterpretParams() if kernels.interpret_mode() else False
    return pl.pallas_call(
        functools.partial(
            _paged_latent_kernel, scale=scale, page=page, ppb=ppb, n_pages=n,
            pack=pack, n_slots=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[block(H, rank), block(pack * H, lanes),
                      block(1, rank), block(1, lanes),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block(H, rank),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, rank), pool_lat.dtype),
                pltpu.VMEM((2, ppb, page, lanes), pool_rope.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), dt),
        # slots run in order: each prefetches the next slot's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32).reshape(-1),
      lengths.astype(jnp.int32).reshape(B),
      q_lat, qb, c_new, pe_new, pool_lat, pool_rope)
