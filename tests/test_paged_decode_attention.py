"""The paged decode attention kernel (interpret mode) against the gather
path's einsum chain: each slot's live pages read through the page table,
this step's row folded in, over ragged lengths, free slots on the sink
page, GQA groups, head sizes, windows and page sizes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention
from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_decode_fits,
                                            pages_per_block)

LAYERS, LAYER = 2, 1


def _gather_reference(q, pool_k, pool_v, layer, pt, lengths, k_new, v_new,
                      scale, window):
    """The gather path of ``apply_attention``: write the new row into the
    layer's pool, gather every slot's whole view, mask it, softmax."""
    B, Hq, dh = q.shape
    _, _, page, Hkv, _ = pool_k.shape
    n = pt.shape[1]
    phys = jnp.take_along_axis(pt, (lengths // page)[:, None], axis=1)[:, 0]
    off = lengths % page
    pk = pool_k[layer].at[phys, off].set(k_new)
    pv = pool_v[layer].at[phys, off].set(v_new)
    ck = jnp.take(pk, pt.reshape(-1), axis=0).reshape(B, n * page, Hkv, dh)
    cv = jnp.take(pv, pt.reshape(-1), axis=0).reshape(B, n * page, Hkv, dh)
    qg = q.reshape(B, 1, Hkv, Hq // Hkv, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(n * page)[None, None, None, None, :]
    qpos = lengths[:, None, None, None, None]
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (qpos - kpos < window)
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(q.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, dh).astype(q.dtype)


def _case(lengths, *, group, dh, page, max_len, hkv=2, seed=0,
          dtype=jnp.bfloat16):
    """Pools of random pages, each live slot on its own shuffled pages and
    free slots (length 0) pointing at the sink page 0, as PagedKV lays
    them out; ``lengths`` are positions already in the pool."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    n = -(-max_len // page)
    P = B * n + 1
    shape = (LAYERS, P, page, hkv, dh)
    pool_k = jnp.asarray(rng.standard_normal(shape), dtype)
    pool_v = jnp.asarray(rng.standard_normal(shape), dtype)
    pt = np.zeros((B, n), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b, ln in enumerate(lengths):
        if ln:
            for j in range(ln // page + 1):
                pt[b, j] = free.pop()
    hq = hkv * group
    q = jnp.asarray(rng.standard_normal((B, hq, dh)), dtype)
    k_new = jnp.asarray(rng.standard_normal((B, hkv, dh)), dtype)
    v_new = jnp.asarray(rng.standard_normal((B, hkv, dh)), dtype)
    return (q, pool_k, pool_v, jnp.asarray(pt),
            jnp.asarray(lengths, jnp.int32), k_new, v_new)


def _check(lengths, *, group=2, dh=64, page=8, max_len=64, window=None,
           hkv=2):
    q, pool_k, pool_v, pt, ln, k_new, v_new = _case(
        lengths, group=group, dh=dh, page=page, max_len=max_len, hkv=hkv)
    scale = float(dh) ** -0.5
    got = paged_decode_attention(q, pool_k, pool_v, LAYER, pt, ln, k_new,
                                 v_new, scale=scale, window=window)
    want = _gather_reference(q, pool_k, pool_v, LAYER, pt, ln, k_new, v_new,
                             scale, window)
    assert got.shape == want.shape and got.dtype == want.dtype
    # bf16 outputs of O(1) values: one or two ulps apart
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    return got


# lengths 0, 1, page-1, page, mid-page and max_len-1 (page 8, max_len 64)
RAGGED = [0, 1, 7, 8, 29, 63]


@pytest.mark.parametrize("block_pages", [1, 3, 8])
def test_ragged_lengths_and_sink_slots(block_pages, monkeypatch):
    """Every length class in one batch; the free slot (length 0) reads no
    page and attends to its new row alone; blocks of 1, 3 (a last block
    past the table's end) and all 8 pages of a slot."""
    # a page of 8 positions x 2 kv heads is 16 block rows
    monkeypatch.setattr(decode_attention, "_BLOCK_ROWS", 16 * block_pages)
    assert pages_per_block(8, 2, 8) == block_pages
    got = _check(RAGGED)
    q, pool_k, pool_v, pt, ln, k_new, v_new = _case(
        RAGGED, group=2, dh=64, page=8, max_len=64)
    # length 0: softmax over the new row alone returns v_new, per q head
    np.testing.assert_array_equal(
        np.asarray(got[0], np.float32),
        np.asarray(jnp.repeat(v_new[0], 2, axis=0), np.float32))


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("dh", [64, 96, 128])
def test_gqa_groups_and_head_sizes(group, dh):
    _check([5, 17, 40, 0], group=group, dh=dh)


@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("window", [None, 5, 24])
def test_windows_and_page_sizes(page, window):
    _check([3, 16, 31, 47, 60], page=page, window=window)


def test_new_row_comes_from_k_new_not_the_pool():
    """The pool does not hold this step's row yet: the row at ``length``
    in the pool is stale, and the kernel must fold the new row in from
    k_new/v_new (the gather reference writes it into the pool first)."""
    q, pool_k, pool_v, pt, ln, k_new, v_new = _case(
        [12, 30], group=2, dh=64, page=8, max_len=64)
    scale = 0.125
    got = paged_decode_attention(q, pool_k, pool_v, LAYER, pt, ln, k_new,
                                 v_new, scale=scale)
    want = _gather_reference(q, pool_k, pool_v, LAYER, pt, ln, k_new, v_new,
                             scale, None)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    # a new key along each head's own query (group 1): the new row takes
    # nearly all the weight, so the output sits next to v_new
    q, pool_k, pool_v, pt, ln, k_new, v_new = _case(
        [12, 30], group=1, dh=64, page=8, max_len=64)
    big = (q.astype(jnp.float32) * 100).astype(q.dtype)
    got = paged_decode_attention(q, pool_k, pool_v, LAYER, pt, ln, big,
                                 v_new, scale=scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(v_new, np.float32), atol=2e-2)


def test_reads_the_named_layer():
    """Layer 0 and layer 1 of the stacked pool give different answers,
    each equal to the reference at that layer."""
    q, pool_k, pool_v, pt, ln, k_new, v_new = _case(
        [20, 9], group=2, dh=64, page=8, max_len=32)
    outs = []
    for layer in range(LAYERS):
        got = paged_decode_attention(q, pool_k, pool_v, layer, pt, ln,
                                     k_new, v_new, scale=0.125)
        want = _gather_reference(q, pool_k, pool_v, layer, pt, ln, k_new,
                                 v_new, 0.125, None)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)
        outs.append(np.asarray(got, np.float32))
    assert np.abs(outs[0] - outs[1]).max() > 0.1


def test_fits_and_block_size():
    """What tiles on the chip: kv heads in whole sublane tiles, head size
    in whole lane tiles.  Blocks hold 2048 (position, kv head) rows."""
    assert paged_decode_fits((28, 1537, 16, 8, 128), 16)      # qwen3-1.7b
    assert not paged_decode_fits((32, 1025, 16, 32, 96), 32)  # phi3-mini
    assert not paged_decode_fits((2, 49, 8, 2, 16), 4)        # reduced qwen3
    assert pages_per_block(16, 8, 128) == 16
    assert pages_per_block(16, 32, 128) == 4
    assert pages_per_block(16, 8, 4) == 4
