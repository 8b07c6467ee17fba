"""The paged latent decode kernel (interpret mode) against the gather path
of latent decode: each slot's live ``lat`` and packed ``rope`` pages read
through the page table, this step's row folded in, over ragged lengths,
free slots on the sink page, odd and even physical pages, and blocks that
do not divide a slot's pages; and the gather path over the two-leaf pool
against the dense latent rectangle, bitwise."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.kernels import latent_attention
from repro.kernels.latent_attention import (latent_pages_per_block,
                                            paged_latent_decode_attention,
                                            paged_latent_fits)
from repro.models import build_model
from repro.models.config import MLAConfig
from repro.models.layers import latent_gather_attention, rope_pack
from repro.serve.kv import PagedKV

LAYERS = 2
PAGE, MAX_LEN = 16, 128                 # 8 pages a slot
# lengths 0 (a free slot on the sink page), 1, page-1, page, page+1, and
# max_len-1 (every page of the slot)
RAGGED = [0, 1, 15, 16, 17, MAX_LEN - 1]


def _case(lengths, *, rank, rope, heads, seed=0, dtype=jnp.bfloat16):
    """Random pools and queries, each live slot on its own shuffled pages
    (so both parities of a packed rope row) and free slots pointing at the
    sink page 0, as PagedKV lays them out."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    n = MAX_LEN // PAGE
    P = B * n + 1
    pack = rope_pack(rope)
    lat = jnp.asarray(rng.standard_normal((LAYERS, P, PAGE, rank)), dtype)
    pe = jnp.asarray(rng.standard_normal(
        (LAYERS, -(-P // pack), PAGE, pack * rope)), dtype)
    pt = np.zeros((B, n), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b, ln in enumerate(lengths):
        if ln:
            for j in range(ln // PAGE + 1):
                pt[b, j] = free.pop()
    q_lat = jnp.asarray(rng.standard_normal((B, heads, rank)), dtype)
    q_pe = jnp.asarray(rng.standard_normal((B, heads, rope)), dtype)
    row = jnp.asarray(rng.standard_normal((B, rank + rope)), dtype)
    cache = {"lat": lat, "rope": pe, "page_table": jnp.asarray(pt),
             "length": jnp.asarray(lengths, jnp.int32)}
    return q_lat, q_pe, cache, row


@pytest.mark.parametrize("block_pages,rope,layer", [
    (1, 64, 1),      # one page a block; rope pages in pairs
    (3, 64, 1),      # 3 does not divide a slot's 8 pages
    (8, 64, 0),      # a slot's every page in one block, at layer 0
    (3, 32, 1),      # four pages' rope to a row
])
def test_kernel_matches_gather_path(block_pages, rope, layer, monkeypatch):
    monkeypatch.setattr(latent_attention, "_BLOCK_POSITIONS",
                        PAGE * block_pages)
    assert latent_pages_per_block(PAGE, MAX_LEN // PAGE) == block_pages
    q_lat, q_pe, cache, row = _case(RAGGED, rank=128, rope=rope, heads=8)
    live = np.asarray(cache["page_table"])[np.asarray(RAGGED) > 0]
    live = live[live > 0]
    assert {0, 1} <= set(live % 2)       # odd and even physical pages
    scale = 0.1
    got = paged_latent_decode_attention(
        q_lat, q_pe, cache["lat"], cache["rope"], layer, cache["page_table"],
        cache["length"], row, scale=scale)
    want = latent_gather_attention(q_lat, q_pe, cache, layer, row,
                                   scale=scale).astype(q_lat.dtype)
    assert got.shape == want.shape and got.dtype == want.dtype
    # bf16 outputs of O(1) values: one or two ulps apart
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    # the free slot attends to its new row alone: o_lat is its latent
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.broadcast_to(np.asarray(
                                      row[0, :128], np.float32), (8, 128)))


def test_fits():
    """What tiles on the chip: DeepSeek-V2-Lite's pool at page 16."""
    assert paged_latent_fits((27, 6145, 16, 512), (27, 3073, 16, 128), 16)
    assert not paged_latent_fits((4, 25, 8, 32), (4, 2, 8, 128), 4)
    assert rope_pack(64) == 2 and rope_pack(8) == 16 and rope_pack(96) == 1


def test_gather_path_equals_dense_rectangle_bitwise():
    """A decode step of a small latent model over the paged pool (prefill
    rows spliced in by PagedKV, on pages whose rope parts start mid-row of
    the packed leaf) gives the logits and rows of the same step over the
    dense rectangle, bit for bit: both attend over the same values in the
    same shapes."""
    cfg = dataclasses.replace(
        get_reduced("qwen2_moe_a2_7b"), family="moe", n_layers=2,
        mla=MLAConfig(kv_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_dim=16),
        head_dim=24)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    lengths, max_len, page = [19, 5, 0, 33], 40, 8
    B = len(lengths)
    dense = {"lat": jnp.zeros((cfg.n_layers, B, max_len, 40), cfg.dtype)}
    kv = PagedKV(model, B, max_len, page)
    # spend a few pages first: slot pages start at 4, mid-row of the rope
    kv.allocator.alloc(3)
    for slot, ln in enumerate(lengths):
        if not ln:
            continue
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, ln)), jnp.int32)
        _, pre = model.prefill(params, toks, max_len=max_len)
        kv.insert_kv(pre, 0, ln, slot)
        dense["lat"] = dense["lat"].at[:, slot].set(pre["lat"][:, 0])
    dense["length"] = jnp.asarray(lengths, jnp.int32)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    got, new = model.decode_step(params, kv.decode_cache(), tok,
                                 kv_limit=max_len)
    want, new_dense = model.decode_step(params, dense, tok)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the rows written this step, read back through the page tables
    for slot, ln in enumerate(lengths):
        if not ln:
            continue
        phys = kv.table[slot, ln // page]
        pack = rope_pack(8)
        lat = np.asarray(new["lat"][:, phys, ln % page])
        rope = np.asarray(new["rope"][:, phys // pack, ln % page,
                                      phys % pack * 8:(phys % pack + 1) * 8])
        np.testing.assert_array_equal(
            np.concatenate([lat, rope], axis=-1),
            np.asarray(new_dense["lat"][:, slot, ln]))
