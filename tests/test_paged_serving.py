"""Disaggregated serving API: paged KV, prefix cache, and stitched
prefills.

Covers the three-stage prefill -> insert(slot) -> generate_step surface:
page-allocator invariants, paged-vs-dense token equality (including
mid-stream evict/refill and EOS truncation), stitched-prefill
miss-then-upgrade, prefix-cache hit determinism and shared-page
refcounting, the bounded prefill-specialization LRU, and the legacy
rectangular generate() deprecation."""

import numpy as np
import pytest

import jax

from repro.cache import CompilationService
from repro.configs import get_reduced
from repro.models import build_model
from repro.serve import (Engine, PageAllocator, PageExhausted, ServeConfig)


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _workload(cfg, seed=42):
    rng = np.random.default_rng(seed)
    lens = [5, 12, 9, 3, 17, 7, 11]
    news = [6, 3, 9, 5, 4, 8, 2]
    prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32) for p in lens]
    return prompts, news


def _drain_tokens(eng, prompts, news):
    """rid is normalized per round so repeat drains of one engine compare."""
    for p, n in zip(prompts, news):
        eng.submit(p, max_new_tokens=n)
    return {f.rid % len(prompts): (list(map(int, f.tokens)), f.finish_reason)
            for f in eng.drain()}


# -- page allocator ------------------------------------------------------------

def test_allocator_exhaustion_and_lifo_reuse():
    a = PageAllocator(5)                  # pages 1..4 usable, 0 is the sink
    first = a.alloc(2)
    assert first == [1, 2]                # lowest pages first
    assert a.used == 2 and a.free_count == 2
    with pytest.raises(PageExhausted):
        a.alloc(3)                        # all-or-nothing: nothing consumed
    assert a.used == 2 and a.free_count == 2
    a.free([2])
    assert a.alloc(1) == [2]              # freed pages reused first (LIFO)
    a.free([1, 2])
    rest = a.alloc(4)
    assert sorted(rest) == [1, 2, 3, 4] and 0 not in rest
    assert a.peak_used == 4
    with pytest.raises(PageExhausted):
        a.alloc(1)


def test_allocator_refcounts_shared_pages():
    a = PageAllocator(4)
    pages = a.alloc(2)
    a.incref(pages)                       # second owner (prefix-cache share)
    a.free(pages)
    assert a.used == 2                    # still held by the other owner
    assert a.free_count == 1
    a.free(pages)
    assert a.used == 0 and a.free_count == 3


def test_allocator_rejects_degenerate_pool():
    with pytest.raises(ValueError):
        PageAllocator(1)                  # only the sink page


# -- paged vs dense equality ---------------------------------------------------

def test_paged_matches_dense_through_evict_refill(setup):
    """7 requests through 3 slots force mid-stream evict + refill; the
    paged engine (small pages, so several pages per slot) must emit exactly
    the dense engine's tokens."""
    cfg, model, params = setup
    prompts, news = _workload(cfg)
    dense = Engine(model, params, ServeConfig(batch=3, max_len=64, paged=False))
    paged = Engine(model, params,
                   ServeConfig(batch=3, max_len=64, paged=True, page_size=8))
    assert paged.paged and not dense.paged
    ref = _drain_tokens(dense, prompts, news)
    got = _drain_tokens(paged, prompts, news)
    assert got == ref
    rep = paged.kv.report()
    assert rep["used"] == 0               # every slot released on finish
    assert rep["peak_used"] > 0
    assert rep["slot_pages"] == [0, 0, 0]


def test_paged_matches_dense_with_eos_truncation(setup):
    """EOS mid-stream truncates identically on both layouts (finish_reason
    and token streams byte-for-byte)."""
    cfg, model, params = setup
    prompts, news = _workload(cfg, seed=7)
    # pick an eos id that actually occurs in the dense reference stream
    dense = Engine(model, params, ServeConfig(batch=2, max_len=64, paged=False))
    ref0 = _drain_tokens(dense, prompts, news)
    eos = ref0[0][0][-1]                  # guaranteed to appear at least once

    def run(paged):
        eng = Engine(model, params,
                     ServeConfig(batch=2, max_len=64, eos_id=eos,
                                 paged=paged, page_size=8))
        return _drain_tokens(eng, prompts, news)

    ref, got = run(False), run(True)
    assert got == ref
    assert any(r[1] == "eos" for r in ref.values())


def test_staged_generate_matches_dense(setup):
    """generate(prompts, prompt_lens=...) — the staged three-call path —
    is layout-independent."""
    cfg, model, params = setup
    rng = np.random.default_rng(3)
    lens = [5, 12, 9]
    prompts = np.zeros((3, max(lens)), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(0, cfg.vocab, (n,))

    def run(paged):
        eng = Engine(model, params,
                     ServeConfig(batch=3, max_len=32, max_new_tokens=6,
                                 paged=paged, page_size=4))
        return eng.generate(prompts.copy(), prompt_lens=lens)

    np.testing.assert_array_equal(run(True), run(False))


# -- the three-stage API directly ----------------------------------------------

def test_manual_prefill_insert_generate_loop(setup):
    """Drive the stages by hand: prefill two prompts, insert into chosen
    slots, chunked generate, release, and reuse the freed slot."""
    cfg, model, params = setup
    rng = np.random.default_rng(11)
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, page_size=4))
    p0 = rng.integers(0, cfg.vocab, (6,)).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)

    with pytest.raises(RuntimeError):
        eng.generate_step()               # nothing inserted yet
    px0, px1 = eng.prefill(p0), eng.prefill(p1)
    assert px0.batch == 1 and px0.bucket == 8
    eng.insert(px0, slot=0)
    eng.insert(px1, slot=1)
    with pytest.raises(RuntimeError):
        eng.insert(px1, slot=1)           # occupied
    with pytest.raises(IndexError):
        eng.insert(px1, slot=2)
    assert eng.occupied == frozenset({0, 1})
    out = eng.generate_step(steps=3)
    assert out.shape == (2, 3)

    # staged run == the same prompts through the reference engine
    ref = Engine(model, params,
                 ServeConfig(batch=1, max_len=32, max_new_tokens=4,
                             paged=False))
    for slot, p, px in ((0, p0, px0), (1, p1, px1)):
        want = ref.generate(p[None].copy(), prompt_lens=[len(p)])[0]
        stream = [int(px.first_tokens[0])] + list(map(int, out[slot]))
        assert stream == list(map(int, want))

    eng.release(0)
    assert eng.occupied == frozenset({1})
    # freed pages make the slot reusable immediately
    px2 = eng.prefill(p0)
    eng.insert(px2, slot=0)
    assert eng.occupied == frozenset({0, 1})


def test_pool_exhaustion_surfaces_at_insert(setup):
    """An undersized explicit pool raises PageExhausted instead of
    silently corrupting a live page."""
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, page_size=4, num_pages=4))
    p = rng.integers(0, cfg.vocab, (12,)).astype(np.int32)   # needs 3 pages
    eng.insert(eng.prefill(p), slot=0)
    with pytest.raises(PageExhausted):
        eng.insert(eng.prefill(p), slot=1)


# -- stitched prefill ----------------------------------------------------------

def test_stitched_prefill_miss_then_upgrade(setup):
    """Prefills route through stitch(): before any plan lands each pow2
    bucket serves through the compiled fallback artifact (status pending),
    explicitly landed per-bucket plans upgrade later prefills, and tokens
    are identical before and after the upgrade.

    Seed 8 is deliberately tie-prone: before widening converts were folded
    into GEMMs (trace._fold_widening_converts) the artifact executor's
    logits wobbled one bf16 ulp off plain jit and this stream's argmax
    flipped — the equality below is the regression test for that bug."""
    cfg, model, params = setup
    rng = np.random.default_rng(8)
    plens, news = (5, 12, 9, 17), (6, 3, 9, 4)
    prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
               for p in plens]
    svc = CompilationService(max_background=0)   # nothing lands by itself
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=64, stitch_execute=True),
                 stitch_service=svc)
    ref = _drain_tokens(
        Engine(model, params, ServeConfig(batch=2, max_len=64)),
        prompts, news)
    assert _drain_tokens(eng, prompts, news) == ref   # plans still pending

    rep = eng.report()["prefill"]
    assert rep["calls"]["stitched"] == len(prompts)
    plans = rep["plans"]
    assert plans and all(k.startswith("prefill@") for k in plans)
    assert {p["status"] for p in plans.values()} == {"pending"}
    assert len(plans) == 3                # buckets 8, 16, 32

    # land every plan (decode + per-bucket prefills) by hand, then re-serve
    for exec_ in (eng._prefill_exec, eng._exec):
        for sp in exec_._specs.values():
            art = svc.compiler("stitch", sp.placement).compile(
                sp.graph, bypass_cache_lookup=True)
            assert art.stats.n_kernels >= 1
    assert eng.land_plans(timeout=5.0) == 0
    assert _drain_tokens(eng, prompts, news) == ref   # upgraded round
    rep = eng.report()["prefill"]
    assert all(p["status"] == "hit" for p in rep["plans"].values())
    assert all(p["plan"]["n_kernels"] >= 1 for p in rep["plans"].values())


def test_prefill_specialization_lru_bounded(setup):
    """The prefill memo is capped at prefill_cache_size (the old
    Scheduler._prefill_fns dict grew without bound)."""
    cfg, model, params = setup
    rng = np.random.default_rng(9)
    eng = Engine(model, params,
                 ServeConfig(batch=1, max_len=64, prefill_cache_size=2))
    for plen in (3, 5, 9, 17, 33):        # buckets 4, 8, 16, 32, 64
        eng.prefill(rng.integers(0, cfg.vocab, (plen,)).astype(np.int32))
    rep = eng.report()["cache"]
    assert rep["prefill_cap"] == 2
    assert rep["prefill_entries"] == 2    # LRU evicted the older buckets


# -- prefix cache --------------------------------------------------------------

def test_prefix_cache_hit_determinism(setup):
    """A repeated prompt hits the cache and the full token stream (first
    token + decode) is identical to the miss path's."""
    cfg, model, params = setup
    rng = np.random.default_rng(21)
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, page_size=4,
                             prefix_cache=True))
    p = rng.integers(0, cfg.vocab, (11,)).astype(np.int32)

    miss = eng.prefill(p)
    assert not miss.cached
    eng.insert(miss, slot=0)
    miss_toks = [int(miss.first_tokens[0])] + \
        list(map(int, eng.generate_step(steps=4)[0]))
    eng.release(0)

    hit = eng.prefill(p)
    assert hit.cached and hit.pages is not None
    assert int(hit.lengths[0]) == 11
    eng.insert(hit, slot=1)
    hit_toks = [int(hit.first_tokens[0])] + \
        list(map(int, eng.generate_step(steps=4)[1]))
    assert hit_toks == miss_toks

    rep = eng.prefix_cache.report()
    assert rep == {"hits": 1, "misses": 1, "hit_rate": 0.5,
                   "entries": 1, "pages_held": 2}   # 11 // 4 full pages


def test_prefix_cache_shared_pages_across_slots(setup):
    """Two live slots share one prefix's full pages; each decodes onto its
    private tail page, so their streams match the unshared reference and
    releasing one slot leaves the other (and the cache) intact."""
    cfg, model, params = setup
    rng = np.random.default_rng(23)
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, page_size=4,
                             prefix_cache=True))
    p = rng.integers(0, cfg.vocab, (9,)).astype(np.int32)
    ref = Engine(model, params,
                 ServeConfig(batch=1, max_len=32, max_new_tokens=7,
                             paged=False))
    want = list(map(int, ref.generate(p[None].copy(), prompt_lens=[9])[0]))

    px0 = eng.prefill(p)                  # miss: registers 2 full pages
    eng.insert(px0, slot=0)
    px1 = eng.prefill(p)                  # hit: shared pages, private tail
    assert px1.cached
    eng.insert(px1, slot=1)
    out = eng.generate_step(steps=5)
    for slot, px in ((0, px0), (1, px1)):
        stream = [int(px.first_tokens[0])] + list(map(int, out[slot]))
        assert stream == want[:6], f"slot {slot}"

    held = eng.prefix_cache.pages_held
    eng.release(1)                        # decrefs shared pages
    assert eng.prefix_cache.pages_held == held   # cache still owns them
    out2 = eng.generate_step(steps=1)     # slot 0 unaffected
    assert int(out2[0, 0]) == want[6]


def test_prefix_cache_evicts_under_pool_pressure(setup):
    """Allocator pressure reclaims cold prefix entries (via the reclaim
    callback) before raising PageExhausted."""
    cfg, model, params = setup
    rng = np.random.default_rng(27)
    # 7 usable pages; each 8-token prompt costs 2 full cached pages and
    # each insert 3 (2 shared-incref'd + 1 private tail here: miss path
    # allocates ceil(8/4)=2 private pages)
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=16, page_size=4, num_pages=8,
                             prefix_cache=True))
    p0 = rng.integers(0, cfg.vocab, (8,)).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, (8,)).astype(np.int32)
    eng.insert(eng.prefill(p0), slot=0)   # 2 slot pages + 2 cached
    assert len(eng.prefix_cache) == 1
    eng.insert(eng.prefill(p1), slot=1)   # needs 2 + 2 but only 3 free:
    assert eng.occupied == {0, 1}         # pressure evicted p0's entry
    assert len(eng.prefix_cache) == 1


def test_prefix_cache_requires_paged(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError):
        Engine(model, params,
               ServeConfig(batch=1, max_len=16, paged=False,
                           prefix_cache=True))


def test_scheduler_counts_prefix_hits(setup):
    """The continuous path reports prefix hits end-to-end and repeated
    prompts still produce the reference tokens."""
    cfg, model, params = setup
    rng = np.random.default_rng(31)
    p = rng.integers(0, cfg.vocab, (9,)).astype(np.int32)
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, page_size=4,
                             prefix_cache=True))
    for _ in range(4):
        eng.submit(p.copy(), max_new_tokens=5)
    fins = {f.rid: f for f in eng.drain()}
    streams = {rid: list(map(int, f.tokens)) for rid, f in fins.items()}
    assert len(set(map(tuple, streams.values()))) == 1   # all identical
    assert sum(f.prefix_cached for f in fins.values()) == 3
    assert eng.serve_report()["prefix_hits"] == 3
    assert eng.report()["prefix_cache"]["hit_rate"] == 0.75


# -- registered Pallas decode attention ----------------------------------------

def test_pallas_decode_attention_collapses_plan(setup):
    """Under kernel_mode('pallas') with unrolled layers, decode routes the
    cache attention through the registered ``_decode_attn_kernel``: paged
    tokens still match dense, and the per-layer masked-softmax einsum chain
    collapses into ONE custom node.  That node stays a kernel of its own
    (stitching around it would nest one pallas_call in another, which
    Mosaic does not lower); the ops between the kernels still stitch."""
    import dataclasses

    from repro.kernels import ops

    cfg, _, _ = setup
    cfg = dataclasses.replace(cfg, scan_layers=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts, news = _workload(cfg)
    prompts, news = prompts[:3], news[:3]

    with ops.kernel_mode("pallas"):
        svc = CompilationService(max_background=0)
        paged = Engine(model, params,
                       ServeConfig(batch=2, max_len=64, paged=True,
                                   page_size=8, stitch_execute=True),
                       stitch_service=svc)
        dense = Engine(model, params,
                       ServeConfig(batch=2, max_len=64, paged=False))
        ref = _drain_tokens(dense, prompts, news)
        got = _drain_tokens(paged, prompts, news)
        assert got == ref

        g = paged._exec._active.graph
        decode_customs = [
            n for n in g.nodes.values()
            if n.kind.value == "custom"
            and n.attrs.get("kernel") == "_decode_attn_kernel"
        ]
        assert len(decode_customs) == cfg.n_layers   # one per layer
        art = svc.compiler("stitch").compile(g, bypass_cache_lookup=True)
        groups = [grp.members for grp in art.groups]
        for n in decode_customs:
            assert frozenset([n.name]) in groups
        # 326 ops into 84 kernels, 29 of them stitched Pallas groups, for
        # the 2-layer reduced config
        assert art.stats.n_kernels * 3 < art.stats.n_ops
        assert art.stats.pallas_groups >= 10


# -- deprecation ---------------------------------------------------------------

def test_legacy_rect_generate_warns_once(setup):
    cfg, model, params = setup
    import repro.serve.engine as engine_mod
    engine_mod._LEGACY_RECT_WARNED = False
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab, (1, 8)).astype(np.int32)
    eng = Engine(model, params,
                 ServeConfig(batch=1, max_len=16, max_new_tokens=2))
    with pytest.warns(DeprecationWarning, match="prefill"):
        eng.generate(prompts.copy())
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)   # second call: silent
        eng.generate(prompts.copy())


def test_paged_rejects_mesh_config(setup):
    cfg, model, params = setup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices for a mesh")
    from jax.sharding import Mesh
    mesh = Mesh(np.array(devs[:2]), ("dp",))
    with pytest.raises(ValueError, match="paged"):
        Engine(model, params,
               ServeConfig(batch=2, max_len=16, paged=True), mesh=mesh)
