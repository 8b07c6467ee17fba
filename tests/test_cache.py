"""StitchCache subsystem: signature canonicalization, shape bucketing,
two-tier persistence, plan replay, and the miss-then-upgrade service."""

import time

import numpy as np
import pytest

from repro.cache import (
    BucketPolicy, CompilationService, EvictionPolicy, GroupRecord,
    MemoryStore, PlanRecord, StitchCache, compute_signature,
)
from repro.core import GraphBuilder, StitchCompiler, build_reference_fn
from repro.core.trace import trace_to_graph
from conftest import make_mlp_norm_graph, make_softmax_graph


def _softmax_graph(pname="x", rows=64, cols=256):
    b = GraphBuilder("softmax")
    x = b.param(pname, (rows, cols))
    m = b.reduce("max", x, axes=(1,))
    e = b.ew("exp", b.ew("sub", x, b.bcast(m, (rows, cols), (0,))))
    s = b.reduce("sum", e, axes=(1,))
    y = b.ew("div", e, b.bcast(s, (rows, cols), (0,)))
    return b.build(outputs=[y]), x


# -------------------------------------------------- signatures ---------------

def test_signature_invariant_under_renaming():
    g1, _ = _softmax_graph("x")
    g2, _ = _softmax_graph("completely_different_input_name")
    s1, s2 = compute_signature(g1), compute_signature(g2)
    assert s1.graph_key == s2.graph_key
    assert s1.shape_key == s2.shape_key


def test_signature_invariant_under_insertion_order():
    """Two independent chains inserted in opposite orders (trace-order
    permutation) must produce the same canonical signature."""
    def build(swap):
        b = GraphBuilder("perm")
        x = b.param("x", (32, 64))
        y = b.param("y", (32, 64))
        if swap:
            bb = b.ew("tanh", y)
            aa = b.ew("exp", x)
        else:
            aa = b.ew("exp", x)
            bb = b.ew("tanh", y)
        return b.build(outputs=[b.ew("add", aa, bb)])

    s1, s2 = compute_signature(build(False)), compute_signature(build(True))
    assert s1.graph_key == s2.graph_key
    assert s1.shape_key == s2.shape_key


def test_signature_invariant_under_trace_order():
    import jax.numpy as jnp

    def f1(x, y):
        a = jnp.exp(x)
        b = jnp.tanh(y)
        return a + b

    def f2(x, y):
        b = jnp.tanh(y)
        a = jnp.exp(x)
        return a + b

    x = np.zeros((8, 16), np.float32)
    g1, _ = trace_to_graph(f1, x, x)
    g2, _ = trace_to_graph(f2, x, x)
    assert compute_signature(g1).graph_key == compute_signature(g2).graph_key


def test_signature_distinguishes_structure():
    def build(op, dtype="float32"):
        b = GraphBuilder("g")
        x = b.param("x", (16, 32), dtype)
        y = b.param("y", (16, 32), dtype)
        return b.build(outputs=[b.ew(op, x, y)])

    base = compute_signature(build("add")).graph_key
    assert compute_signature(build("sub")).graph_key != base
    assert compute_signature(build("add", "bfloat16")).graph_key != base
    # operand order matters (sub is not commutative): swapping operands of
    # structurally distinguishable inputs must not collide
    b3 = GraphBuilder("g")
    x3 = b3.param("x", (16, 32))
    e3 = b3.ew("exp", x3)
    gc = b3.build(outputs=[b3.ew("sub", x3, e3)])
    b4 = GraphBuilder("g")
    x4 = b4.param("x", (16, 32))
    e4 = b4.ew("exp", x4)
    gd = b4.build(outputs=[b4.ew("sub", e4, x4)])
    assert compute_signature(gc).graph_key != compute_signature(gd).graph_key


def test_signature_shapes_factored_out():
    g1, _ = _softmax_graph(rows=100)
    g2, _ = _softmax_graph(rows=120)
    s1, s2 = compute_signature(g1), compute_signature(g2)
    assert s1.graph_key == s2.graph_key      # same program
    assert s1.shape_key != s2.shape_key      # different concrete shapes


# -------------------------------------------------- bucketing ----------------

def test_bucket_policy_pow2():
    p = BucketPolicy()
    assert p.bucket_shape((100, 256)) == (128, 256)
    assert p.bucket_shape((120, 256)) == (128, 256)
    assert p.bucket_shape((3, 100)) == (3, 128)   # small dims stay exact
    assert p.bucket_shape(()) == ()
    assert BucketPolicy(mode="exact").bucket_shape((100,)) == (100,)


def test_bucketed_shapes_share_cache_entry():
    cache = StitchCache()
    comp = StitchCompiler(mode="stitch", cache=cache)
    g100, x100 = _softmax_graph(rows=100)
    g120, x120 = _softmax_graph(rows=120)
    a = comp.compile(g100)
    assert a.stats.cache_status == "miss"
    b = comp.compile(g120)                   # same bucket (128): replay
    assert b.stats.cache_status == "hit"
    rep = cache.report()
    assert rep["total_hits"] == 1 and rep["total_misses"] == 1
    # hit and miss landed in the SAME bucket
    (bucket, counts), = rep["per_bucket"].items()
    assert counts == {"hits": 1, "misses": 1}
    # replayed executable is numerically identical to the reference
    rng = np.random.default_rng(0)
    inp = rng.standard_normal((120, 256)).astype(np.float32)
    ref = build_reference_fn(g120)({x120: inp})
    out = b({x120: inp})
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=2e-4, atol=2e-4)


def test_plans_keyed_by_hardware():
    from repro.core.cost import TPU_V5E, V100
    cache = StitchCache()
    g, _ = _softmax_graph()
    StitchCompiler(hw=V100, mode="stitch", cache=cache).compile(g)
    g2, _ = _softmax_graph("renamed")
    other = StitchCompiler(hw=TPU_V5E, mode="stitch", cache=cache).compile(g2)
    assert other.stats.cache_status == "miss"   # V100 plan must not shadow it


def test_plans_keyed_by_gen_config():
    """A plan solved under one GenConfig must not replay under another —
    the knobs (large_gemm_flops, pack_patterns, ...) change which patterns
    exist, so a stale hit would silently execute the wrong plan."""
    from repro.core.fusiongen import GenConfig
    cache = StitchCache()
    g, _ = _softmax_graph()
    StitchCompiler(mode="stitch", cache=cache).compile(g)
    g2, _ = _softmax_graph("renamed")
    other = StitchCompiler(
        mode="stitch", cache=cache,
        gen_cfg=GenConfig(large_gemm_flops=1.0)).compile(g2)
    assert other.stats.cache_status == "miss"
    # the default config still hits (None hashes like GenConfig())
    g3, _ = _softmax_graph("renamed_again")
    same = StitchCompiler(mode="stitch", cache=cache).compile(g3)
    assert same.stats.cache_status == "hit"


def test_graph_mutation_invalidates_live_memo():
    from repro.core import OpKind, OpNode
    cache = StitchCache()
    comp = StitchCompiler(mode="stitch", cache=cache)
    g, x = _softmax_graph()
    comp.compile(g)
    g.add(OpNode("late", OpKind.ELEMENTWISE, (64, 256), "float32",
                 (g.outputs[0],), {"op": "neg"}))
    g.mark_output("late")
    cg = comp.compile(g)                         # must NOT replay stale plan
    assert cg.stats.cache_status == "miss"
    rng = np.random.default_rng(0)
    inp = rng.standard_normal((64, 256)).astype(np.float32)
    ref = build_reference_fn(g)({x: inp})
    out = cg({x: inp})
    np.testing.assert_allclose(np.asarray(out["late"]), np.asarray(ref["late"]),
                               rtol=2e-4, atol=2e-4)


def test_distant_shapes_miss():
    cache = StitchCache()
    comp = StitchCompiler(mode="stitch", cache=cache)
    g64, _ = _softmax_graph(rows=64)
    g100, _ = _softmax_graph(rows=100)
    comp.compile(g64)
    out = comp.compile(g100)                 # bucket 128 != 64
    assert out.stats.cache_status == "miss"
    assert cache.report()["total_misses"] == 2


# -------------------------------------------------- store / eviction ---------

def _dummy_record(i):
    return PlanRecord(
        graph_key=f"g{i}", bucket_key="b", shape_key="s", mode="stitch",
        hw="TPU_V5E", n_nodes=1, groups=(GroupRecord((0,), "op"),))


def test_memory_lru_eviction():
    ms = MemoryStore(capacity=2)
    for i in range(3):
        ms.put(_dummy_record(i))
    assert len(ms) == 2 and ms.evictions == 1
    # keys carry the placement component ("" = single-device) since v2 and
    # the GenConfig digest ("" for records frozen without a compiler) since v3
    assert ms.get(("g0", "b", "stitch", "TPU_V5E", "", "")) is None   # evicted
    assert ms.get(("g2", "b", "stitch", "TPU_V5E", "", "")) is not None


def test_disk_roundtrip_replay_matches_fresh_compile(tmp_path, rng):
    g = make_mlp_norm_graph()
    inputs = {
        "x": rng.standard_normal((128, 256), dtype=np.float32),
        "w": (rng.standard_normal((256, 256)) * 0.05).astype(np.float32),
        "gamma": rng.standard_normal(256, dtype=np.float32),
        "eps": np.float32(1e-5),
    }
    d = str(tmp_path / "plans")
    cold = StitchCompiler(mode="stitch", cache=StitchCache(directory=d)).compile(g)
    assert cold.stats.cache_status == "miss"

    # new process simulation: fresh cache over the same directory, fresh
    # graph object (isomorphic rebuild)
    g2 = make_mlp_norm_graph()
    warm_cache = StitchCache(directory=d)
    warm = StitchCompiler(mode="stitch", cache=warm_cache).compile(g2)
    assert warm.stats.cache_status == "hit"
    assert warm.stats.n_kernels == cold.stats.n_kernels
    assert warm.stats.pallas_groups == cold.stats.pallas_groups

    ref = build_reference_fn(g2)(inputs)
    out_cold, out_warm = cold(inputs), warm(inputs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out_warm[k]), np.asarray(ref[k]),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(out_warm[k]),
                                   np.asarray(out_cold[k]),
                                   rtol=2e-4, atol=2e-4)


# -------------------------------------------------- replay skips pipeline ----

def test_cache_hit_skips_pattern_gen_ilp_and_tuning(monkeypatch):
    cache = StitchCache()
    comp = StitchCompiler(mode="stitch", cache=cache)
    g, _ = _softmax_graph()
    first = comp.compile(g)
    assert first.stats.cache_status == "miss"

    def boom(*a, **k):
        raise AssertionError("expensive pipeline stage ran on a cache hit")

    from repro.core.tuner import TemplateTuner
    monkeypatch.setattr("repro.core.compiler.generate_patterns", boom)
    monkeypatch.setattr("repro.core.compiler.solve_fusion_plan", boom)
    monkeypatch.setattr(TemplateTuner, "tune", boom)

    # same graph object (live memo) ...
    second = comp.compile(g)
    assert second.stats.cache_status == "hit"
    assert second.stats.n_kernels == first.stats.n_kernels
    # ... and an isomorphic rebuild (record replay)
    g2, _ = _softmax_graph("renamed")
    third = comp.compile(g2)
    assert third.stats.cache_status == "hit"
    assert third.stats.n_kernels == first.stats.n_kernels


def test_warm_compile_at_least_10x_faster():
    cache = StitchCache()
    comp = StitchCompiler(mode="stitch", cache=cache)
    g = make_mlp_norm_graph()
    t0 = time.perf_counter()
    comp.compile(g)
    cold = time.perf_counter() - t0
    comp.compile(g)                          # absorb one-time warm-path setup
    t0 = time.perf_counter()
    warm_cg = comp.compile(g)
    warm = time.perf_counter() - t0
    assert warm_cg.stats.cache_status == "hit"
    assert cold / max(warm, 1e-9) >= 10.0, (cold, warm)


# -------------------------------------------------- service ------------------

def test_service_miss_then_upgrade():
    svc = CompilationService(StitchCache(), fallback_mode="xla")
    g, x = _softmax_graph()
    fb, status = svc.compile_or_fallback(g)
    assert status == "miss"
    assert fb.stats.mode == "xla"            # served immediately, unstitched
    svc.wait(timeout=120)
    g2, x2 = _softmax_graph("renamed")       # background compile landed
    up, status = svc.compile_or_fallback(g2)
    assert status == "hit"
    assert up.stats.mode == "stitch" and up.stats.cache_status == "hit"
    rng = np.random.default_rng(0)
    inp = rng.standard_normal((64, 256)).astype(np.float32)
    ref = build_reference_fn(g2)({x2: inp})
    out = up({x2: inp})
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=2e-4, atol=2e-4)


def test_engine_miss_then_upgrade_identical_tokens():
    import jax
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig
    from repro.train import init_state

    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    state = init_state(model, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 8)).astype(np.int32)

    base = Engine(model, state.params,
                  ServeConfig(batch=2, max_len=48, max_new_tokens=3))
    ref = base.generate(prompts.copy())

    svc = CompilationService(StitchCache())
    eng = Engine(model, state.params,
                 ServeConfig(batch=2, max_len=48, max_new_tokens=3,
                             stitch_execute=True),
                 stitch_service=svc)
    first = eng.generate(prompts.copy())
    # the shared exec layer polls per decode step (the scheduler-path
    # behavior, now unified), so the background compile may land and
    # upgrade mid-generate; anything but a failure is healthy here
    assert eng.stitch_status in ("miss", "pending", "hit")
    np.testing.assert_array_equal(first, ref)     # fallback/upgraded serve
    svc.wait(timeout=300)
    second = eng.generate(prompts.copy())
    assert eng.stitch_status == "hit"             # upgraded to stitched plan
    np.testing.assert_array_equal(second, ref)    # stitched decode identical
    rep = eng.stitch_report()
    assert rep["plan"]["mode"] == "stitch"
    assert rep["plan"]["n_kernels"] < rep["plan"]["n_ops"]
