"""The public execution layer: ``repro.exec.stitch()``.

Contract under test — the jit-like transform every subsystem now dispatches
through: pytree-aware tracing (nested containers, kwargs), static-argnum
specialization, shape-drift fallback, miss-then-upgrade, donation, sharded
dispatch equality, one jitted program per served artifact, background-failure
surfacing, and the anytime ILP budget feeding the same pipeline.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.cache import CompilationService
from repro.configs import get_reduced
from repro.exec import StitchedFunction, stitch
from repro.launch.mesh import make_host_mesh
from repro.models import build_model


def ck(a, b, rtol=1e-6, atol=1e-6):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.fixture
def svc():
    # max_background=0: upgrades land only when the test compiles them —
    # deterministic miss-then-upgrade points
    return CompilationService(max_background=0)


# ---------------------------------------------------------------------------
# the tracing boundary: pytrees, kwargs, statics, drift
# ---------------------------------------------------------------------------

def test_stitch_pytree_and_kwargs_roundtrip(svc):
    """Nested dict/tuple inputs AND outputs round-trip through stitch()
    matching the jit reference, with kwargs flowing as traced inputs."""
    def fn(tree, pair, bias=None):
        x, y = pair
        h = tree["a"]["w"] * jnp.tanh(x) + y
        if bias is not None:
            h = h + bias["b"]
        return {"out": (h, h * 2.0), "norm": jnp.sqrt(jnp.sum(h * h, -1))}

    rng = np.random.default_rng(0)
    tree = {"a": {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)}}
    pair = (jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
            jnp.asarray(rng.standard_normal((16,)), jnp.float32))
    bias = {"b": jnp.asarray(rng.standard_normal((16,)), jnp.float32)}

    sf = stitch(fn, service=svc)
    out = sf(tree, pair, bias=bias)
    ref = jax.jit(fn)(tree, pair, bias=bias)
    assert (jax.tree_util.tree_structure(out)
            == jax.tree_util.tree_structure(ref))
    ck(out, ref)
    assert sf.stitched_calls == 1 and sf.fallback_calls == 0
    assert sf.status in ("miss", "pending")

    # a kwargs *structure* change is signature drift: jit serves that call
    out2 = sf(tree, pair)
    ck(out2, jax.jit(fn)(tree, pair))
    assert sf.fallback_calls == 1


def test_stitch_static_argnums_retrace_on_change(svc):
    def fn(x, n):
        return {"p": x ** n, "s": jnp.sum(x) * n}

    x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 8)),
                    jnp.float32)
    sf = stitch(fn, service=svc, static_argnums=(1,))
    ck(sf(x, 2), jax.jit(fn, static_argnums=(1,))(x, 2))
    ck(sf(x, 3), jax.jit(fn, static_argnums=(1,))(x, 3))
    ck(sf(x, 2), jax.jit(fn, static_argnums=(1,))(x, 2))   # cached retrace
    assert sf.report()["specializations"] == 2
    assert sf.stitched_calls == 3 and sf.fallback_calls == 0


def test_stitch_shape_drift_falls_back(svc):
    def fn(d):
        return {"y": jnp.tanh(d["x"]) * d["g"]}

    rng = np.random.default_rng(2)
    d = {"x": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
         "g": jnp.asarray(rng.standard_normal((16,)), jnp.float32)}
    sf = stitch(fn, service=svc)
    sf(d)
    assert sf.fallback_calls == 0
    drifted = {"x": d["x"][:, :8], "g": d["g"][:8]}
    ck(sf(drifted), jax.jit(fn)(drifted))          # served by jit, this call
    assert sf.fallback_calls == 1
    sf(d)                                          # original shape: stitched
    assert sf.fallback_calls == 1 and sf.stitched_calls == 2


def test_stitch_upgrade_hits_and_matches(svc):
    def fn(d):
        h = jnp.exp(d["x"] - jnp.max(d["x"], -1, keepdims=True))
        return h / jnp.sum(h, -1, keepdims=True)

    d = {"x": jnp.asarray(np.random.default_rng(3).standard_normal((16, 64)),
                          jnp.float32)}
    sf = stitch(fn, service=svc)
    first = sf(d)
    assert sf.status in ("miss", "pending")
    assert sf.compiled.stats.mode == "xla"         # fallback artifact
    svc.compiler("stitch").compile(sf.graph, bypass_cache_lookup=True)
    second = sf(d)
    assert sf.status == "hit"
    assert sf.compiled.stats.mode == "stitch"
    ck(first, jax.jit(fn)(d))
    ck(second, jax.jit(fn)(d))
    assert sf.plan_stats()["n_kernels"] < sf.plan_stats()["n_ops"]


def test_stitch_donate_argnums_deletes_consumed(svc):
    def fn(state, g):
        return {"w": state["w"] - 0.1 * g}

    state = {"w": jnp.ones((8, 8), jnp.float32)}
    g = jnp.ones((8, 8), jnp.float32)
    sf = stitch(fn, service=svc, donate_argnums=(0,))
    out = sf(state, g)
    assert state["w"].is_deleted()
    assert not out["w"].is_deleted()


def test_stitch_donation_keeps_passthrough_outputs(svc):
    """A donated input leaf that the function returns unchanged is aliased
    by the output — it must survive the donation (jit aliases it safely;
    the stitched delete must not corrupt the result)."""
    def fn(state, g):
        return {"w": state["w"] - 0.1 * g, "frozen": state["frozen"]}

    state = {"w": jnp.ones((8, 8), jnp.float32),
             "frozen": jnp.full((4,), 7.0, jnp.float32)}
    g = jnp.ones((8, 8), jnp.float32)
    sf = stitch(fn, service=svc, donate_argnums=(0,))
    out = sf(state, g)
    assert sf.stitched_calls == 1
    assert state["w"].is_deleted()               # genuinely consumed
    assert not out["frozen"].is_deleted()        # passthrough survives
    np.testing.assert_array_equal(np.asarray(out["frozen"]), np.full(4, 7.0))


def test_stitch_shadow_mode_serves_jit_but_reports(svc):
    def fn(x):
        return jnp.tanh(x) * 2.0

    x = jnp.ones((4, 4), jnp.float32)
    sf = stitch(fn, mode="shadow", service=svc)
    ck(sf(x), jax.jit(fn)(x))
    assert sf.jit_calls == 1 and sf.stitched_calls == 0
    assert sf.report()["plan"]["mode"] == "xla"    # compiled for reporting


# ---------------------------------------------------------------------------
# sharded dispatch (the --model-parallel 2 acceptance shape)
# ---------------------------------------------------------------------------

def test_stitch_sharded_matches_jit_reference():
    """An arbitrary pytree function with an in-body collective, stitched
    over the (4, 2) host mesh, matches its (single-device) jit reference
    across the miss-then-upgrade transition, under a mesh-keyed placement."""
    mesh = make_host_mesh(2)
    allax = tuple(mesh.axis_names)

    def fn(params, b):
        h = jnp.tanh(b["x"] @ params["w"]) + params["c"]
        loss = jax.lax.pmean(jnp.mean(h), allax)
        return {"loss": loss, "h": h * 2.0}

    def ref_fn(params, b):
        h = jnp.tanh(b["x"] @ params["w"]) + params["c"]
        return {"loss": jnp.mean(h), "h": h * 2.0}

    rng = np.random.default_rng(4)
    params = {"w": jnp.asarray(rng.standard_normal((16, 16)), jnp.float32),
              "c": jnp.asarray(rng.standard_normal((16,)), jnp.float32)}
    b = {"x": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)}

    svc = CompilationService(max_background=0)
    sf = stitch(fn, service=svc, mesh=mesh,
                in_specs=(P(), P(allax)),
                out_specs={"loss": P(), "h": P(allax)})
    ref = jax.jit(ref_fn)(params, b)
    ck(sf(params, b), ref)
    assert sf.placement.startswith("mesh[data=4,model=2]")
    assert sf._active.sharded
    svc.compiler("stitch", sf.placement).compile(sf.graph,
                                                 bypass_cache_lookup=True)
    ck(sf(params, b), ref)
    assert sf.status == "hit" and sf.compiled.stats.mode == "stitch"
    # mesh-keyed: the plan does not exist at the single-device placement
    assert svc.cache.lookup(sf.graph, svc.compiler("stitch")) is None


def test_stitch_mesh_requires_specs():
    mesh = make_host_mesh(1)
    if mesh.size == 1:
        pytest.skip("needs a multi-device host")
    with pytest.raises(ValueError, match="in_specs"):
        stitch(lambda x: x, mode="jit", mesh=mesh)


# ---------------------------------------------------------------------------
# one jitted program per served artifact
# ---------------------------------------------------------------------------

def _small_fn(x):
    h = x * jax.nn.sigmoid(x)
    return h / (1.0 + jnp.sum(h * h, axis=-1, keepdims=True))


def _landed(fn, x, svc):
    """``stitch(fn)`` whose stitched plan has landed before its first call."""
    sf = stitch(fn, service=svc, name="one_program")
    sf.warmup(x)
    svc.compiler("stitch").compile(sf.graph, bypass_cache_lookup=True)
    sf.poll_upgrade()
    assert sf.status == "hit" and sf.compiled.stats.mode == "stitch"
    return sf


def _walk_spans(tracer):
    return [e for e in tracer.events() if e["name"] == "exec.walk"]


@pytest.fixture
def traced():
    obs.clear_trace()
    obs.enable_tracing()
    yield obs.tracer
    obs.disable_tracing()
    obs.clear_trace()


def test_off_mesh_stitched_call_dispatches_one_program(svc, traced):
    """Every off-mesh stitched call is one launch with no upload, while the
    walk traced into the program issued the plan's ``walk_launches``; the
    program is built once for the artifact."""
    x = jnp.asarray(np.random.default_rng(5).standard_normal((8, 64)),
                    jnp.float32)
    sf = _landed(_small_fn, x, svc)
    for _ in range(3):
        ck(sf(x), jax.jit(_small_fn)(x), rtol=2e-5, atol=2e-5)
    walks = _walk_spans(traced)
    assert [w["args"] for w in walks] == [{"launches": 1, "uploads": 0}] * 3
    plan = sf.report()["plan"]
    assert (plan["launches"], plan["uploads"]) == (1, 0)
    assert plan["walk_launches"] == sf.compiled.launches > 0
    assert plan["program_builds"] == 1
    assert sf.stitched_calls == 3 and sf.fallback_calls == 0


def test_upgrade_swap_rebuilds_the_program_once(svc, traced):
    """The fallback artifact's program serves until the upgrade lands; the
    swap builds the stitched artifact's program once, and later calls reuse
    it."""
    x = jnp.asarray(np.random.default_rng(6).standard_normal((16, 64)),
                    jnp.float32)
    sf = stitch(_small_fn, service=svc)
    sf(x)
    assert sf.status in ("miss", "pending")
    assert sf.report()["plan"]["program_builds"] == 1
    fallback_program = sf._active.program
    svc.compiler("stitch").compile(sf.graph, bypass_cache_lookup=True)
    for _ in range(3):
        ck(sf(x), jax.jit(_small_fn)(x), rtol=2e-5, atol=2e-5)
    assert sf.status == "hit"
    assert sf.report()["plan"]["program_builds"] == 2
    assert sf._active.program is not fallback_program
    assert [w["args"]["launches"] for w in _walk_spans(traced)] == [1] * 4


def test_signature_drift_falls_back_to_jit_with_no_walk(svc, traced):
    """A drifted call is served by jit: no walk span, no program built for
    the drifted shapes."""
    x = jnp.ones((8, 64), jnp.float32)
    sf = _landed(_small_fn, x, svc)
    sf(x)
    obs.clear_trace()
    drifted = jnp.ones((4, 64), jnp.float32)
    ck(sf(drifted), jax.jit(_small_fn)(drifted), rtol=2e-5, atol=2e-5)
    assert _walk_spans(traced) == []
    assert sf.fallback_calls == 1 and sf.stitched_calls == 1
    assert sf.report()["plan"]["program_builds"] == 1


def test_walk_that_fails_to_trace_is_served_by_jit(svc, monkeypatch):
    """A walk that ``jax.jit`` cannot trace degrades like a failed trace of
    the function: jit serves the call and every later one, and the error is
    in ``report()["errors"]``."""
    from repro.core.compiler import CompiledGraph

    def untraceable(self, inputs):
        raise TypeError("walk cannot be traced")

    x = jnp.ones((8, 64), jnp.float32)
    sf = stitch(_small_fn, service=svc, name="untraceable")
    sf.warmup(x)
    monkeypatch.setattr(CompiledGraph, "__call__", untraceable)
    for _ in range(2):
        ck(sf(x), jax.jit(_small_fn)(x), rtol=2e-5, atol=2e-5)
    assert sf.status == "error" and sf.fallback_calls == 2
    rep = sf.report()
    assert obs.validate_exec_report(rep) == []
    assert list(rep["errors"].values()) == ["TypeError: walk cannot be traced"]
    assert list(rep["errors"]) == ["exec/untraceable/"]


class _Tap:
    """Stands in for an engine's stitched function and keeps the arguments
    of each call."""

    def __init__(self, fn):
        self.fn = fn
        self.args = []

    def __call__(self, *args):
        self.args.append(args)
        return self.fn(*args)

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _eager_walk(sf, args):
    """The active specialization's artifact walked eagerly, group by group,
    at ``args``."""
    sp = sf._active
    outs = sp.compiled(dict(zip(sp.names, jax.tree_util.tree_leaves(
        (tuple(args), {})))))
    return jax.tree_util.tree_unflatten(
        sp.out_tree, [outs[o] for o in sp.graph.outputs])


def _tapped_engine(name, tmp_path):
    """A paged engine whose stitched plans have landed (reduced qwen3, or
    the small DeepSeek-V2 of ``test_deepseek_v2``), with one request
    prefilled and one decode step served through taps."""
    from test_deepseek_v2 import TINY, _program, _stitched_engine, weights

    if name == "qwen3":
        model = build_model(get_reduced("qwen3_1_7b"))
        params = model.init(jax.random.PRNGKey(0))
    else:
        model, params = _program(TINY), weights.make(TINY, 3)
    eng = _stitched_engine(model, params, tmp_path)
    eng._exec, eng._prefill_exec = _Tap(eng._exec), _Tap(eng._prefill_exec)
    eng.insert(eng.prefill(np.arange(1, 12, dtype=np.int32)), 1)
    eng.generate_step(1)
    return eng


@pytest.mark.parametrize("case", ["small", "qwen3.decode", "qwen3.prefill",
                                  "deepseek.decode"])
def test_stitched_program_matches_eager_walk(case, svc, tmp_path):
    """The one jitted program computes what the artifact's eager group walk
    computes at the same inputs: a small function, and the reduced qwen3
    and DeepSeek paged decode steps (gather path) and a prefill bucket.
    Inside one program XLA fuses the jnp groups and may reorder a float32
    sum, so the two agree to float32 rounding."""
    if case == "small":
        x = jnp.asarray(np.random.default_rng(7).standard_normal((8, 64)),
                        jnp.float32)
        sf, args = _landed(_small_fn, x, svc), (x,)
        got = sf(*args)
        builds = {1}                 # the plan landed before the first call
    else:
        name, step = case.split(".")
        eng = _tapped_engine(name, tmp_path)
        tap = eng._exec if step == "decode" else eng._prefill_exec
        sf, args = tap.fn, tap.args[-1]
        assert sf.status == "hit" and sf.compiled.stats.mode == "stitch"
        got = sf(*args)
        # the plan's program, after the fallback artifact's unless the
        # background compile landed before the first call's poll
        builds = {1, 2}
        if step == "decode":
            path = eng.stitch_report()["paged_attention"]
            assert path == ("gather" if name == "qwen3" else "mla-gather")
    assert sf.report()["calls"]["fallback"] == 0
    assert sf.report()["plan"]["program_builds"] in builds
    assert sf._active.program_for is sf.compiled
    want = _eager_walk(sf, args)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    ck(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# background-compile failure: surfaced once, never silently swallowed
# ---------------------------------------------------------------------------

def test_background_failure_warns_once_and_reports(monkeypatch):
    def fn(x):
        return jnp.tanh(x) * jnp.exp(x)

    x = jnp.ones((8, 32), jnp.float32)
    svc = CompilationService(max_background=0)   # no thread yet
    sf = stitch(fn, service=svc)
    sf(x)                                        # trace + fallback artifact
    assert sf.status in ("miss", "pending")

    def boom(*a, **k):
        raise RuntimeError("ILP exploded")

    # only stitch-mode compiles solve the ILP; the xla fallback is unaffected
    monkeypatch.setattr("repro.core.compiler.solve_fusion_plan", boom)
    svc.max_background = 2
    sf(x)                                        # poll re-kicks the compile
    svc.wait(60.0)
    with pytest.warns(RuntimeWarning, match="ILP exploded"):
        sf(x)                                    # failure surfaced, once
    assert sf.status == "failed"
    rep = sf.report()
    assert "ILP exploded" in rep["error"]
    assert "ILP exploded" in rep["service_error"]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        out = sf(x)                              # no second warning; and the
    ck(out, jax.jit(fn)(x))                      # fallback still serves
    # the doomed compile is not re-kicked
    assert not svc.ensure_compiling(sf.graph)


# ---------------------------------------------------------------------------
# anytime ILP: wall-clock budget -> greedy fallback plan
# ---------------------------------------------------------------------------

def _mlp_graph(rows=64, d=128):
    from repro.core import GraphBuilder

    b = GraphBuilder("mlp_norm")
    x = b.param("x", (rows, d))
    w = b.param("w", (d, d))
    gm = b.param("gamma", (d,))
    h = b.dot(x, w, name="dot_0")
    mu = b.reduce("mean", h, axes=(1,), keepdims=True)
    dlt = b.ew("sub", h, b.bcast(mu, (rows, d), (0, 1)))
    v = b.reduce("mean", b.ew("square", dlt), axes=(1,), keepdims=True)
    eps = b.const("eps", ())
    b.graph[eps].attrs["value"] = np.float32(1e-6)
    r = b.ew("rsqrt", b.ew("add", v, eps))
    y = b.ew("mul", b.ew("mul", dlt, b.bcast(r, (rows, d), (0, 1))),
             b.ew("relu", b.bcast(gm, (rows, d), (1,))))
    return b.build(outputs=[y])


def test_anytime_ilp_greedy_fallback_is_valid():
    from repro.core import CostModel, GenConfig, generate_patterns
    from repro.core.ilp import solve_fusion_plan

    g = _mlp_graph()
    patterns = generate_patterns(g, GenConfig())
    scores = [CostModel().score(p).score for p in patterns]

    exact = solve_fusion_plan(g, patterns, scores)
    assert exact.method == "ilp" and not exact.budget_expired

    budgeted = solve_fusion_plan(g, patterns, scores, budget_seconds=0.0)
    assert budgeted.method == "greedy" and budgeted.budget_expired
    # valid plan: pairwise disjoint members, every member a graph node
    seen = set()
    for p in budgeted.chosen:
        assert not (p.members & seen)
        seen |= p.members
    assert budgeted.objective > 0


def test_plan_budget_compiles_correct_executable(rng):
    from repro.core import StitchCompiler, build_reference_fn

    g = _mlp_graph()
    inputs = {"x": rng.standard_normal((64, 128)).astype(np.float32),
              "w": (rng.standard_normal((128, 128)) * 0.05).astype(np.float32),
              "gamma": rng.standard_normal(128).astype(np.float32)}
    compiled = StitchCompiler(mode="stitch", plan_budget=0.0).compile(g)
    assert compiled.stats.ilp is not None
    assert compiled.stats.ilp.method == "greedy"
    assert compiled.stats.n_kernels < compiled.stats.n_ops
    ref = build_reference_fn(g)(inputs)
    out = compiled(inputs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=2e-4, atol=2e-4)


def test_stitched_function_rejects_bad_mode():
    with pytest.raises(ValueError, match="mode"):
        StitchedFunction(lambda x: x, mode="nope")
