"""repro.obs — span tracing, metrics registry, measured timer, and the
unified exec-report schema.

Contract under test: span nesting and Chrome-trace schema validity (every
exported event Perfetto-loadable), registry snapshot round-trip through
JSON and Prometheus text, the disabled fast path costing nothing and
recording nothing, the one shared percentile implementation, the
``repro.obs/exec-report@1`` schema across engine / train step / optimizer,
and an end-to-end trace of a tiny stitched serve run where the
fallback→stitched upgrade event lands *after* the compile-land event.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.cache import CompilationService
from repro.exec import stitch
from repro.obs.metrics import Histogram, MetricsRegistry, percentiles
from repro.obs.trace import Tracer


@pytest.fixture
def traced():
    """Enable the process tracer for one test, clean before and after."""
    obs.clear_trace()
    obs.enable_tracing()
    yield obs.tracer
    obs.disable_tracing()
    obs.clear_trace()


@pytest.fixture
def svc():
    # max_background=0: upgrades land only when the test compiles them —
    # deterministic miss-then-upgrade points
    return CompilationService(max_background=0)


# ---------------------------------------------------------------------------
# tracer: spans, events, Chrome-trace export
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="test", k=1):
        with tr.span("inner", cat="test"):
            time.sleep(0.001)
        tr.event("marker", cat="test", x=7)
    evs = tr.events()
    names = [e["name"] for e in evs]
    # spans record at exit: inner closes before outer; the instant marker
    # fires between them
    assert names == ["inner", "marker", "outer"]
    inner, marker, outer = evs
    assert inner["ph"] == "X" and outer["ph"] == "X" and marker["ph"] == "i"
    # the inner interval nests inside the outer one
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"k": 1}
    assert marker["args"] == {"x": 7}


def test_span_set_attaches_args_discovered_mid_span():
    tr = Tracer(enabled=True)
    with tr.span("stage", cat="test", fixed=1) as s:
        s.set(found=42)
    (ev,) = tr.events()
    assert ev["args"] == {"fixed": 1, "found": 42}


def test_chrome_trace_schema_and_json_validity(tmp_path):
    """Every exported event carries the Chrome trace-event required fields
    and the whole document survives a JSON round-trip (Perfetto-loadable)."""
    tr = Tracer(enabled=True)
    with tr.span("compile.graph", cat="compile", graph="g"):
        tr.event("cache.miss", cat="cache")
    tr.counter_event("serve.slots", active=3, free=1)
    path = tr.save(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in events)
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)
    for e in events:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in e, f"event {e} missing {key}"
        assert e["ph"] in ("X", "i", "C", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"


def test_disabled_tracer_is_free_and_records_nothing():
    """The no-op contract: with tracing off, span()/event() must be cheap
    (shared null span, single attribute check) and record zero events."""
    tr = Tracer(enabled=False)
    s1 = tr.span("a", x=1)
    s2 = tr.span("b")
    assert s1 is s2                      # the one shared NULL_SPAN
    t0 = time.perf_counter()
    for _ in range(100_000):
        with tr.span("hot"):
            pass
        tr.event("hot")
    assert time.perf_counter() - t0 < 1.0    # generous absolute bound
    assert len(tr) == 0
    # the module-level façade takes the same early exit
    obs.disable_tracing()
    with obs.span("x") as s:
        s.set(anything=1)
    obs.event("y")
    assert len(obs.tracer) == 0


def test_tracer_clear_resets_epoch_and_buffer():
    tr = Tracer(enabled=True)
    tr.event("one")
    assert len(tr) == 1
    tr.clear()
    assert len(tr) == 0 and tr.events() == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_percentiles_shared_implementation_edge_cases():
    assert percentiles(()) == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    one = percentiles([3.5])
    assert one == {"p50": 3.5, "p95": 3.5, "p99": 3.5}
    many = percentiles(range(1, 101))
    assert many["p50"] == pytest.approx(50.5)
    assert many["p99"] == pytest.approx(99.01)
    # serve.metrics re-exports the same function (satellite: one impl)
    from repro.serve.metrics import percentiles as serve_pct
    assert serve_pct is percentiles


def test_histogram_summary_and_capacity_bound():
    h = Histogram(capacity=8)
    assert h.summary() == {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                           "mean": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    for v in range(20):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 20 and s["sum"] == 190.0       # exact despite drops
    assert s["min"] == 0.0 and s["max"] == 19.0
    assert len(h.values) <= 8


def test_registry_snapshot_roundtrip_and_prometheus(tmp_path):
    reg = MetricsRegistry()
    reg.counter("tokens_total").inc(5)
    reg.counter("cache_lookups", result="hit").inc(2)
    reg.gauge("occupancy").set(0.75)
    reg.histogram("step_seconds").observe(0.1)
    reg.histogram("step_seconds").observe(0.3)
    reg.register_provider("stitch", lambda: {"status": "hit"})

    snap = reg.snapshot()
    assert snap["counters"]["tokens_total"] == 5
    assert snap["counters"]['cache_lookups{result="hit"}'] == 2
    assert snap["gauges"]["occupancy"] == 0.75
    assert snap["histograms"]["step_seconds"]["count"] == 2
    assert snap["providers"]["stitch"] == {"status": "hit"}

    path = tmp_path / "metrics.json"
    reg.to_json(str(path), run="t")
    loaded = json.loads(path.read_text())
    assert loaded.pop("run") == "t"
    assert loaded == json.loads(json.dumps(snap))       # round-trip exact

    prom = reg.to_prometheus()
    assert "# TYPE tokens_total counter" in prom
    assert "tokens_total 5" in prom
    assert 'cache_lookups{result="hit"} 2' in prom
    assert "# TYPE step_seconds summary" in prom
    assert "step_seconds_count 2" in prom
    assert 'quantile="0.50"' in prom


def test_registry_kind_clash_and_provider_error():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.register_provider("boom", lambda: 1 / 0)
    snap = reg.snapshot()
    assert "ZeroDivisionError" in snap["providers"]["boom"]["error"]


# ---------------------------------------------------------------------------
# the unified exec-report schema (satellite: one documented shape)
# ---------------------------------------------------------------------------

def _small_fn(x):
    h = x * jax.nn.sigmoid(x)
    return h / (1.0 + jnp.sum(h * h, axis=-1, keepdims=True))


def test_exec_report_schema_stitched_function(svc):
    sf = stitch(_small_fn, service=svc, name="schema_fn")
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 16)),
                    jnp.float32)
    sf(x)
    rep = sf.report()
    assert obs.validate_exec_report(rep) == []
    assert rep["schema"] == obs.EXEC_REPORT_SCHEMA
    assert rep["name"] == "schema_fn"
    assert rep["calls"] == {"stitched": 1, "fallback": 0, "jit": 0}
    # compat aliases stay in sync
    assert rep["stitched_calls"] == rep["calls"]["stitched"]
    assert rep["errors"] == {}
    assert rep["cache"]["total_misses"] >= 1
    assert "per_placement" in rep["cache"]


def test_exec_report_schema_uniform_across_callers(svc):
    """Engine (even jit-mode), train step's grad/optimizer, and PackedAdamW
    all report the same schema — dashboards special-case nothing."""
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.optim.packed import PackedAdamW
    from repro.serve import Engine, ServeConfig

    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(batch=2, max_len=16))
    assert obs.validate_exec_report(eng.stitch_report()) == []   # jit mode

    tiny = {"w": jnp.ones((4, 4), jnp.float32)}
    packed = PackedAdamW(AdamWConfig(lr=1e-3), tiny, use_compiler=False)
    rep = packed.report()
    assert obs.validate_exec_report(rep) == []
    assert rep["status"] == "jnp"
    assert rep["n_leaves"] == 1


def test_exec_report_surfaces_service_errors(svc):
    """A failed background compile shows up in ``errors`` (stringified
    service key -> message), not just the scalar ``service_error``."""
    sf = stitch(_small_fn, service=svc, name="err_fn")
    x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 8)),
                    jnp.float32)
    sf(x)
    sig = svc.cache.signature_of(sf.graph)
    key = svc.cache.key_for(sig, "stitch", svc.hw.name, "")
    svc.errors[key] = "RuntimeError: ILP exploded"
    rep = sf.report()
    assert obs.validate_exec_report(rep) == []
    assert list(rep["errors"].values()) == ["RuntimeError: ILP exploded"]
    assert all(isinstance(k, str) for k in rep["errors"])


# ---------------------------------------------------------------------------
# measured kernel timer
# ---------------------------------------------------------------------------

def test_measured_timer_records_per_path_and_modeled(svc, traced):
    reg = obs.registry()
    reg.clear()
    sf = stitch(_small_fn, service=svc, name="timed_fn")
    x = jnp.asarray(np.random.default_rng(2).standard_normal((8, 16)),
                    jnp.float32)
    sf(x)                                        # untimed warm call
    assert sf.report()["measured"] is None
    obs.enable_timing()
    try:
        for _ in range(3):
            sf(x)
    finally:
        obs.disable_timing()
    rep = sf.report()
    meas = rep["measured"]["stitched"]
    assert meas["count"] == 3 and meas["min"] > 0.0
    # the same numbers landed in the registry and in the trace
    hist = reg.histogram("exec_measured_seconds", fn="timed_fn",
                         path="stitched")
    assert hist.count == 3
    timed = [e for e in obs.tracer.events() if e["name"] == "exec.measured"]
    assert len(timed) == 3
    assert all(e["args"]["path"] == "stitched" for e in timed)


# ---------------------------------------------------------------------------
# e2e: tiny stitched serve run, upgrade strictly after compile-land
# ---------------------------------------------------------------------------

def test_e2e_stitched_serve_trace_upgrade_after_land(traced):
    """The acceptance scenario: a stitched serve run leaves a trace with
    compile-stage spans, a cache hit/miss event per compiled graph,
    per-step decode spans, and a fallback→stitched upgrade event whose
    timestamp is strictly after the compile.land event's."""
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    svc = CompilationService(max_background=0)   # deterministic upgrade
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, stitch_execute=True),
                 stitch_service=svc)
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab, (5,)).astype(np.int32),
                   max_new_tokens=3)
    eng.step()                                   # miss: fallback serves
    assert eng.stitch_status in ("miss", "pending")
    # land the stitch compile (what the background thread would do)
    sp = eng._exec._active
    svc.compiler("stitch", sp.placement).compile(sp.graph,
                                                 bypass_cache_lookup=True)
    # fresh requests decode after the land: their first poll upgrades
    # (with EOS off the scheduler chunked the first batch's whole budget
    # into step one, so new work is what drives post-land decode calls)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab, (5,)).astype(np.int32),
                   max_new_tokens=3)
    eng.drain()
    assert eng.stitch_status == "hit"

    events = obs.tracer.events()
    names = [e["name"] for e in events]
    assert "compile.graph" in names              # compile-stage spans
    assert "compile.pattern_gen" in names and "compile.ilp" in names
    assert "cache.miss" in names                 # per-graph lookup evidence
    assert "serve.step" in names and "serve.prefill" in names
    assert "serve.evict" in names

    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    lands = by_name.get("compile.land", [])
    upgrades = by_name.get("exec.upgrade", [])
    assert lands and upgrades
    decode_land = [e for e in lands if e["args"]["graph"] == "decode_step"]
    assert decode_land
    # the ordering claim: the serving path flipped to stitched only after
    # the compile landed in the cache
    assert min(u["ts"] for u in upgrades) > min(e["ts"] for e in decode_land)

    # the exported document stays schema-valid with real pipeline events
    doc = obs.tracer.chrome_trace()
    for e in doc["traceEvents"]:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e)
    json.dumps(doc)                              # serializable end-to-end

    # inspect CLI renders both views from this trace without error
    from repro.launch.inspect import compile_timeline, measured_table
    timeline = compile_timeline(events)
    assert any("compile.land" in line for line in timeline)
    assert any("exec.upgrade" in line for line in timeline)
    measured_table(events)                       # no timer on: stub line


def test_serving_latency_summary_keys_always_present():
    """Satellite: ServeMetrics.summary() exposes latency percentiles and
    finish reasons even for an empty run (all-zero, not missing)."""
    from repro.serve.metrics import ServeMetrics
    s = ServeMetrics().summary()
    for key in ("e2e_latency_s", "ttft_s", "queue_latency_s"):
        assert s[key] == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    assert s["finish_reasons"] == {}
    assert s["tokens_per_sec"] == 0.0


# ---------------------------------------------------------------------------
# spans on the profiler's clock, and the walk's dispatch counters
# ---------------------------------------------------------------------------

def _xplane_spans(trace_dir) -> list[tuple[str, int, int, dict]]:
    """(name, start, end, args) of every program span on the host plane."""
    from jax.profiler import ProfileData

    (path,) = list(trace_dir.glob("**/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns), dict(e.stats))
                        for e in line.events
                        if e.name.startswith(("serve.", "exec."))]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(outer, inner) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_spans_land_on_the_profiler_host_plane(tmp_path):
    """With tracing enabled while the JAX profiler records, one request's
    prefill, insert, decode step and release appear on the xplane's host
    plane, nested as the served path runs them, with their args."""
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 ServeConfig(batch=2, max_len=32, stitch_execute=True),
                 stitch_service=CompilationService())
    prompt = np.arange(1, 6, dtype=np.int32)
    for _ in range(2):                 # trace + land plans, then compile them
        eng.insert(eng.prefill(prompt), 0)
        eng.generate_step(1)
        eng.release(0)
        assert eng.land_plans() == 0
    obs.clear_trace()
    obs.enable_tracing()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.insert(eng.prefill(prompt, rid=7), 1)
        eng.generate_step(1)
        eng.release(1)
    finally:
        jax.profiler.stop_trace()
        obs.disable_tracing()
        obs.clear_trace()
    spans = _xplane_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    for name in ("serve.prefill", "serve.prefill.readback", "serve.insert",
                 "serve.release"):
        assert by[name][0][3]["rid"] == 7, name
    assert by["serve.insert"][0][3]["slot"] == 1
    (decode,) = by["serve.decode"]
    assert (decode[3]["slots"], decode[3]["steps"]) == (1, 1)
    children = [s[0] for s in spans if s is not decode and _inside(decode, s)
                and not any(_inside(o, s) for o in spans
                            if o is not decode and o is not s
                            and _inside(decode, o))]
    assert children == ["serve.decode.prep", "exec.decode_step",
                        "serve.decode.sample", "serve.decode.readback",
                        "serve.decode.commit"]
    (step,) = by["exec.decode_step"]
    assert step[3]["path"] == "stitched"
    check, walk = [s for s in spans if s[0] in ("exec.check", "exec.walk")
                   and _inside(step, s)]
    assert (check[0], walk[0]) == ("exec.check", "exec.walk")
    # the walk is one jitted program: one launch, no upload, while the walk
    # traced into it issued the plan's ``walk_launches``
    plan = eng._exec.report()["plan"]
    assert walk[3]["launches"] == plan["launches"] == 1
    assert walk[3]["uploads"] == plan["uploads"] == 0
    assert plan["walk_launches"] == eng._exec.compiled.launches > 0
    (prefill,) = by["exec.prefill"]
    assert _inside(by["serve.prefill"][0], prefill)
    assert any(s[0] == "exec.walk" and _inside(prefill, s) for s in spans)


def test_disabled_spans_are_the_null_span_and_cheap():
    obs.disable_tracing()
    assert obs.span("serve.decode", cat="serve", slots=3, steps=1) \
        is obs.NULL_SPAN
    t0 = time.perf_counter()
    for _ in range(10_000):
        with obs.span("exec.walk", cat="exec") as s:
            s.set(launches=1, uploads=0)
    assert time.perf_counter() - t0 < 0.1
    assert len(obs.tracer) == 0


def test_exec_span_covers_check_and_walk(svc, traced):
    """``exec.<name>`` spans the whole call: the routing (drift check and
    upgrade poll) in ``exec.check``, the artifact in ``exec.walk``; a
    drifted call is served by jit with no walk."""
    sf = stitch(_small_fn, service=svc, name="spanned_fn")
    x = jnp.ones((8, 16), jnp.float32)
    sf(x)
    obs.clear_trace()
    sf(x)
    names = [e["name"] for e in obs.tracer.events()]
    assert names == ["exec.check", "exec.walk", "exec.spanned_fn"]
    check, walk, call = obs.tracer.events()
    assert call["args"] == {"path": "stitched"}
    assert call["ts"] <= check["ts"] and \
        walk["ts"] + walk["dur"] <= call["ts"] + call["dur"]
    plan = sf.report()["plan"]
    assert walk["args"] == {"launches": plan["launches"],
                            "uploads": plan["uploads"]} == {"launches": 1,
                                                            "uploads": 0}
    obs.clear_trace()
    sf(jnp.ones((4, 16), jnp.float32))                 # signature drift
    assert [e["name"] for e in obs.tracer.events()] == [
        "exec.check", "exec.spanned_fn"]
    assert obs.tracer.events()[-1]["args"] == {"path": "fallback"}


def test_compiled_graph_counts_launches_and_uploads(monkeypatch):
    """One call's ``launches`` is its Pallas callable calls plus its
    ``eval_node`` calls; ``uploads`` its constants plus host inputs."""
    from repro.core import GraphBuilder, StitchCompiler
    from repro.core import compiler as compiler_mod

    b = GraphBuilder("walk")
    x = b.param("x", (64, 256))
    w = b.param("w", (64, 256))
    c = b.const("c", (256,))
    xc = b.ew("add", x, b.bcast(c, (64, 256), (1,)))
    m = b.reduce("max", xc, axes=(1,))
    e = b.ew("exp", b.ew("sub", xc, b.bcast(m, (64, 256), (0,))))
    s = b.reduce("sum", e, axes=(1,))
    y = b.ew("div", e, b.bcast(s, (64, 256), (0,)))
    z = b.custom("opaque", (64, 256), "float32", (y,), eval_fn=jnp.tanh)
    g = b.build(outputs=[b.ew("mul", z, w)])
    g[c].attrs["value"] = np.ones((256,), np.float32)
    cg = StitchCompiler(mode="stitch").compile(g)
    kinds = {grp.kind for grp in cg.groups}
    assert "pallas" in kinds and kinds - {"pallas"}
    calls = {"eval_node": 0, "pallas": 0}
    real_eval = compiler_mod.eval_node

    def counted_eval(*a, **k):
        calls["eval_node"] += 1
        return real_eval(*a, **k)

    monkeypatch.setattr(compiler_mod, "eval_node", counted_eval)
    for grp in cg.groups:
        if grp.kind == "pallas":
            real = grp.tuned.callable

            def counted(*a, _real=real):
                calls["pallas"] += 1
                return _real(*a)

            monkeypatch.setattr(grp.tuned, "callable", counted)
    out = cg({"x": np.ones((64, 256), np.float32),         # a host value
              "w": jnp.ones((64, 256), jnp.float32)})
    assert calls["pallas"] >= 1 and calls["eval_node"] >= 1
    assert cg.launches == calls["pallas"] + calls["eval_node"]
    assert cg.uploads == 2                     # the constant and x
    np.testing.assert_allclose(
        np.asarray(out[g.outputs[0]]),
        np.tanh(np.full((64, 256), 1 / 256, np.float32)), rtol=1e-5)
