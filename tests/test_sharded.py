"""Mesh-aware stitched execution: shard_map dispatch for train and serve.

The tier-1 suite runs on a forced 8-device host platform (conftest.py sets
``--xla_force_host_platform_device_count=8``), so these tests exercise a
real (4, 2) data x model mesh on CPU:

* the sharded ``StitchedTrainStep`` (``--stitch --model-parallel 2``,
  DP=4) must reproduce both the single-device stitched trajectory and the
  sharded-jit trajectory to tolerance, including a mid-run
  miss-then-upgrade transition under ``shard_map``;
* the StitchCache must key plans by placement (mesh + PartitionSpecs):
  a plan compiled at one mesh never answers a lookup at another;
* the serving engine's DP-replica dispatch must be token-for-token
  equal to the unsharded engine on both the static and continuous paths;
* ``make_host_mesh`` must reject a non-dividing ``--model-parallel``
  with an error naming the valid divisors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.cache import CompilationService, StitchCache, placement_key
from repro.configs import get_reduced
from repro.core import StitchCompiler
from repro.launch.mesh import make_host_mesh
from repro.models import build_model, local_shape
from repro.optim import AdamWConfig
from repro.train import StitchedTrainStep, init_state, make_train_step

from conftest import make_softmax_graph

B, S = 8, 8
N_STEPS = 4
UPGRADE_AT = 2           # steps 0-1 on the XLA fallback, 2-3 stitched


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the forced 8-device host platform")
    return make_host_mesh(2)


@pytest.fixture(scope="module")
def model():
    return build_model(get_reduced("qwen3_1_7b"))


@pytest.fixture(scope="module")
def opt_cfg():
    return AdamWConfig(warmup_steps=2, total_steps=20)


def make_batch(vocab, i):
    r = np.random.default_rng(500 + i)
    return {"tokens": jnp.asarray(r.integers(0, vocab, (B, S)), jnp.int32),
            "labels": jnp.asarray(r.integers(0, vocab, (B, S)), jnp.int32)}


# ---------------------------------------------------------------------------
# launcher fix: make_host_mesh divisibility validation
# ---------------------------------------------------------------------------

def test_make_host_mesh_validates_divisibility():
    n = len(jax.devices())
    assert n == 8
    mesh = make_host_mesh(2)
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    with pytest.raises(ValueError) as ei:
        make_host_mesh(3)                     # 8 devices, MP=3: no mesh
    msg = str(ei.value)
    assert "[1, 2, 4, 8]" in msg and "8 devices" in msg
    with pytest.raises(ValueError):
        make_host_mesh(0)


# ---------------------------------------------------------------------------
# mesh-keyed cache entries (the hit/miss assertion)
# ---------------------------------------------------------------------------

def test_mesh_keyed_cache_hit_miss(mesh):
    """A plan compiled at one placement must hit ONLY at that placement —
    another mesh (or the single-device placement) is a miss and makes its
    own entry."""
    g, _x, _y = make_softmax_graph()
    cache = StitchCache()
    pl_a = placement_key(mesh, P("data"))
    pl_b = placement_key(mesh, P(("data", "model")))
    comp_a = StitchCompiler(mode="stitch", use_pallas=False, cache=cache,
                            placement=pl_a)
    comp_b = StitchCompiler(mode="stitch", use_pallas=False, cache=cache,
                            placement=pl_b)
    comp_1d = StitchCompiler(mode="stitch", use_pallas=False, cache=cache)

    assert pl_a != pl_b != ""
    comp_a.compile(g)                              # cold at placement A
    assert cache.lookup(g, comp_a) is not None     # hit at A
    assert cache.lookup(g, comp_b) is None         # miss at B
    assert cache.lookup(g, comp_1d) is None        # miss at single-device
    comp_b.compile(g)
    comp_1d.compile(g)
    assert len(cache.store.memory) == 3            # one entry per placement
    per_pl = cache.report()["per_placement"]
    assert per_pl[pl_a]["hits"] >= 1
    assert per_pl[pl_b]["misses"] >= 1
    assert per_pl["single-device"]["misses"] >= 1


def test_mesh_keyed_disk_roundtrip(mesh, tmp_path):
    """Placement survives the disk store: a fresh process (new StitchCache)
    replays the mesh-keyed record, and the other placement still misses."""
    g, _x, _y = make_softmax_graph()
    pl = placement_key(mesh, P("data"))
    c1 = StitchCache(str(tmp_path))
    StitchCompiler(mode="stitch", use_pallas=False, cache=c1,
                   placement=pl).compile(g)

    c2 = StitchCache(str(tmp_path))                # fresh cache, same disk
    hit = c2.lookup(g, StitchCompiler(mode="stitch", use_pallas=False,
                                      cache=c2, placement=pl))
    assert hit is not None and hit.stats.cache_status == "hit"
    assert c2.lookup(g, StitchCompiler(mode="stitch", use_pallas=False,
                                       cache=c2)) is None


def test_local_shape_arithmetic(mesh):
    assert local_shape((8, 16), P(("data", "model")), mesh) == (1, 16)
    assert local_shape((8, 16), P("data", "model"), mesh) == (2, 8)
    assert local_shape((8, 16), P(), mesh) == (8, 16)
    with pytest.raises(ValueError):
        local_shape((6, 16), P("data"), mesh)      # 6 % 4 != 0


# ---------------------------------------------------------------------------
# the tentpole: sharded stitched training trajectory
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_run(mesh, model, opt_cfg):
    """One 4-step run of each trajectory: sharded stitched (upgrade after
    step 2), single-device stitched (upgraded the same way), and sharded
    jit.  Shared by the assertions below — the stitch compiles dominate the
    cost."""
    vocab = model.cfg.vocab

    # sharded stitched: max_background=0 pins the upgrade point
    svc_sh = CompilationService(max_background=0)
    st_sh = StitchedTrainStep(model, opt_cfg, service=svc_sh, mesh=mesh)
    s_sh = jax.device_put(init_state(model, jax.random.PRNGKey(0)),
                          st_sh.state_shardings())

    # single-device stitched reference
    svc_1d = CompilationService(max_background=0)
    st_1d = StitchedTrainStep(model, opt_cfg, service=svc_1d)
    s_1d = init_state(model, jax.random.PRNGKey(0))

    # sharded jit reference (GSPMD over the same mesh placement)
    jit_step = jax.jit(make_train_step(model, opt_cfg))
    s_jit = jax.device_put(init_state(model, jax.random.PRNGKey(0)),
                           st_sh.state_shardings())

    hist = {"sh": [], "1d": [], "jit": []}
    statuses = []
    for i in range(N_STEPS):
        if i == UPGRADE_AT:
            # land the stitched plans mid-run (what the background thread
            # would do), for BOTH placements
            for st, svc in ((st_sh, svc_sh), (st_1d, svc_1d)):
                for phase in (st._grad, st._packed):
                    svc.compiler("stitch", phase.placement).compile(
                        phase.graph, bypass_cache_lookup=True)
        s_sh, m_sh = st_sh(s_sh, make_batch(vocab, i))
        s_1d, m_1d = st_1d(s_1d, make_batch(vocab, i))
        s_jit, m_jit = jit_step(s_jit, make_batch(vocab, i))
        statuses.append((st_sh._grad.status, st_sh._packed.status))
        for k, m in (("sh", m_sh), ("1d", m_1d), ("jit", m_jit)):
            hist[k].append((float(m["loss"]), float(m["grad_norm"])))
    return {"hist": hist, "statuses": statuses, "st_sh": st_sh,
            "st_1d": st_1d, "svc_sh": svc_sh, "mesh": mesh,
            "final": {"sh": s_sh, "1d": s_1d, "jit": s_jit}}


def test_sharded_matches_single_device_stitched(sharded_run):
    """--stitch --model-parallel 2 (DP=4) loss/grad-norm trajectories match
    the single-device stitched run to tolerance, across the mid-run
    upgrade."""
    for (l_sh, g_sh), (l_1d, g_1d) in zip(sharded_run["hist"]["sh"],
                                          sharded_run["hist"]["1d"]):
        np.testing.assert_allclose(l_sh, l_1d, rtol=5e-3)
        np.testing.assert_allclose(g_sh, g_1d, rtol=2e-2)


def test_sharded_matches_sharded_jit(sharded_run):
    """...and the sharded-jit (GSPMD) trajectory."""
    for (l_sh, g_sh), (l_j, g_j) in zip(sharded_run["hist"]["sh"],
                                        sharded_run["hist"]["jit"]):
        np.testing.assert_allclose(l_sh, l_j, rtol=5e-3)
        np.testing.assert_allclose(g_sh, g_j, rtol=2e-2)


def test_sharded_final_states_close(sharded_run):
    for a, b in zip(jax.tree_util.tree_leaves(sharded_run["final"]["sh"].params),
                    jax.tree_util.tree_leaves(sharded_run["final"]["1d"].params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-3)
    assert int(sharded_run["final"]["sh"].step) == N_STEPS
    assert int(sharded_run["final"]["sh"].opt.count) == N_STEPS


def test_sharded_miss_then_upgrade_under_shard_map(sharded_run):
    """Steps 0-1 served the XLA fallback artifacts under shard_map, steps
    2-3 the stitched plans — never the jit fallback."""
    st = sharded_run["st_sh"]
    assert sharded_run["statuses"][UPGRADE_AT - 1][0] in ("miss", "pending")
    assert sharded_run["statuses"][UPGRADE_AT] == ("hit", "hit")
    assert st._grad.compiled.stats.mode == "stitch"
    assert st.fallback_steps == 0
    # packed update: ONE kernel over the TP-shard-local panels
    assert st._packed.kernel_count == 1
    grad_stats = st._grad.compiled.stats
    assert grad_stats.n_kernels < grad_stats.n_ops


def test_sharded_cache_keys_are_mesh_scoped(sharded_run):
    """Acceptance hit/miss assertion: the sharded grad plan hits at its
    placement and misses at the single-device placement (and vice versa) —
    the cache holds distinct mesh-keyed entries."""
    st, svc = sharded_run["st_sh"], sharded_run["svc_sh"]
    assert st._grad.placement.startswith("mesh[data=4,model=2]")
    hit = svc.cache.lookup(st._grad.graph,
                           svc.compiler("stitch", st._grad.placement))
    assert hit is not None
    assert svc.cache.lookup(st._grad.graph, svc.compiler("stitch")) is None
    # the single-device run's phases hit only at the "" placement
    st1 = sharded_run["st_1d"]
    assert st1._grad.placement == ""


def test_sharded_step_donates_consumed_state(mesh, model, opt_cfg, sharded_run):
    """The sharded dispatch frees the consumed params+moments (the stitched
    analogue of donate_argnums): every old buffer is deleted, every new one
    alive."""
    vocab = model.cfg.vocab
    st = sharded_run["st_sh"]
    s0 = jax.device_put(init_state(model, jax.random.PRNGKey(9)),
                        st.state_shardings())
    old = jax.tree_util.tree_leaves((s0.params, s0.opt.m, s0.opt.v))
    s1, _ = st(s0, make_batch(vocab, 77))
    assert sum(l.is_deleted() for l in old) == len(old)
    new = jax.tree_util.tree_leaves((s1.params, s1.opt.m, s1.opt.v))
    assert not any(l.is_deleted() for l in new)


def test_sharded_shape_drift_falls_back(sharded_run, model):
    """A drifted batch is served by the (sharded-jit) fallback for that call
    only."""
    st = sharded_run["st_sh"]
    base = st.fallback_steps
    s = jax.device_put(init_state(model, jax.random.PRNGKey(4)),
                       st.state_shardings())
    r = np.random.default_rng(0)
    drifted = {"tokens": jnp.asarray(r.integers(0, model.cfg.vocab, (B, S // 2)),
                                     jnp.int32),
               "labels": jnp.asarray(r.integers(0, model.cfg.vocab, (B, S // 2)),
                                     jnp.int32)}
    s, m = st(s, drifted)
    assert st.fallback_steps == base + 1
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# serving: DP-replica dispatch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup(mesh, model):
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_serve_dp_dispatch_matches_unsharded(mesh, serve_setup):
    from repro.serve import Engine, ServeConfig
    model, params = serve_setup
    vocab = model.cfg.vocab
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, vocab, (4, 8)).astype(np.int32)

    eng_ref = Engine(model, params, ServeConfig(batch=4, max_len=32,
                                                max_new_tokens=6))
    eng_sh = Engine(model, params, ServeConfig(batch=4, max_len=32,
                                               max_new_tokens=6), mesh=mesh)
    assert eng_sh.dp_replicas == 4          # slots=4 spread over the DP axis

    np.testing.assert_array_equal(eng_ref.generate(prompts.copy()),
                                  eng_sh.generate(prompts.copy()))

    # continuous batching: same request stream, token-for-token equal
    reqs = [rng.integers(0, vocab, (int(rng.integers(3, 9)),)).astype(np.int32)
            for _ in range(6)]
    for eng in (eng_ref, eng_sh):
        for p in reqs:
            eng.submit(p, max_new_tokens=4)
    fins_ref = sorted(eng_ref.drain(), key=lambda f: f.rid)
    fins_sh = sorted(eng_sh.drain(), key=lambda f: f.rid)
    for a, b in zip(fins_ref, fins_sh):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_serve_rejects_undividable_slots(mesh, serve_setup):
    from repro.serve import Engine, ServeConfig
    model, params = serve_setup
    with pytest.raises(ValueError, match="does not divide"):
        Engine(model, params, ServeConfig(batch=3, max_len=32), mesh=mesh)


def test_serve_stitched_sharded_upgrade(mesh, serve_setup):
    """Stitched decode under shard_map: the fallback artifact serves
    immediately, the mesh-keyed stitched plan lands, and tokens never
    change."""
    from repro.serve import Engine, ServeConfig
    model, params = serve_setup
    vocab = model.cfg.vocab
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, vocab, (4, 8)).astype(np.int32)

    ref = Engine(model, params, ServeConfig(batch=4, max_len=32,
                                            max_new_tokens=5)
                 ).generate(prompts.copy())

    svc = CompilationService(max_background=0)
    eng = Engine(model, params,
                 ServeConfig(batch=4, max_len=32, max_new_tokens=5,
                             stitch_execute=True),
                 stitch_service=svc, mesh=mesh)
    np.testing.assert_array_equal(ref, eng.generate(prompts.copy()))
    assert eng.stitch_status in ("miss", "pending")
    st = eng._stitch
    assert st["sharded"] and st["placement"].startswith("mesh[")
    svc.compiler("stitch", st["placement"]).compile(st["graph"],
                                                    bypass_cache_lookup=True)
    np.testing.assert_array_equal(ref, eng.generate(prompts.copy()))
    assert eng.stitch_status == "hit"
    assert eng._stitch["compiled"].stats.mode == "stitch"


# ---------------------------------------------------------------------------
# tracing shard-local collectives (axis_env)
# ---------------------------------------------------------------------------

def test_trace_collective_as_custom_partition(mesh):
    """A shard-local function containing a pmean traces with axis_env: the
    collective becomes an executable CUSTOM fusion partition, and the
    compiled artifact runs correctly inside shard_map."""
    from repro.core.ir import OpKind
    from repro.core.trace import trace_to_graph

    def local_fn(x):
        return jax.lax.pmean(x * 2.0 + 1.0, ("data", "model"))

    g, names = trace_to_graph(
        local_fn, jax.ShapeDtypeStruct((1, 4), jnp.float32),
        axis_env=[("data", 4), ("model", 2)])
    kinds = [n.kind for n in g.nodes.values()]
    assert OpKind.CUSTOM in kinds           # the psum partitions fusion
    compiled = StitchCompiler(mode="stitch", use_pallas=False).compile(g)

    def body(x):
        outs = compiled(dict(zip(names, [x])))
        return outs[g.outputs[0]]

    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    got = jax.shard_map(body, mesh=mesh, in_specs=P(("data", "model")),
                        out_specs=P(), check_vma=False)(x)
    want = np.mean(np.asarray(x) * 2.0 + 1.0, axis=0, keepdims=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
