"""End-to-end system tests: training loop + checkpoint/restart + fault
tolerance + serving + distributed-optimization pieces."""

import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.data import DataConfig, SyntheticTokens
from repro.ft import (StepFailure, StragglerMonitor, TrainSupervisor,
                      elastic_remesh, usable_mesh_shape)
from repro.models import build_model
from repro.optim import AdamWConfig, adamw, compress
from repro.serve import Engine, ServeConfig
from repro.train import TrainState, init_state, make_train_step
from repro.ckpt import store


def _setup(arch="qwen3_1_7b", steps=20):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    state = init_state(model, jax.random.PRNGKey(0))
    data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4))
    step = jax.jit(make_train_step(
        model, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=steps)))
    return cfg, model, state, data, step


def test_loss_decreases_over_training():
    cfg, model, state, data, step = _setup()
    losses = []
    for s in range(20):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, f"no learning: {losses[0]} -> {losses[-1]}"


def test_checkpoint_roundtrip_exact():
    cfg, model, state, data, step = _setup()
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    state, _ = step(state, batch)
    with tempfile.TemporaryDirectory() as d:
        store.save(state, d, int(state.step))
        like = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)
        restored, s = store.restore(d, like)
        assert s == 1
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restart_reproduces_uninterrupted_run():
    """Supervisor with an injected failure converges to the SAME state as an
    uninterrupted run (exact data replay + checkpoint restore)."""
    with tempfile.TemporaryDirectory() as d1, tempfile.TemporaryDirectory() as d2:
        def run(ckpt_dir, fail):
            cfg, model, state, data, step = _setup()
            sup = TrainSupervisor(
                step, lambda s: {k: jnp.asarray(v) for k, v in data.batch(s).items()},
                ckpt_dir, ckpt_every=4)
            return sup.run(state, 12,
                           fail_at={7: StepFailure("boom")} if fail else None)

        s_fail = run(d1, True)
        s_ok = run(d2, False)
        for a, b in zip(jax.tree.leaves(s_fail), jax.tree.leaves(s_ok)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-5, atol=1e-6)


def test_async_checkpointer_crash_safety():
    cfg, model, state, data, step = _setup()
    with tempfile.TemporaryDirectory() as d:
        ck = store.AsyncCheckpointer(d)
        ck.save_async(state, 0)
        ck.wait()
        assert store.latest_step(d) == 0
        ck.save_async(state, 5)
        ck.wait()
        assert store.latest_step(d) == 5


def test_straggler_and_elastic():
    mon = StragglerMonitor(8, threshold=1.5)
    times = np.ones(8)
    times[3] = 4.0
    flagged = None
    for _ in range(4):
        flagged = mon.observe(times)
    assert flagged == [3]
    assert usable_mesh_shape(240, 16) == (15, 16)  # lost a host: DP shrinks
    with pytest.raises(ValueError):
        usable_mesh_shape(8, 16)
    mesh = elastic_remesh(jax.devices(), model_parallel=1)
    assert mesh.shape["model"] == 1


def test_elastic_reshard_checkpoint_roundtrip():
    """Checkpoint saved under one sharding restores under another mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    cfg, model, state, data, step = _setup()
    with tempfile.TemporaryDirectory() as d:
        store.save(state, d, 0)
        mesh = elastic_remesh(jax.devices(), model_parallel=1)
        like = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)
        sh = jax.tree.map(lambda l: NamedSharding(mesh, P()), like)
        restored, _ = store.restore(d, like, shardings=sh)
        np.testing.assert_array_equal(
            np.asarray(restored.params["embed"]), np.asarray(state.params["embed"]))


def test_gradient_compression_error_feedback():
    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)}
    err = compress.init_error(grads)
    # accumulated dequantized updates converge to true sum (error feedback)
    total_q = jnp.zeros((64, 64))
    for _ in range(50):
        q, scales, err = compress.compress_with_feedback(grads, err)
        total_q = total_q + compress.dequantize(q, scales)["w"]
    total_true = grads["w"] * 50
    rel = float(jnp.linalg.norm(total_q - total_true) / jnp.linalg.norm(total_true))
    assert rel < 0.01, f"error feedback did not converge: {rel}"


def test_quantize_roundtrip_bounded_error():
    rng = np.random.default_rng(1)
    tree = {"a": jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)}
    q, s = compress.quantize(tree)
    deq = compress.dequantize(q, s)
    err = float(jnp.max(jnp.abs(deq["a"] - tree["a"])))
    assert err <= float(s["a"]) * 0.5 + 1e-6


def test_serving_engine_greedy_deterministic():
    cfg, model, state, data, step = _setup()
    eng = Engine(model, state.params,
                 ServeConfig(batch=2, max_len=48, max_new_tokens=6))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    a = eng.generate(prompts.copy())
    b = eng.generate(prompts.copy())
    assert np.array_equal(a, b)
    assert a.shape == (2, 6)


def test_data_pipeline_stateless_replay():
    d = SyntheticTokens(DataConfig(vocab=1000, seq_len=16, global_batch=4))
    b1, b2 = d.batch(7), d.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d.batch(7)["tokens"], d.batch(8)["tokens"])
    # per-host sharding: hosts see disjoint streams
    h0 = SyntheticTokens(DataConfig(1000, 16, 4), host_id=0, n_hosts=2)
    h1 = SyntheticTokens(DataConfig(1000, 16, 4), host_id=1, n_hosts=2)
    assert not np.array_equal(h0.batch(0)["tokens"], h1.batch(0)["tokens"])
    assert h0.batch(0)["tokens"].shape == (2, 16)


def test_labels_are_next_token():
    d = SyntheticTokens(DataConfig(vocab=50, seq_len=8, global_batch=2))
    b = d.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_zero1_opt_state_specs():
    """ZeRO-1: optimizer m/v get an extra data-axis shard on a divisible dim."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    cfg = get_reduced("qwen3_1_7b")
    model = build_model(cfg)
    state = init_state(model, jax.random.PRNGKey(0))
    mesh = make_host_mesh(1)
    from repro.models import param_pspecs
    aparams = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                           state.params)
    pspecs = param_pspecs(aparams, cfg, mesh)
    ospecs = adamw.opt_state_pspecs(state.opt, pspecs, mesh)
    m_specs = jax.tree.leaves(ospecs.m, is_leaf=lambda x: isinstance(x, P))
    assert any("data" in str(s) for s in m_specs), \
        "no m/v leaf picked up the data axis"


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (the code then sets no directory), else to the fixed .jax_cache/
    at the checkout root."""
    from pathlib import Path

    from repro.launch.hostenv import COMPILE_CACHE_DIR, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if from_env:
        assert after == before
    else:
        assert after == str(COMPILE_CACHE_DIR)
        assert COMPILE_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
