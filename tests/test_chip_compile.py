"""Compiles for a described TPU v5e: the kernels of the serving path at
published widths go through Mosaic, with no chip attached.

What the Pallas interpreter accepts, the chip's compiler may refuse: a block
not aligned to the (8, 128) tiling, or more VMEM than a kernel may hold.
Each test here builds its kernels compiled (``interpret_mode`` patched to
False, as on a TPU), lowers them for one chip of a described ``v5e:2x2``
topology, and checks that the result holds a Mosaic kernel
(``tpu_custom_call``).  Nothing runs, so nothing here is a time or a
result: only that the chip's compiler takes the kernel.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.core import StitchCompiler
from repro.core.codegen import canonical_dtype
from repro.core.trace import trace_to_graph
from repro.core.tuner import grid_row_block
from repro.kernels.stitched import build_stitched_callable

from conftest import make_mlp_norm_graph, make_softmax_graph

SLOTS, MAX_LEN, PAGE = 4, 256, 16      # the chip smoke's serving shape


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one; keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(monkeypatch, one_chip):
    """``compile(fn, *avals)``: lower ``fn`` for one v5e chip with every
    Pallas kernel built compiled, and return the compiled text."""
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)

    def compile_(fn, *avals):
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for a in avals]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
        return text

    return compile_


def _pallas_groups(g):
    """Plan ``g`` and return (pattern, rebuilt kernel, input avals) for each
    group the plan runs as a stitched Pallas kernel."""
    cg = StitchCompiler(mode="stitch").compile(g)
    out = []
    for grp in cg.groups:
        if grp.kind != "pallas":
            continue
        p, t = grp.tuned.pattern, grp.tuned.template
        fn = build_stitched_callable(p, row_block=grid_row_block(t),
                                     scratch_ops=t.scratch_ops)
        avals = [jax.ShapeDtypeStruct(g[i].shape, canonical_dtype(g[i].dtype))
                 for i in p.external_inputs]
        out.append((p, fn, avals))
    return out


def _decode_graph():
    """The paged decode step of full-width qwen3-1.7b, traced at abstract
    shapes.  Depth is cut to 2 layers: the layers run in one scan, so the
    graph the planner sees does not grow with depth."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("qwen3_1_7b"), n_layers=2)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pages_per_slot = MAX_LEN // PAGE
    pool = jax.eval_shape(
        lambda: model.init_paged_cache(SLOTS * pages_per_slot + 1, PAGE))
    cache = {"k": pool["k"], "v": pool["v"],
             "page_table": jax.ShapeDtypeStruct((SLOTS, pages_per_slot),
                                                jnp.int32),
             "length": jax.ShapeDtypeStruct((SLOTS,), jnp.int32)}
    tok = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32)

    def decode_step(params, cache, tok):
        return model.decode_step(params, cache, tok, kv_limit=MAX_LEN)

    g, _ = trace_to_graph(decode_step, params, cache, tok, name="decode")
    return g


@pytest.mark.parametrize("graph", ["softmax_64x256", "mlp_norm_8x2048",
                                   "qwen3_decode"])
def test_stitched_groups_compile(graph, compiled):
    """Every Pallas group the planner picks compiles for the chip.  The
    2048x2048 f32 weight of ``mlp_norm`` (32 MiB double-buffered) exceeds
    a kernel's VMEM, so the planner must leave that GEMM outside."""
    g = {"softmax_64x256": lambda: make_softmax_graph(64, 256)[0],
         "mlp_norm_8x2048": lambda: make_mlp_norm_graph(8, 2048),
         "qwen3_decode": _decode_graph}[graph]()
    groups = _pallas_groups(g)
    assert groups, f"the {graph} plan has no Pallas group"
    for _, fn, avals in groups:
        compiled(fn, *avals)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_compiles(dtype, compiled):
    from repro.kernels.norms import rmsnorm

    compiled(rmsnorm, jax.ShapeDtypeStruct((SLOTS, 2048), dtype),
             jax.ShapeDtypeStruct((2048,), jnp.float32))


def test_flash_attention_compiles(compiled):
    from repro.kernels.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 128, 16, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 128, 8, 128), jnp.bfloat16)
    compiled(lambda q, k, v: flash_attention(q, k, v, causal=True), q, kv, kv)


def test_decode_attention_compiles(compiled):
    from repro.kernels.decode_attention import decode_attention

    q = jax.ShapeDtypeStruct((SLOTS, 1, 16, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((SLOTS, MAX_LEN, 8, 128), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    compiled(decode_attention, q, kv, kv, pos)
