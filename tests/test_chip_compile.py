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
import re

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
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), avals)
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


def _decode_step():
    """The paged decode step of full-width qwen3-1.7b and its abstract
    arguments.  Depth is cut to 2 layers: the layers run in one scan, so
    the graph the planner sees does not grow with depth."""
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

    return decode_step, (params, cache, tok)


def _decode_graph():
    fn, args = _decode_step()
    g, _ = trace_to_graph(fn, *args, name="decode")
    return g


DS_SLOTS, DS_MAX_LEN = 48, 2048       # the DeepSeek cell's serving shape


def _deepseek_cfg(n_layers: int = 3):
    """DeepSeek-V2-Lite at published widths, one chip's 16 of 64 experts."""
    from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                     YarnConfig)

    return ModelConfig(
        name="deepseek-v2-lite", family="moe", n_layers=n_layers, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400, head_dim=192,
        n_dense_layers=1, tie_embeddings=False,
        mla=MLAConfig(kv_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                      v_dim=128, yarn=YarnConfig(factor=40.0,
                                                 original_max=4096,
                                                 mscale_all_dim=0.707)),
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                      d_shared=2816, renormalize=False, held=(0, 16)),
        dtype="bfloat16", param_dtype="bfloat16")


def _deepseek_pool(model):
    return jax.eval_shape(lambda: model.init_paged_cache(
        DS_SLOTS * (DS_MAX_LEN // PAGE) + 1, PAGE))


def _deepseek_decode_step():
    """The paged latent decode step of DeepSeek-V2-Lite at 48 slots and its
    abstract arguments.  Depth is cut to the dense layer and two MoE layers
    (each stack is one scan)."""
    from repro.models import build_model

    model = build_model(_deepseek_cfg())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pps = DS_MAX_LEN // PAGE
    cache = dict(_deepseek_pool(model),
                 page_table=jax.ShapeDtypeStruct((DS_SLOTS, pps), jnp.int32),
                 length=jax.ShapeDtypeStruct((DS_SLOTS,), jnp.int32))
    tok = jax.ShapeDtypeStruct((DS_SLOTS, 1), jnp.int32)

    def decode_step(params, cache, tok):
        return model.decode_step(params, cache, tok, kv_limit=DS_MAX_LEN)

    return decode_step, (params, cache, tok)


def _deepseek_decode_graph():
    """The DeepSeek decode step's graph: its slot vectors of 48 rows and
    its scalar masks are where the chip refused stitched groups (a rank-1
    block of 8 rows; a bool scalar input)."""
    fn, args = _deepseek_decode_step()
    g, _ = trace_to_graph(fn, *args, name="decode")
    return g


@pytest.mark.parametrize("graph", ["softmax_64x256", "mlp_norm_8x2048",
                                   "qwen3_decode", "deepseek_decode"])
def test_stitched_groups_compile(graph, compiled):
    """Every Pallas group the planner picks compiles for the chip.  The
    2048x2048 f32 weight of ``mlp_norm`` (32 MiB double-buffered) exceeds
    a kernel's VMEM, so the planner must leave that GEMM outside."""
    g = {"softmax_64x256": lambda: make_softmax_graph(64, 256)[0],
         "mlp_norm_8x2048": lambda: make_mlp_norm_graph(8, 2048),
         "qwen3_decode": _decode_graph,
         "deepseek_decode": _deepseek_decode_graph}[graph]()
    groups = _pallas_groups(g)
    assert groups, f"the {graph} plan has no Pallas group"
    for _, fn, avals in groups:
        compiled(fn, *avals)


def test_paged_decode_step_compiles(compiled):
    """The whole decode step, the paged kernel inside the layer scan,
    compiles for the chip; the kernel reads the stacked pool, so the step
    keeps no gather of the slots' views."""
    fn, args = _decode_step()
    text = compiled(fn, *args)          # the kernel is its only Mosaic call
    page = r"bf16\[\d+,%d,8,128\]\{[^}]*\}" % PAGE
    assert not re.search(page + r" gather\(", text), "pages still gathered"


def test_latent_decode_step_compiles(compiled):
    """The whole DeepSeek decode step compiles with the paged latent kernel
    inside each layer scan, and keeps no gather of the latent pages."""
    from repro.models.layers import attention_path

    fn, args = _deepseek_decode_step()
    assert attention_path(_deepseek_cfg(), args[1]) == "mla-kernel"
    text = compiled(fn, *args)
    # the kernel's o_lat (slots, heads, rank) is a Mosaic call's output
    assert re.search(r"bf16\[%d,16,512\][^\n]*tpu_custom_call" % DS_SLOTS,
                     text), "no paged latent kernel in the step"
    page = r"bf16\[\d+,%d,(512|128)\]\{[^}]*\}" % PAGE
    assert not re.search(page + r" gather\(", text), "pages still gathered"


@pytest.mark.parametrize("step", ["qwen3", "deepseek"])
def test_stitched_decode_program_compiles(step, monkeypatch, one_chip):
    """The decode step as ``stitch()`` serves it: its plan's group walk
    traced into one jitted program, which compiles for the chip with the
    Mosaic kernels inside.  In one program the latent row write lands in
    the program's own output, so no temporary holds a copy of the pool."""
    from repro.cache import CompilationService
    from repro.exec import stitch

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    fn, avals = {"qwen3": _decode_step,
                 "deepseek": _deepseek_decode_step}[step]()
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), avals)
    sf = stitch(fn, mode="offline", service=CompilationService(),
                name="decode_step")
    assert sf.warmup(*args) == "compiled"
    sp = sf._active
    assert sf._build(sp, args, {})
    assert sf.report()["plan"]["walk_launches"] > 1
    program = sp.program.lower(*args).compile()
    assert "tpu_custom_call" in program.as_text()
    if step == "deepseek":
        pool = sum(l.size * l.dtype.itemsize for n, l in avals[1].items()
                   if n in ("lat", "rope"))
        assert program.memory_analysis().temp_size_in_bytes < pool / 4


def test_paged_latent_decode_attention_compiles(compiled):
    """The kernel at the DeepSeek cell's shapes: 48 slots of 2048
    positions, page 16, 16 heads, rank 512 + rope 64, 27 layers."""
    from repro.kernels.latent_attention import paged_latent_decode_attention

    n = DS_MAX_LEN // PAGE
    P = DS_SLOTS * n + 1
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    compiled(lambda *a: paged_latent_decode_attention(*a, scale=0.1147),
             bf16(DS_SLOTS, 16, 512), bf16(DS_SLOTS, 16, 64),
             bf16(27, P, PAGE, 512), bf16(27, -(-P // 2), PAGE, 128), i32(),
             i32(DS_SLOTS, n), i32(DS_SLOTS), bf16(DS_SLOTS, 576))


def test_latent_pool_bytes_per_token(one_chip):
    """On the chip the paged latent pool holds 576 bf16 values a token and
    layer, 31,104 B a token over 27 layers, unpadded: as device arguments
    its two leaves take that many bytes per pool position, within 0.1%
    (the rope leaf rounds the page count up to whole pairs)."""
    from repro.models import build_model

    pool = _deepseek_pool(build_model(_deepseek_cfg(27)))
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), pool)
    mem = jax.jit(lambda pool: {k: v[0, 0, 0, 0] for k, v in pool.items()}
                  ).lower(args).compile().memory_analysis()
    positions = pool["lat"].shape[1] * PAGE
    assert mem.argument_size_in_bytes / positions == pytest.approx(
        31104, rel=1e-3)


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


def _paged_avals(slots, max_len, page, hkv, dh, layers, group):
    n = max_len // page
    pool = jax.ShapeDtypeStruct((layers, slots * n + 1, page, hkv, dh),
                                jnp.bfloat16)
    q = jax.ShapeDtypeStruct((slots, hkv * group, dh), jnp.bfloat16)
    new = jax.ShapeDtypeStruct((slots, hkv, dh), jnp.bfloat16)
    return (q, pool, pool, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((slots, n), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32), new, new)


@pytest.mark.parametrize("shape", [
    # the chat cells: 12 slots of 2048 positions, page 16, qwen3-1.7b heads
    dict(slots=12, max_len=2048, page=16, hkv=8, dh=128, layers=28, group=2),
    # 32 kv heads (phi3-mini's count) at a head size that tiles, page 8
    dict(slots=8, max_len=2048, page=8, hkv=32, dh=128, layers=32, group=1),
])
def test_paged_decode_attention_compiles(shape, compiled):
    from repro.kernels.decode_attention import (paged_decode_attention,
                                                paged_decode_fits)

    avals = _paged_avals(**shape)
    assert paged_decode_fits(avals[1].shape, avals[0].shape[1])
    compiled(paged_decode_attention, *avals)


def test_paged_decode_keeps_gather_at_phi3_widths(compiled):
    """phi3-mini's head size of 96 is not a whole lane tile, and Mosaic
    refuses the kernel's page copy there; the paged step keeps the gather
    path for it, on the chip as elsewhere."""
    from repro.configs import get_config
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.models.layers import attention_path

    cfg = get_config("phi3_mini_3_8b")
    avals = _paged_avals(slots=8, max_len=2048, page=16, hkv=cfg.n_kv_heads,
                         dh=cfg.dh, layers=cfg.n_layers, group=1)
    pool = {"k": avals[1], "v": avals[2], "page_table": avals[4]}
    assert attention_path(cfg, pool) == "gather"
    with pytest.raises(Exception, match="aligned to tiling"):
        compiled(paged_decode_attention, *avals)


def test_decode_attention_compiles(compiled):
    from repro.kernels.decode_attention import decode_attention

    q = jax.ShapeDtypeStruct((SLOTS, 1, 16, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((SLOTS, MAX_LEN, 8, 128), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    compiled(decode_attention, q, kv, kv, pos)
