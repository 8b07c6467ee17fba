"""StitchCompiler end-to-end: all three modes numerically identical to the
oracle; stitch mode compresses kernels and uses Pallas groups."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import StitchCompiler, build_reference_fn, emit_source
from repro.core.trace import trace_to_graph
from conftest import make_mlp_norm_graph, make_softmax_graph


def _run_all_modes(g, inputs, rtol=2e-4):
    ref = build_reference_fn(g)(inputs)
    stats = {}
    for mode in ("off", "xla", "stitch"):
        cg = StitchCompiler(mode=mode).compile(g)
        out = cg(inputs)
        for k in ref:
            np.testing.assert_allclose(
                np.asarray(out[k]), np.asarray(ref[k]), rtol=rtol, atol=rtol,
                err_msg=f"mode={mode} output={k}")
        stats[mode] = cg.stats
    return stats


def test_softmax_modes(rng):
    g, x, y = make_softmax_graph()
    stats = _run_all_modes(g, {x: rng.standard_normal((64, 256), dtype=np.float32)})
    assert stats["off"].n_kernels > stats["xla"].n_kernels >= stats["stitch"].n_kernels
    assert stats["stitch"].n_kernels == 1
    assert stats["stitch"].pallas_groups == 1


def test_mlp_norm_modes(rng):
    g = make_mlp_norm_graph()
    inputs = {
        "x": rng.standard_normal((128, 256), dtype=np.float32),
        "w": (rng.standard_normal((256, 256)) * 0.05).astype(np.float32),
        "gamma": rng.standard_normal(256, dtype=np.float32),
        "eps": np.float32(1e-5),
    }
    stats = _run_all_modes(g, inputs)
    assert stats["stitch"].compression > stats["xla"].compression


def test_traced_function_pipeline(rng):
    def f(x, w):
        h = jnp.tanh(x @ w)
        return jax.nn.softmax(h * 2.0, axis=-1) + jnp.exp(-h)

    x = rng.standard_normal((64, 128), dtype=np.float32)
    w = (rng.standard_normal((128, 128)) * 0.1).astype(np.float32)
    g, names = trace_to_graph(f, x, w)
    expected = np.asarray(f(x, w))
    inputs = dict(zip(names, [x, w]))
    for mode in ("off", "xla", "stitch"):
        out = StitchCompiler(mode=mode).compile(g)(inputs)
        np.testing.assert_allclose(
            np.asarray(out[g.outputs[0]]), expected, rtol=2e-4, atol=2e-4)


def test_packing_of_independent_ops(rng):
    """Paper §5.1 kernel packing: independent same-shape elementwise chains
    (gradient-accumulation pattern) end up in ONE kernel."""
    from repro.core import GraphBuilder
    b = GraphBuilder("pack")
    outs = []
    for i in range(4):
        x = b.param(f"x{i}", (256, 128))
        y = b.param(f"y{i}", (256, 128))
        outs.append(b.ew("add", b.ew("mul", x, y), x))
    g = b.build(outputs=outs)
    cg = StitchCompiler(mode="stitch").compile(g)
    assert cg.stats.n_kernels == 1, "independent chains should pack"
    # xla baseline cannot pack (no data deps between chains)
    cg_xla = StitchCompiler(mode="xla").compile(g)
    assert cg_xla.stats.n_kernels == 4
    inputs = {f"x{i}": rng.standard_normal((256, 128), dtype=np.float32)
              for i in range(4)}
    inputs |= {f"y{i}": rng.standard_normal((256, 128), dtype=np.float32)
               for i in range(4)}
    ref = build_reference_fn(g)(inputs)
    out = cg(inputs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-6)


def test_emit_source_readable():
    g, x, y = make_softmax_graph(rows=8, cols=16)
    from repro.core import FusionPattern, CostModel, generate_templates
    p = FusionPattern(g, frozenset(n for n in g.nodes if n != x))
    templates = generate_templates(p, CostModel())
    assert templates
    src = emit_source(p, templates[0])
    assert "def stitched_kernel" in src and "template:" in src
    assert "jnp.max" in src or "ew." in src


def test_stats_pattern_classes():
    g = make_mlp_norm_graph()
    cg = StitchCompiler(mode="stitch").compile(g)
    assert sum(cg.stats.pattern_classes.values()) >= 1
    assert cg.stats.modeled_time > 0


def test_attention_mlp_block_single_kernel(rng):
    """A full transformer block — rmsnorm, q/k/v projections, Pallas flash
    attention, output projection, MLP, residuals — compiles to one stitched
    kernel on each side of the attention kernel.  The traced flash kernel
    is tagged by its body name, and it stays a kernel of its own: stitched
    around, it would nest one pallas_call in another, which Mosaic does
    not lower."""
    from repro.kernels.flash_attention import flash_attention

    B, S, D, H = 2, 128, 16, 2
    dh, F = D // H, 4 * 16

    def mk(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32) * 0.1)

    wq, wk, wv, wo = mk(D, D), mk(D, D), mk(D, D), mk(D, D)
    w1, w2, g1, g2 = mk(D, F), mk(F, D), mk(D), mk(D)
    x = mk(B, S, D)

    def rms(v, gain):
        var = jnp.mean(v * v, axis=-1, keepdims=True)
        return v * jax.lax.rsqrt(var + 1e-6) * gain

    def block(wq, wk, wv, wo, w1, w2, g1, g2, x):
        h = rms(x, g1)
        q = (h @ wq).reshape(B, S, H, dh)
        k = (h @ wk).reshape(B, S, H, dh)
        v = (h @ wv).reshape(B, S, H, dh)
        a = flash_attention(q, k, v, causal=True).reshape(B, S, D)
        x2 = x + a @ wo
        return x2 + jax.nn.gelu(rms(x2, g2) @ w1) @ w2

    args = (wq, wk, wv, wo, w1, w2, g1, g2, x)
    ref = np.asarray(jax.jit(block)(*args))
    g, names = trace_to_graph(block, *args)
    (flash,) = [n.name for n in g.nodes.values()
                if n.attrs.get("kernel") == "_flash_kernel"]
    cg = StitchCompiler(mode="stitch").compile(g)
    assert cg.stats.n_kernels == 3, cg.stats
    assert cg.stats.pallas_groups == 2, cg.stats
    assert frozenset([flash]) in [grp.members for grp in cg.groups]
    out = cg(dict(zip(names, args)))
    np.testing.assert_array_equal(np.asarray(out[g.outputs[0]]), ref)


def test_unregistered_custom_still_partitions(rng):
    """An opaque custom op with no registry entry keeps its partition-op
    status — no silent attempt to inline arbitrary foreign kernels."""
    from functools import partial

    @partial(jax.custom_vjp)
    def opaque(x):
        return jnp.tanh(x) * 1.5

    opaque.defvjp(lambda x: (opaque(x), x), lambda res, ct: (ct,))

    def f(x, w):
        return jax.nn.relu(opaque(x @ w) + 1.0)

    x = jnp.asarray(rng.standard_normal((32, 64), np.float32))
    w = jnp.asarray(rng.standard_normal((64, 64), np.float32) * 0.1)
    g, names = trace_to_graph(f, x, w)
    customs = [n for n in g.nodes.values()
               if n.kind.value == "custom" and "project" not in n.attrs]
    if not customs:
        pytest.skip("custom_vjp traced away; nothing to assert")
    cg = StitchCompiler(mode="stitch").compile(g)
    groups_with_custom = [grp for grp in cg.groups
                          if any(c.name in grp.members for c in customs)]
    for grp in groups_with_custom:
        assert len(grp.members) == 1, "unregistered custom must not fuse"


@pytest.mark.parametrize("rows,d,gemm_inside", [(128, 256, True),
                                                (8, 2048, False)])
def test_vmem_budget_decides_gemm_admission(rng, rows, d, gemm_inside):
    """On the TPU model a kernel's double-buffered blocks count against the
    on-chip budget: a 256x256 weight stitches with its norm, a 2048x2048
    float32 one (32 MiB double-buffered, over 16 MiB) stays outside every
    Pallas group, though both GEMMs are below ``large_gemm_flops``.  Either
    way the plan computes the oracle's result."""
    g = make_mlp_norm_graph(rows, d)
    cg = StitchCompiler(mode="stitch").compile(g)
    pallas = [grp for grp in cg.groups if grp.kind == "pallas"]
    assert pallas
    assert any("dot_0" in grp.members for grp in pallas) == gemm_inside
    inputs = {"x": rng.standard_normal((rows, d), dtype=np.float32),
              "w": (rng.standard_normal((d, d)) * 0.05).astype(np.float32),
              "gamma": rng.standard_normal(d, dtype=np.float32),
              "eps": np.float32(1e-5)}
    ref = build_reference_fn(g)(inputs)
    out = cg(inputs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=2e-4, atol=2e-4)
