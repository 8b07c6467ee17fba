"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its pure-jnp oracle in ref.py."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import norms, activations, softmax as ksoftmax, rope as krope
from repro.kernels import cross_entropy as kxent, flash_attention as kflash
from repro.kernels import mamba_scan as kmamba, rg_lru as krglru, router as krouter

RNG = np.random.default_rng(7)


def _x(shape, dtype="float32", scale=1.0):
    return (RNG.standard_normal(shape) * scale).astype(dtype)


SHAPES_2D = [(8, 128), (64, 256), (33, 512), (128, 96)]
DTYPES = ["float32", "bfloat16"]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_sweep(shape, dtype):
    x, g = _x(shape, dtype), _x(shape[-1:], dtype)
    np.testing.assert_allclose(
        np.asarray(norms.rmsnorm(x, g), np.float32),
        np.asarray(ref.rmsnorm(x, g), np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES_2D)
def test_rmsnorm_residual_sweep(shape):
    x, r, g = _x(shape), _x(shape), _x(shape[-1:])
    got = norms.rmsnorm_residual(x, r, g)
    want = ref.rmsnorm_residual(x, r, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
def test_layernorm_sweep(shape, dtype):
    x = _x(shape, dtype)
    g, b = _x(shape[-1:]), _x(shape[-1:])
    np.testing.assert_allclose(
        np.asarray(norms.layernorm(x, g, b), np.float32),
        np.asarray(ref.layernorm(x, g, b), np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES_2D + [(4, 16, 64)])
@pytest.mark.parametrize("scale", [1.0, 0.125])
def test_softmax_sweep(shape, scale):
    x = _x(shape, scale=3.0)
    np.testing.assert_allclose(
        np.asarray(ksoftmax.softmax(x, scale)),
        np.asarray(ref.softmax(x, scale)), rtol=2e-5, atol=2e-6)


def test_softmax_masked_fully_masked_row():
    x = _x((4, 64))
    mask = np.ones((4, 64), bool)
    mask[2] = False  # fully-masked row must not produce NaN
    out = np.asarray(ksoftmax.softmax(x, 1.0, mask))
    assert np.isfinite(out).all()


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
def test_glu_sweep(shape, dtype):
    g, u = _x(shape, dtype), _x(shape, dtype)
    np.testing.assert_allclose(
        np.asarray(activations.swiglu(g, u), np.float32),
        np.asarray(ref.swiglu(g, u), np.float32), **_tol(dtype))
    np.testing.assert_allclose(
        np.asarray(activations.geglu(g, u), np.float32),
        np.asarray(ref.geglu(g, u), np.float32), **_tol(dtype))
    np.testing.assert_allclose(
        np.asarray(activations.squared_relu(g), np.float32),
        np.asarray(ref.squared_relu(g), np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,L,H,dh", [(1, 16, 2, 32), (2, 64, 8, 64), (3, 128, 4, 128)])
@pytest.mark.parametrize("theta", [10000.0, 1e6])
def test_rope_sweep(B, L, H, dh, theta):
    x = _x((B, L, H, dh))
    pos = np.broadcast_to(np.arange(L, dtype=np.int32), (B, L))
    np.testing.assert_allclose(
        np.asarray(krope.rope(x, pos, theta)),
        np.asarray(ref.rope(x, pos, theta)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,V", [(16, 512), (64, 4096), (33, 1000)])
def test_cross_entropy_sweep(B, V):
    logits = _x((B, V), scale=4.0)
    labels = RNG.integers(0, V, B).astype(np.int32)
    np.testing.assert_allclose(
        float(kxent.cross_entropy(logits, labels)),
        float(ref.cross_entropy(logits, labels)), rtol=1e-5)


@pytest.mark.parametrize("Hq,Hkv", [(8, 8), (8, 2), (16, 1)])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_attention_sweep(Hq, Hkv, window):
    q = _x((2, 128, Hq, 64), scale=0.5)
    k = _x((2, 128, Hkv, 64), scale=0.5)
    v = _x((2, 128, Hkv, 64))
    np.testing.assert_allclose(
        np.asarray(kflash.flash_attention(q, k, v, window=window)),
        np.asarray(ref.attention(q, k, v, window=window)),
        rtol=2e-4, atol=2e-4)


def test_flash_attention_q_offset_decode_chunk():
    """Chunked prefill: second half attends to full first half."""
    q = _x((1, 64, 4, 32))
    k = _x((1, 128, 4, 32))
    v = _x((1, 128, 4, 32))
    got = kflash.flash_attention(q, k, v, q_offset=64)
    pos_q = (64 + np.arange(64))[None]
    want = ref.attention(q, k, v, positions_q=pos_q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("Bb,L,Dm,N", [(1, 16, 32, 8), (2, 48, 64, 16), (2, 33, 128, 16)])
def test_mamba_scan_sweep(Bb, L, Dm, N):
    x = _x((Bb, L, Dm), scale=0.5)
    dt = np.abs(_x((Bb, L, Dm), scale=0.1))
    A = -np.abs(_x((Dm, N)))
    B = _x((Bb, L, N), scale=0.3)
    C = _x((Bb, L, N), scale=0.3)
    D = _x((Dm,))
    np.testing.assert_allclose(
        np.asarray(kmamba.mamba_scan(x, dt, A, B, C, D)),
        np.asarray(ref.mamba_scan(x, dt, A, B, C, D)), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,L,D", [(1, 16, 64), (2, 48, 128), (2, 37, 256)])
def test_rg_lru_sweep(B, L, D):
    x, ig, rg_, lam = _x((B, L, D)), _x((B, L, D)), _x((B, L, D)), _x((D,))
    np.testing.assert_allclose(
        np.asarray(krglru.rg_lru(x, ig, rg_, lam)),
        np.asarray(ref.rg_lru(x, ig, rg_, lam)), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("T,E,k", [(64, 8, 2), (256, 60, 4), (128, 32, 8)])
def test_router_sweep(T, E, k):
    logits = _x((T, E), scale=2.0)
    w1, i1 = krouter.topk_router(logits, k)
    w2, i2 = ref.topk_router(logits, k)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


def test_router_weights_sum_to_one():
    logits = _x((64, 16), scale=2.0)
    w, _ = krouter.topk_router(logits, 4)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)


def test_mamba_final_state_matches_incremental():
    """prefill state == running decode steps one by one."""
    Bb, L, Dm, N = 1, 8, 16, 4
    x = _x((Bb, L, Dm), scale=0.5)
    dt = np.abs(_x((Bb, L, Dm), scale=0.1))
    A = -np.abs(_x((Dm, N)))
    B = _x((Bb, L, N), scale=0.3)
    C = _x((Bb, L, N), scale=0.3)
    D = _x((Dm,))
    _, h = ref.mamba_scan(x, dt, A, B, C, D, return_state=True)
    hinc = np.zeros((Bb, Dm, N), np.float32)
    for t in range(L):
        dA = np.exp(dt[:, t, :, None] * A[None])
        dBx = (dt[:, t] * x[:, t])[..., None] * B[:, t][:, None, :]
        hinc = dA * hinc + dBx
    np.testing.assert_allclose(np.asarray(h), hinc, rtol=1e-4, atol=1e-5)


def test_interpret_mode_follows_platform():
    """Pallas runs interpreted exactly on the CPU backend."""
    import jax

    from repro import kernels

    assert kernels.interpret_mode() == (jax.default_backend() == "cpu")


def test_block_bytes_pad_to_the_chip_tile():
    """VMEM blocks pad their two minor dims to (8 sublanes of 32 bits,
    128 lanes): a (4, 1, 2048) bfloat16 block holds 4 x 16 x 2048 x 2."""
    from repro.core.cost import vmem_tile_bytes

    assert vmem_tile_bytes((4, 1, 2048), 2) == 4 * 16 * 2048 * 2
    assert vmem_tile_bytes((8, 2048), 4) == 8 * 2048 * 4
    assert vmem_tile_bytes((1,), 4) == 8 * 128 * 4
    assert vmem_tile_bytes((3, 100), 1) == 32 * 128
