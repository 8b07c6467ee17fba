"""Public kernel API — jit-friendly wrappers that dispatch Pallas <-> oracle.

Models call these; ``KernelMode`` decides the backend:

* ``"pallas"``  — the Pallas kernels (interpreted on the CPU, compiled on a
                  TPU: :func:`repro.kernels.interpret_mode`).
* ``"ref"``     — the pure-jnp oracles; clean HLO for the multi-pod dry-run
                  and for gradient tracing (several kernels are fwd-only).

Default is "ref" so distributed lowering is always clean; tests/examples opt
in to "pallas".  The switch is a context var, so nested code needs no
threading of flags.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Literal

import jax
import jax.numpy as jnp

from . import ref as _ref
from . import norms as _norms
from . import activations as _act
from . import softmax as _softmax
from . import rope as _rope
from . import cross_entropy as _xent
from . import decode_attention as _decode
from . import flash_attention as _flash
from . import mamba_scan as _mamba
from . import rg_lru as _rglru
from . import router as _router

KernelMode = Literal["pallas", "ref"]
_mode: contextvars.ContextVar[str] = contextvars.ContextVar("kernel_mode", default="ref")


def get_mode() -> str:
    return _mode.get()


@contextlib.contextmanager
def kernel_mode(mode: KernelMode):
    tok = _mode.set(mode)
    try:
        yield
    finally:
        _mode.reset(tok)


def _use_pallas() -> bool:
    return _mode.get() == "pallas"


# -- wrappers -----------------------------------------------------------------

def rmsnorm(x, gamma, eps: float = 1e-6):
    if _use_pallas():
        return _norms.rmsnorm(x, gamma, eps)
    return _ref.rmsnorm(x, gamma, eps)


def rmsnorm_residual(x, res, gamma, eps: float = 1e-6):
    if _use_pallas():
        return _norms.rmsnorm_residual(x, res, gamma, eps)
    return _ref.rmsnorm_residual(x, res, gamma, eps)


def layernorm(x, gamma, beta, eps: float = 1e-5):
    if _use_pallas():
        return _norms.layernorm(x, gamma, beta, eps)
    return _ref.layernorm(x, gamma, beta, eps)


def softmax(x, scale: float = 1.0, mask=None):
    if _use_pallas():
        return _softmax.softmax(x, scale, mask)
    return _ref.softmax(x, scale, mask)


def swiglu(gate, up):
    if _use_pallas():
        return _act.swiglu(gate, up)
    return _ref.swiglu(gate, up)


def geglu(gate, up):
    if _use_pallas():
        return _act.geglu(gate, up)
    return _ref.geglu(gate, up)


def squared_relu(x):
    if _use_pallas():
        return _act.squared_relu(x)
    return _ref.squared_relu(x)


def rope(x, positions, theta: float = 10000.0):
    if _use_pallas():
        return _rope.rope(x, positions, theta)
    return _ref.rope(x, positions, theta)


def cross_entropy(logits, labels):
    if _use_pallas():
        return _xent.cross_entropy(logits, labels)
    return _ref.cross_entropy(logits, labels)


def attention(q, k, v, *, causal: bool = True, scale: float | None = None,
              window: int | None = None, q_offset: int = 0):
    if _use_pallas():
        return _flash.flash_attention(
            q, k, v, causal=causal, scale=scale, window=window, q_offset=q_offset)
    pos_q = None
    if q_offset:
        pos_q = (q_offset + jnp.arange(q.shape[1]))[None, :]
    return _ref.attention(q, k, v, causal=causal, scale=scale, window=window,
                          positions_q=pos_q)


def decode_attention(q, k, v, positions, *, scale: float | None = None,
                     window: int | None = None):
    """Single-token decode attention against a dense KV view; pallas-only
    (callers gate on :func:`get_mode` — the ref path is the einsum chain in
    :func:`repro.models.layers.apply_attention`)."""
    return _decode.decode_attention(q, k, v, positions, scale=scale,
                                    window=window)


def mamba_scan(x, delta, A, B, C, D, return_state: bool = False):
    if _use_pallas() and not return_state:
        return _mamba.mamba_scan(x, delta, A, B, C, D)
    return _ref.mamba_scan(x, delta, A, B, C, D, return_state=return_state)


def rg_lru(x, input_gate, rec_gate, Lambda, c: float = 8.0,
           return_state: bool = False):
    if _use_pallas() and not return_state:
        return _rglru.rg_lru(x, input_gate, rec_gate, Lambda, c)
    return _ref.rg_lru(x, input_gate, rec_gate, Lambda, c, return_state=return_state)


def topk_router(logits, k: int, renormalize: bool = True):
    if _use_pallas():
        return _router.topk_router(logits, k, renormalize)
    return _ref.topk_router(logits, k, renormalize)
