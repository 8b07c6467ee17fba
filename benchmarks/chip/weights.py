"""Seeded random weights, made by the benchmark and handed to both the
system under test and the plain reference.

One jitted call builds every leaf on the device, in the dtype the
configuration serves (``serve_dtype``).  The layout, leaf name -> (shape,
init kind), is the architecture's (``arch.load(cfg).shapes``); the leaves
are drawn in sorted order of their names, leaf ``i`` from
``fold_in(key, i)``, and nested on the dots of their names.  Init kinds:
``("normal", std)`` and ``("gain",)``, 1 + 0.1 * normal.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import arch


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, val in flat.items():
        *path, leaf = name.split(".")
        node = tree
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = val
    return tree


def make(cfg: dict, seed: int, dtype=None):
    """All weights for ``cfg`` from ``seed``, in one jitted call on the
    default device."""
    dtype = jnp.dtype(dtype or cfg["serve_dtype"])
    spec = arch.load(cfg).shapes(cfg)

    def build(key):
        flat = {}
        for i, (name, (shape, kind)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if kind[0] == "gain":
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = kind[1] * jax.random.normal(k, shape, jnp.float32)
            flat[name] = x.astype(dtype)
        return _nest(flat)

    return jax.jit(build)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any integer below 2**64.  ``jax.random.key`` keeps
    only the low 32 bits without 64-bit mode, so the high word is folded
    in: seeds that differ above bit 31 still give different keys."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed >> 32)
