"""Pallas kernels: the stitched-kernel emitter and the hand-written kernels.

Whether Pallas runs compiled or in its interpreter is decided here, once,
from the platform: every ``pallas_call`` in the package asks
:func:`interpret_mode` when it is built.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Interpret Pallas exactly when JAX's default backend is the CPU (which
    has no Mosaic compiler); on a TPU every kernel compiles."""
    return jax.default_backend() == "cpu"
