"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and only the
dry-run wants 512 placeholder devices).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the stitched executors place
    arrays through ``shard_map`` specs, not through sharding-in-types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; the multi-pod mesh adds an outer 2-pod DP
    axis (gradient reduction crosses DCN on 'pod', ICI on 'data')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Largest (data, model) mesh over whatever devices exist (1 on this CPU
    container, N under ``--xla_force_host_platform_device_count=N``) — used
    by the real train/serve drivers and the elastic-restart path.

    ``model_parallel`` must divide the device count: the old
    ``max(n // model_parallel, 1)`` silently built a mesh wanting
    ``data * model_parallel != n`` devices, and ``jax.make_mesh`` then
    failed with an opaque reshape error deep in the launcher."""
    n = len(jax.devices())
    if model_parallel < 1:
        raise ValueError(f"model_parallel must be >= 1, got {model_parallel}")
    if n % model_parallel != 0:
        divisors = [d for d in range(1, n + 1) if n % d == 0]
        raise ValueError(
            f"model_parallel={model_parallel} does not divide the host "
            f"device count ({n} devices); valid values are {divisors}")
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))
