"""Process environment shared by the launchers, the test suite, the
benchmark harness and the chip smoke: pre-jax host-platform device forcing
and the placement of JAX's persistent compile cache.

jax locks the device count at first init, so this must run before the first
jax import anywhere in the process — which is why this module deliberately
imports nothing but ``os``.  An operator-provided
``--xla_force_host_platform_device_count`` already in ``XLA_FLAGS`` always
wins; other flags in the variable are preserved (the
:mod:`repro.launch.dryrun` merge idiom).
"""

from __future__ import annotations

import os
from pathlib import Path

FLAG = "--host-devices"

# fixed, at the checkout root: a directory named per run (temporary name,
# process id, time) would never be found again by the next run
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def parse_host_devices(argv) -> int | None:
    """Extract ``--host-devices N`` / ``--host-devices=N`` from ``argv``.
    Malformed or missing values return None — argparse (which also declares
    the flag) produces the user-facing error later."""
    for i, a in enumerate(argv):
        if a == FLAG:
            if i + 1 < len(argv):
                try:
                    return int(argv[i + 1])
                except ValueError:
                    return None
        elif a.startswith(FLAG + "="):
            try:
                return int(a.split("=", 1)[1])
            except ValueError:
                return None
    return None


def force_host_devices(n: int | None = None, argv=None) -> None:
    """Force ``n`` host-platform devices (or the count named by
    ``--host-devices`` in ``argv``) by merging into ``XLA_FLAGS``.  No-op if
    neither is given or the operator already forced a count."""
    if n is None:
        n = parse_host_devices(argv if argv is not None else [])
    if n is None:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n)}").strip()


def use_compile_cache() -> None:
    """Turn on JAX's persistent compile cache.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    directory is set here; otherwise the cache lives in ``.jax_cache/`` at
    the checkout root."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
