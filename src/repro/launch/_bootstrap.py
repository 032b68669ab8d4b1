"""Pre-jax process bootstrap shared by the launch CLIs.

The host-platform device count and the compilation-cache directory are
read at first jax init, so drivers must set them before anything imports
jax. This module must therefore stay import-light (os/sys only).
"""
from __future__ import annotations

import os
import sys
from typing import List, Optional

#: the checkout root: src/repro/launch/_bootstrap.py -> four levels up
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def mesh_flag(argv: List[str]) -> Optional[str]:
    """The value of a ``--mesh X`` / ``--mesh=X`` argument, if present."""
    for i, a in enumerate(argv):
        if a.startswith("--mesh="):
            return a.split("=", 1)[1]
        if a == "--mesh" and i + 1 < len(argv):
            return argv[i + 1]
    return None


def force_host_devices(n) -> None:
    """Force ``n`` host-platform devices before the first jax init.

    No-op when jax is already imported (the count is locked) or when the
    flag is already present (e.g. conftest.py or a sweep env set it). Any
    pre-existing XLA_FLAGS are preserved, not clobbered. The flag only
    shapes the CPU backend: on a TPU host it changes nothing.
    """
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} --xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = flags.strip()


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path, before the
    first jax init, and return that path.

    A ``JAX_COMPILATION_CACHE_DIR`` already in the environment is used as
    it is. Otherwise the cache is ``<checkout>/.jax_cache``: the directory
    is part of what a later process must find again, so it is never built
    from a temporary name, a pid or the time.

    Programs are cached however quickly they compiled (unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise): JAX's
    default of one second would skip the serve path, which is a hundred
    programs of a fraction of a second each on a TPU."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            _CHECKOUT, ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]
