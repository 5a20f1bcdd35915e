"""Which device a run used, and where its compiled programs are cached.

Every entry point calls :func:`enable_compile_cache` before its first
compilation and reports :func:`device_info` in its JSON, so a result always
names the hardware it came from.
"""

from __future__ import annotations

import os
from pathlib import Path

#: JAX's own variable; when set it alone decides where the cache lives.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Fixed in-checkout cache path (listed in .gitignore). The path is part of
#: what a later run must find again, so it never depends on a temp name, a
#: pid or the time.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    directory is set here. Otherwise the cache goes to :data:`CACHE_DIR`.

    The cache key covers each program's name stack (its ``named_scope``s),
    which JAX leaves out by default: an executable cached from code that
    named its layers otherwise is then never run in its place, so a
    profiler trace names the code as it stands.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX sees."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
