"""Where JAX keeps its persistent compilation cache.

The runtime compiles one small XLA program per block shape and payload,
so a warm cache saves most of a cold start.  The cache directory is part
of what makes an entry findable again, so it is never temporary or
per-process:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
  here overrides it;
* otherwise: ``.jax_cache/`` at the root of the checkout (gitignored).

Call :func:`enable_compile_cache` before the first compilation; importing
this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# the default (1 s) skips nearly every per-block program the runtime
# compiles; a tenth of a second still keeps trivial programs out
_MIN_COMPILE_SECS = 0.1


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        path = env_dir
    else:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_SECS
        )
    return path
