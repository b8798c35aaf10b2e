"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that compile at full size (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable` before their first compile, so a
second process on the same machine reads the programs the first one wrote.
"""
from __future__ import annotations

import os
import pathlib

#: the environment variable JAX itself reads for the cache directory
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (listed in ``.gitignore``).  It is a
#: fixed path, never built from a temporary name, a process id or the time:
#: a directory that moves between runs never hits.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already caches there and
    nothing is set here.  Otherwise the cache goes to
    ``CHECKOUT_CACHE_DIR``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
