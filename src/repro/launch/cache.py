"""JAX persistent compilation cache placement.

A full-width train step takes tens of seconds to minutes to compile, so
every entry point that compiles one (``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``) calls ``enable_compile_cache``
first.  The cache key includes its directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax reads that
variable itself; nothing is set in code), otherwise ``.jax_cache/`` at the
root of the checkout.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
