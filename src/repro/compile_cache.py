"""Where JAX's persistent compilation cache lives for this repo's entry points.

The fused propose programs take seconds to minutes to compile for a TPU, so
every entry point that runs them (``chip_smoke.py``, ``benchmarks/run.py``)
calls :func:`enable_compile_cache` before its first compile. The cache's
directory is part of its key, so it is one fixed path: JAX's own
``JAX_COMPILATION_CACHE_DIR`` when that is set, otherwise
``.cache/jax_compile`` inside the checkout.
"""

from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache", "jax_compile",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    other directory is configured here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
