"""Where JAX keeps its persistent compilation cache for this checkout.

Call ``use_compile_cache()`` once at the start of a program (the chip
smoke check, the benchmark runner, the examples), never at library
import.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing else is set here; otherwise the cache lives in a fixed
``.jax_cache/`` at the checkout root, so that repeated runs from the same
checkout find their compiled kernels again.
"""

from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
