"""Where JAX keeps its persistent compilation cache.

Compiling the device kernels dominates a cold run, and the cache only
hits when its directory stays put: the directory is part of the key.
JAX reads JAX_COMPILATION_CACHE_DIR itself, so where that is set the
code sets nothing; otherwise the cache lives at <checkout>/.jax_cache,
a fixed path that no temporary name, pid or clock reading enters.
"""

from __future__ import annotations

import os
import pathlib

#: The fallback directory, inside the checkout (listed in .gitignore).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
