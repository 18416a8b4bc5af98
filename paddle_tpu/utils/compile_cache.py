"""Where JAX's persistent compilation cache lives.

The directory is part of the cache's key, so it must not move between runs:
no temporary name, pid or timestamp.  Whoever runs the program may place it
from outside with ``JAX_COMPILATION_CACHE_DIR`` (jax reads that variable
itself, and then nothing is set in code); otherwise it is one fixed,
git-ignored directory at the root of the checkout.
"""
from __future__ import annotations

import os

_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Make compiled programs persist across processes; returns the
    directory in effect.  Call before the first compilation."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return jax.config.jax_compilation_cache_dir
