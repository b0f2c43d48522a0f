"""Where JAX's persistent compilation cache lives.

One rule for every entry point that wants compiled programs to survive
the process (``chip_smoke.py``): the operator's
``JAX_COMPILATION_CACHE_DIR`` wins, untouched; otherwise a fixed
directory in the checkout. The path is part of the cache key, so it
never derives from a pid, a time or ``tempfile``.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Turn the persistent cache on and return the directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX has already read it and
    nothing is set in code; unset, the cache goes to
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
