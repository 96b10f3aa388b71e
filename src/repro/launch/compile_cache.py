"""JAX's persistent compilation cache, kept at one fixed place.

A cached program is found again only under the directory it was written
to, so the directory must not move between runs: no temporary, process-id
or time-based path.
"""
from __future__ import annotations

import os

#: Cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: fixed,
#: inside the checkout, and listed in ``.gitignore``.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left to JAX and no other directory is set here."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
