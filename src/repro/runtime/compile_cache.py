"""Where the persistent XLA compile cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and nothing
else is used.  Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout: a fixed path, because the directory is part of what a
later process looks up, so a path that moves between runs never hits.
"""

from __future__ import annotations

import os

import jax

#: The in-checkout default (gitignored).
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process; returns its
    directory.  Call it from entry points only: importing a module must
    not change global JAX configuration."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
