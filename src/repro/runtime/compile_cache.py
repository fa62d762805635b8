"""Where JAX keeps its persistent compilation cache.

Launchers and ``chip_smoke.py`` call ``enable()`` once, before they
compile anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
uses that directory and nothing is changed.  Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout: a fixed path, because the
path is part of what makes a later run find an entry again.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
