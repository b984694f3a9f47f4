"""Where the persistent XLA compilation cache lives.

The one place in the repo that sets it; every entry point (the four
``launch/cli.py`` mains, ``chip_smoke.py``, ``__graft_entry__.py``) calls
:func:`place_compile_cache` first thing.  The directory is part of the cache
key, so it is either what the caller exported or one fixed path per
checkout — never a temp name.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Return the cache directory in use: ``JAX_COMPILATION_CACHE_DIR`` when
    the caller set it (jax reads it itself; nothing else is set here), else
    ``<checkout>/.jax_compile_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_compile_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
