"""Where entry points keep JAX's persistent compilation cache.

Call ``enable()`` from an entry point (a script's ``main``), never at
import: tests and library users keep JAX's own default (no cache).
"""
from __future__ import annotations

import os
import pathlib

#: the checkout root (this file is src/repro/launch/compile_cache.py)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
#: the fixed in-checkout cache path (gitignored).  A later run finds the
#: cache only where an earlier one left it, so the path must not move
#: between runs: no temp dir, pid or timestamp in it.
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    wins: nothing is set here.  Otherwise the cache goes to
    ``DEFAULT_DIR``, so a second run in the same checkout reuses the
    first run's compiles.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
