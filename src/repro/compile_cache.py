"""JAX's persistent compilation cache, placed from outside the program.

A cold compile of a training round takes tens of seconds to minutes; the
cache lets a second process (or a later run) load it instead. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and nothing is
set here. Otherwise the cache lives in the checkout at a fixed path — the
path is part of what a later run must find again, so it never depends on a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    CHECKOUT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
