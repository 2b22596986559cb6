"""Persistent XLA compilation cache for the entry points.

``launch/serve.py``, ``launch/train.py`` and ``chip_smoke.py`` call
``enable_compile_cache()`` from their ``main()``.  Nothing enables it at
import, and tests never do: a test process compiles from scratch.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
this sets nothing, so the cache goes there and nowhere else.  Otherwise
the cache goes to ``<checkout>/.jax_cache`` (git-ignored).  The path is
fixed — never drawn from a temp name, a PID or the clock — so a later run
of the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.
    -> that directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
