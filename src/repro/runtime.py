"""Process-level JAX plumbing shared by the library and its entry points.

  * :func:`trace_state_clean` — whether code runs outside any jax trace
    (eager host logic such as spans, the escalation ladder and the
    Nyström rank probe branches on it).
  * :func:`enable_compile_cache` — the one place the persistent
    compilation cache directory is chosen.  Every entry point
    (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``) calls it
    first; library code never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, inside the checkout: the cache path is part of what a later run
# must find again, so it is never derived from a temp name, pid or time.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def trace_state_clean() -> bool:
    """True when no jax trace is active (concrete values, host time)."""
    return jax.core.trace_ctx.is_top_level()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (jax reads it
    itself; nothing else is set here).  Otherwise the cache lives at
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
