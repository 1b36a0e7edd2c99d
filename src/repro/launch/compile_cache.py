"""JAX's persistent compilation cache, set up in one place for every entry
point that runs on a chip (`chip_smoke.py`, `benchmarks/run.py`).

A cold process on a TPU spends most of a short run compiling the fused
programs, and the cache's directory is part of what an entry is found by,
so it lives at a fixed path and never at one derived from a temporary
name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory where `JAX_COMPILATION_CACHE_DIR` is not set
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    `JAX_COMPILATION_CACHE_DIR` where it is set (JAX reads the variable
    itself, so no other path is set here), else `<repo>/.jax_cache`.
    Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
