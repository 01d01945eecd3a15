"""Where JAX keeps compiled programs between processes.

One function, called by every entry point that compiles on the chip
(``chip_smoke.py``, ``benchmarks/run.py``) before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def use_compile_cache(repo_root) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it by
    itself, so nothing is set here.  Otherwise the cache lives at the fixed
    ``<repo_root>/.jax_cache``, so a later process run from the same
    checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(repo_root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
