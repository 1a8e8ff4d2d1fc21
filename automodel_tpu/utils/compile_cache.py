"""One persistent XLA compile cache, placed from outside or at a fixed path.

Every entry point that can touch a device calls ``enable_compile_cache()``
first. The cache's directory is part of each entry's key, so a directory
that moves (a tempdir, a pid, a timestamp) never hits — hence exactly two
cases:

- ``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads it itself and this sets
  no path in code, so whoever launched the process decides where compiled
  programs live (and whether the next process finds them again);
- otherwise: ``.jax_compile_cache/`` at the root of the checkout
  (git-ignored), shared by the CLI, the benchmark's runs, the tools and
  chip_smoke.py's legs.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """→ the directory compiled programs persist in (see module doc)."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
