"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) and the test suite's ``conftest.py`` call
``use_compile_cache()`` once, before they compile anything.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# One fixed path (never a temporary name, a process id or the time), so a
# later run on the same checkout finds what an earlier one compiled.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself; no other directory is set in code.  Otherwise the cache lives
    in ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
