"""Where JAX keeps its persistent compilation cache.

A cache entry's key includes the directory, so the directory must not
move between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself) or a fixed path inside the
checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; call before the
    first compile.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


__all__ = ["CACHE_DIR", "use_compile_cache"]
