"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold process on the chip spends most of its set-up compiling the
bucket ladders; the cache lets the next process load them instead.
Its directory is part of what makes an entry found again, so it never
moves: ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX applies it
itself), otherwise ``.jax_cache/`` at the root of the checkout this
package sits in.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.jax_cache`` — src/repro/common/ is three levels down.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
