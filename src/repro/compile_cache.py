"""Where JAX's persistent compilation cache lives.

A compiled program is keyed on the cache directory among other things, so
the directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself), otherwise
the fixed ``<checkout>/.jax_cache``.  Nothing here runs at import; entry
points call :func:`enable_compile_cache` from their ``main``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: this file is ``<checkout>/src/repro/compile_cache.py``
CHECKOUT = Path(__file__).resolve().parents[2]

DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own reading of it
    stands and no other directory is set in code.  Without it, the cache
    goes to :data:`DEFAULT_CACHE_DIR` — never a temporary, per-process or
    time-stamped path, which would never be hit again.
    """
    # An executable keeps the HLO metadata of the program that compiled
    # it, and JAX leaves that metadata out of the key by default: a
    # program that differs from a cached one only in its layer names
    # (``repro.tracing``) would run, and profile, under the old names.
    # Key on the metadata, with each op's location cut to its name so that
    # the key holds no file path or line.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
