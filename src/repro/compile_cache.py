"""JAX's persistent compilation cache, kept at one fixed place.

A full-width decode step takes tens of seconds to compile, and every
process that drives the chip would otherwise pay it again.  JAX keys
cache entries by the program and the cache directory, so the directory
must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when
that is set (JAX reads the variable itself, and nothing here overrides
it), else ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Entry points call :func:`enable` before their first compile; importing
this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the repository checkout this package lives in (``src/repro/..``)
REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
