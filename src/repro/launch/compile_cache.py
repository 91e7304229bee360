"""JAX's persistent compilation cache for the entry points.

Each entry point (``chip_smoke.py``, ``repro.launch.serve``/``train``/``tune``)
calls :func:`enable_compile_cache` first in its ``main``; importing this
module, and the tests, change nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed path: the cache key includes it, so a directory that moved between
# runs would never hit.  ``.gitignore`` lists it.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across processes.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing is set here; otherwise the cache goes to
    ``<repo root>/.jax_cache``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
