"""Persistent XLA compile cache, placeable from outside.

Every entry point (``ocvf-train``, ``ocvf-recognize``, ``bench.py``,
``bench_serving.py``, ``chip_smoke.py``, ``__graft_entry__``'s ``__main__``)
calls ``enable()`` first thing — never at package import, so importing the
library (and the CPU test suite) leaves JAX's configuration alone.

``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads that variable itself, so when
it is set this module does nothing and no code path names another
directory. Unset, the cache lives at ``<checkout>/.jax_cache`` (git-ignored),
derived from this file's location — the same directory from any working
directory and across runs, so a later run finds what an earlier one
compiled.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache``: three levels up from this file
#: (utils/ -> opencv_facerecognizer_tpu/ -> checkout root).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Make sure a persistent compile cache is configured; returns the
    directory in use."""
    env_dir = os.environ.get(CACHE_DIR_ENV)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
