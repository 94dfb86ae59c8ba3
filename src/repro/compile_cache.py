"""Where JAX's persistent compilation cache lives for this repository's
entry points (`chip_smoke.py`, `repro.launch.train_gbdt`,
`benchmarks/pipeline.py`).

`JAX_COMPILATION_CACHE_DIR`, when set, wins and nothing else is set.
Otherwise the cache goes to `<checkout>/.jax_cache`: a fixed path, so a
second run of the same program finds the first run's executables (the
directory is part of the cache key; a temporary or per-process directory
never hits). Nothing calls this at import time — the test suite runs
without a persistent cache.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def compile_cache_dir(environ=os.environ) -> str:
    """The directory `enable_compile_cache` points JAX at."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
