"""Where XLA's persistent compilation cache lives.

A BERT-scale train step takes tens of seconds to compile on a TPU and the
decode engine compiles a program per prompt bucket, so every process that
starts a trainer or an engine (bench.py, chip_smoke.py, scripts/) calls
`enable()` once, before its first compile. Nothing else in the tree sets
a cache directory.
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache, from this package's own location: the directory
# is part of the cache key, so it must be the same path on every run —
# never a temp dir, a pid or a time
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX has already read it and no
    directory is set in code. Without it the cache goes to DEFAULT_DIR.
    """
    import jax
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", directory)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # JAX keeps only compiles slower than 1 s by default; prefill
        # buckets, write programs and the eager ops of a startup program
        # are faster than that one by one and slow in sum
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
