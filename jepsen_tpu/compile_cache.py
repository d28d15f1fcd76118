"""Where JAX keeps its persistent compilation cache.

Every entry point that touches the device (`chip_smoke.py`, the bench
children, `cli.main`, the checkerd daemon) calls `place()` before its
first compile.  When `JAX_COMPILATION_CACHE_DIR` is set, JAX already
reads it and `place()` sets nothing.  Otherwise the cache goes to one
fixed directory, `<repo>/.jax_cache` (git-ignored): the cache key
includes nothing about the path, but a directory that moves between
runs never hits, so the path must not depend on the caller.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: The fixed fallback: next to the `jepsen_tpu` package, in the repo.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def place() -> str:
    """Points JAX's persistent compilation cache at its one directory
    and returns that directory.  Call before the process's first
    compile: JAX decides once per process whether the cache is used."""
    ambient = os.environ.get(ENV)
    if ambient:
        return ambient
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
