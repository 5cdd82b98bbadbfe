"""JAX's persistent compilation cache for the entry points.

`enable()` is called from the `main()` of each launcher and from
`chip_smoke.py` — never at import. Where `JAX_COMPILATION_CACHE_DIR` is set
the cache lives there and nowhere else; otherwise at `<checkout>/.jax_cache`
(listed in `.gitignore`). The path is fixed because it is part of the
cache's key: a directory named after a pid, a temp dir or the time would
never be hit again.
"""
from __future__ import annotations

import os

import jax

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable() -> str:
    """Turn the persistent cache on and return its directory. Every
    program is cached, however quick its compile: a one-chip serving run
    compiles dozens of small per-(meta, bucket) programs."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
