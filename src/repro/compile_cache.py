"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from typing import Optional

import jax

#: fixed directory inside the checkout (listed in .gitignore); the cache
#: only hits again when the path stays the same, so it never varies
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> Optional[str]:
    """Turn on the persistent compilation cache for an accelerator backend
    and return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``DEFAULT_DIR``.  Every compile is cached, however short.  On the CPU
    it does nothing and returns None: XLA:CPU warns on every executable
    it loads back from the cache.  Entry points call this at the start of
    ``main``, never at import."""
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
