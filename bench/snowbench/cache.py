"""JAX's persistent compilation cache at a fixed path of the checkout."""
from __future__ import annotations

import os
from pathlib import Path


def use_compile_cache(root: Path) -> str:
    """Point JAX's persistent compile cache at ``$JAX_COMPILATION_CACHE_DIR``
    (JAX reads the variable itself) or else at ``<root>/.jax_cache``: a
    fixed path, so the next run of this checkout hits the cache.  Every
    program is cached, however fast it compiled, so that a warm run
    compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
