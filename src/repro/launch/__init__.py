# Launchers: mesh construction, multi-pod dry-run, train/serve drivers.
import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path, because the cache key includes it.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache in ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else in ``<repo>/.jax_cache``; returns the directory."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_COMPILE_CACHE))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
