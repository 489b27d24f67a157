"""repro.launch: command-line entry points and the serving front end."""
import os
from pathlib import Path

#: Where compiled programs are cached when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path in the checkout, so a later run from the same checkout finds
#: them (the directory is part of the cache key).
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that directory
    and nothing is changed; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Called by the ``__main__`` blocks (never at
    import), so library users and tests keep JAX's default of no cache."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
