"""Launchers: production mesh, multi-pod dry-run, training and serving."""
import os


def use_compile_cache(default_dir: str) -> str:
    """Keep JAX's persistent compile cache in ``default_dir``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set, which JAX reads itself. For entry
    points (``chip_smoke.py``, ``benchmarks.run``), never on import; the
    path is fixed because it is part of every cache key. Returns the
    directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", default_dir)
    return jax.config.jax_compilation_cache_dir
