"""Where the persistent XLA compilation cache lives.

The cache is placed from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
helper touches nothing. Otherwise the cache goes to ONE fixed directory
inside the checkout (``<repo>/.jax_cache``, gitignored) — fixed because a
later process can only find the cache again at the same path; a temp
dir, a pid or a timestamp in the path never hits.

Entry points that compile (``chip_smoke.py``, ``benchmark/run.py``, the
``examples/`` trainers) call :func:`enable_compile_cache` before
their first compile. Library code never does: where a process keeps its
cache is the entry point's decision.
"""
import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns the directory
    in use (the environment's, else :data:`DEFAULT_CACHE_DIR`)."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
