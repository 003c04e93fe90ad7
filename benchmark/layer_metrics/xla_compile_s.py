"""Seconds the backend compiled inside set-up (``backend_compile_s`` of the
account's phases: the ``jax.backend_compile`` spans beneath each; a
program the cache served is a ``jax.cache_load`` instead): 0 in a run the
compile cache serves whole. None where the program keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    return sa.jax_s("backend_compile_s")
