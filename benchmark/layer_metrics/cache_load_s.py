"""Seconds spent retrieving executables from the persistent compile cache
inside set-up (``cache_load_s`` of the account's phases: the
``jax.cache_load`` spans beneath each). None where the
program keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    return sa.jax_s("cache_load_s")
