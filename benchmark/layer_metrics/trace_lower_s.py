"""Seconds JAX spent tracing functions to jaxprs and lowering them to
modules inside set-up (``trace_lower_s`` of the account's phases: the
``jax.trace`` + ``jax.lower`` spans beneath each, nested ones counted
once): what no compile cache saves. None where the
program keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    return sa.jax_s("trace_lower_s")
