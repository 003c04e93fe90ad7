"""Device time per traced step under the program's ``mla_core`` scope (the
attention function's call inside a latent mixer: the flash kernels
``flash_fwd`` / ``flash_dq`` / ``flash_dkdv`` on the chip), forward,
backward and the recomputed forward, every latent layer: a cross-cut of
``mla_ms_per_step``. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "mla_core")
