"""Device time per traced step under the program's ``mla`` scope (a latent
attention mixer: q, the latent and its up-projection, the attention core,
the output projection), forward, backward and recomputed, every latent
layer: a cross-cut of ``attn_ms_per_step``."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "mla")
