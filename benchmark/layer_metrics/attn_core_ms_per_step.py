"""Device time per traced step under the program's ``attn_core`` scope (every
call of a softmax attention core on q, k, v (plain, grouped or chosen,
latent: the flash kernels on the chip), the projections, norms and rotation
outside), forward, backward and the recomputed forward, read as
``moe_ms_per_step`` reads its scope. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "attn_core")
