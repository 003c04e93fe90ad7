"""Device time per traced step under the program's ``dense_ffn`` scope (a
block's DENSE feed-forward alone, the SwiGLU of a leading dense layer or
the two-matmul GELU one; the pre-norm, a sandwich norm and the residual add
lie outside), forward, backward and the recomputed forward, read as
``moe_ms_per_step`` reads its scope. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "dense_ffn")
