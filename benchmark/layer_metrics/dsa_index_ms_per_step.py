"""Device time per traced step under the program's ``dsa_index`` scope (a
sparse attention's indexer: its three projections, the key's LayerNorm,
the rotation, and each block's index scores, float32 products), every
layer: a cross-cut of ``attn_ms_per_step``. Forward only: no gradient
passes a choice of keys, and a recomputed block keeps the choice by name
(``ops/dsa.py:KEPT``). None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "dsa_index")
