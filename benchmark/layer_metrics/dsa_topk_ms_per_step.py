"""Device time per traced step under the program's ``dsa_topk`` scope (the
choice alone: each query's ``topk`` keys of a block's index scores,
``ops/dsa.py:choose``), every layer: a cross-cut of ``attn_ms_per_step``.
Once a layer and step: a block recomputed in the backward pass keeps the
choice by name and holds none of this. None from a program without the
scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "dsa_topk")
