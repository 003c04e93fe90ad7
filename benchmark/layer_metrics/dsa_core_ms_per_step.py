"""Device time per traced step under the program's ``dsa_core`` scope (the
attention function's call on grouped K/V heads with the indexer's choice
as a selection: the flash kernels ``flash_fwd`` / ``flash_bwd`` on the
chip, the layout passes around them and the sum of dk and dv over each
group of query heads), forward and backward, every layer: a cross-cut of
``attn_ms_per_step``. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "dsa_core")
