"""Device time per traced step under the program's ``swa_core`` scope (the
attention function's call of a SLIDING-WINDOW layer: on the chip the flash
kernels ``flash_fwd`` / ``flash_bwd`` over the tiles the window's band
touches alone, the layout passes around them and the sum of dk and dv over
each group of query heads), forward and backward, every window layer: a
cross-cut of ``attn_ms_per_step`` beside ``dsa_core_ms_per_step``, which
holds the same model's global layers. None from a program without the
scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "swa_core")
