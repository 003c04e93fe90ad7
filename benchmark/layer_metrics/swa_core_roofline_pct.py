"""The sliding-window attention cores' share of their roofline: the least
time the chip could take for a step's model FLOPs of the window layers'
cores over the (query, key) pairs INSIDE the window only (the family's
closed form, ``swa_core_flops_per_step``: Q K^T and P V over the head's
features, once forward and twice backward, over the bf16 peak of
``peaks.json``) over the device time under the program's ``swa_core``
scope. FLOPs bound it. A kernel that walks tiles behind the window's far
edge, or masks where it could skip, runs more than is counted and reads
lower; one that walks the band's tiles alone still computes the masked
halves of the tiles either edge crosses and recomputes the scores in its
backward pass, so the share stays under what the same kernels read on all
causal pairs, and under 100: no counted pair lies in a tile that is not
walked. None where the program has no such scope or the family no such
closed form."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "swa_core")
    flops = getattr(ctx.family, "swa_core_flops_per_step", None)
    if not ms or ctx.peaks is None or flops is None:
        return None
    rows = rec["tokens_per_step"] / rec["chips"] / ctx.traffic["seq"]
    least_s = flops(ctx.config, rows, ctx.traffic["seq"]) \
        / ctx.peaks["bf16_flops"]
    return 100.0 * least_s / (ms / 1e3)
