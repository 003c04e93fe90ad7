"""The sparse attention cores' share of their roofline: the least time the
chip could take for a step's model FLOPs of the cores over the CHOSEN
(query, key) pairs only (the family's closed form,
``dsa_core_flops_per_step``: Q K^T and P V over the head's features, once
forward and twice backward, over the bf16 peak of ``peaks.json``) over the
device time under the program's ``dsa_core`` scope. FLOPs bound it (a
query's 2,048 chosen keys against rows of 128 features). The kernels
compute every causal tile and mask the pairs that were not chosen, and
recompute the scores in their backward pass: a core that is dense under
its mask reads under 44 % of what the same kernels read on all causal
pairs at seq 8,192 (the chosen pairs' share), which is the point of
counting so. None where the program has no such scope or the family no
such closed form."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "dsa_core")
    flops = getattr(ctx.family, "dsa_core_flops_per_step", None)
    if not ms or ctx.peaks is None or flops is None:
        return None
    rows = rec["tokens_per_step"] / rec["chips"] / ctx.traffic["seq"]
    least_s = flops(ctx.config, rows, ctx.traffic["seq"]) \
        / ctx.peaks["bf16_flops"]
    return 100.0 * least_s / (ms / 1e3)
