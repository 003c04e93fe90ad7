"""The grouped expert matmuls' share of their roofline: the least time the
chip could take for a step's expert FLOPs (the family's closed form,
``expert_flops_per_step``, over the bf16 peak of ``peaks.json``) over the
device time under the program's ``moe_experts`` scope (the three grouped
matmuls and the activation, forward and backward). FLOPs bound it: at
OLMoE's shapes a group is 1,024 rows against [2048, 1024] weights, about
700 FLOP a byte against the chip's 240."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "moe_experts")
    if not ms or ctx.peaks is None:
        return None
    flops = ctx.family.expert_flops_per_step
    least_s = flops(ctx.config, rec["tokens_per_step"] / rec["chips"]) \
        / ctx.peaks["bf16_flops"]
    return 100.0 * least_s / (ms / 1e3)
