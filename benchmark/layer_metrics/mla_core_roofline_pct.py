"""The latent layers' attention cores' share of their roofline: the least
time the chip could take for a step's CAUSAL model FLOPs of the cores (the
family's closed form, ``mla_attn_flops_per_step``: Q K^T over nope + pe
features and P V over the values, half the S x S square, once forward and
twice backward, over the bf16 peak of ``peaks.json``) over the device time
under the program's ``mla_core`` scope. FLOPs bound it (at seq 8,192 a
head's scores are 8,192 x 8,192 against 8,192 x 192 inputs). The time
holds the kernels' own recomputation of the scores in their two backward
passes and the recomputed blocks' second forward pass, and the closed form
does not: by that count alone (4 forward-sized passes of scores counted
where 7 run: forward, the recomputed forward, and 2.5 each in dq and
dk/dv) the share is of the MODEL's work and cannot pass about 55 %; the
same kernels at 32 heads stand near 30 % in ``kimi_linear_train_1chip``
(35.4 ms for 2.06 TFLOP; PR 30)."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "mla_core")
    flops = getattr(ctx.family, "mla_attn_flops_per_step", None)
    if not ms or ctx.peaks is None or flops is None:
        return None
    rows = rec["tokens_per_step"] / rec["chips"] / ctx.traffic["seq"]
    least_s = flops(ctx.config, rows, ctx.traffic["seq"]) \
        / ctx.peaks["bf16_flops"]
    return 100.0 * least_s / (ms / 1e3)
