"""The conv mixers' share of their roofline: the least time the chip could
take for a step's model FLOPs of the mixers' two projections (the
family's closed form, ``conv_mix_flops_per_step``: d -> 3 d and d -> d,
2 FLOPs a weight and token, once forward and twice backward, over the
bf16 peak of ``peaks.json``) over the device time under the program's
``conv_mix`` scope (``conv_ms_per_step``), which holds the mixer WHOLE:
both projections, the gates and the 3-tap filter between them, forward,
backward and the recomputed forward. FLOPs bound it: at a width of 2,048
the projections are 0.8 d = 1,600 FLOPs a byte of what lies between them
(the chip's ridge is 240), and that element-wise work has no time of its
own to divide by: XLA fuses most of it into the projections' fusions,
which a trace names by their matmul (``phases.py``'s rule), so the share
is stated for the scope that holds the work wherever it was fused to.
The time holds the blocks' recomputed forward and the closed form does
not: three quarters is the ceiling while a conv block is recomputed. None
where the program has no such scope or the family no such closed form."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "conv_mix")
    flops = getattr(ctx.family, "conv_mix_flops_per_step", None)
    if not ms or ctx.peaks is None or flops is None:
        return None
    tokens = rec["tokens_per_step"] / rec["chips"]
    least_s = flops(ctx.config, tokens) / ctx.peaks["bf16_flops"]
    return 100.0 * least_s / (ms / 1e3)
