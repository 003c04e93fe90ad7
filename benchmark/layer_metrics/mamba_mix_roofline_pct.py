"""The Mamba mixers' share of their roofline: the least time the chip
could take for a step's model FLOPs of the mixers' two projections (the
family's closed form, ``mamba_proj_flops_per_step``: d -> [z | xBC | dt]
and inner -> d, 2 FLOPs a weight and token, once forward and twice
backward, over the bf16 peak of ``peaks.json``) over the device time under
the program's ``mamba`` scope (``mamba_ms_per_step``), which holds the
mixer WHOLE: both projections, the filter, the recurrence, the gate and
the grouped norm, forward, backward and the recomputed forward. So the
share says how much of the mixer's time its matmuls could not be blamed
for: the recurrence's own share is ``ssd_scan_roofline_pct``. The time
holds the blocks' recomputed forward and the closed form does not: three
quarters is the ceiling while a Mamba block is recomputed. None where the
program has no such scope or the family no such closed form."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "mamba")
    flops = getattr(ctx.family, "mamba_proj_flops_per_step", None)
    if not ms or ctx.peaks is None or flops is None:
        return None
    tokens = rec["tokens_per_step"] / rec["chips"]
    least_s = flops(ctx.config, tokens) / ctx.peaks["bf16_flops"]
    return 100.0 * least_s / (ms / 1e3)
