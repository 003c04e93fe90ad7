"""Device time per traced step under the program's ``kda`` scope (a Kimi
Delta Attention mixer: projections, short convolutions, gates, the delta
rule, the gated norm, the output projection), forward, backward and
recomputed, every KDA layer: a cross-cut of ``attn_ms_per_step``. Read as
``moe_ms_per_step`` reads its scope; None where the program has no such
scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "kda")
