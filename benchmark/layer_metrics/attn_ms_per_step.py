"""Device time per traced step under the program's ``attention`` scope
(``models/layers.py:TransformerBlock``: the q/k/v projections, QK-norm,
RoPE, the attention core, the output projection), forward and backward:
a cross-cut of ``fwd_ms_per_step`` and ``bwd_ms_per_step`` like
``head_ms_per_step``. Read as ``moe_ms_per_step`` reads its scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "attention")
