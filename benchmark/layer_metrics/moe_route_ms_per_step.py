"""Device time per traced step under ``moe_route``, inside ``moe``: the
router matmul, softmax and top-k, the sort by expert, the row gather into
expert order and the un-sort and gated combine, forward and backward:
what dispatch and combine cost beside the expert matmuls."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "moe_route")
