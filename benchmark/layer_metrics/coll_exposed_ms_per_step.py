"""The part of the collective ops' time during which no other op ran on
that chip, per traced step, mean over chips: what the exchange costs."""
from benchmark.layer_metrics.coll_ms_per_step import per_step


def read(rec, ctx):
    return per_step(rec, 1)
