"""Device time per traced step under the program's ``moe_shared`` scope (the
shared expert(s) inside ``moe``, a dense feed-forward every token passes: a
cross-cut of ``moe_ms_per_step``), forward, backward and the recomputed
forward, read as ``moe_ms_per_step`` reads its scope. None from a program
without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "moe_shared")
