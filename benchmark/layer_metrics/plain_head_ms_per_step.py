"""Device time per traced step under the program's ``plain_head`` scope (the
head that makes the full [tokens, vocab] logits, with the log-softmax and
the target pick after it (a step has this or the lean head)), forward,
backward and the recomputed forward, read as ``moe_ms_per_step`` reads its
scope. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "plain_head")
