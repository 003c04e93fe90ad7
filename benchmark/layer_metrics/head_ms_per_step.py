"""Device time per traced step under the lean head's scopes
(``lean_head``, ``lean_head_bwd``), forward and backward: a cross-cut of
``fwd_ms_per_step`` and ``bwd_ms_per_step``, not a part beside them."""
from benchmark.phases import phase_ms


def read(rec, ctx):
    return phase_ms(rec, "head")
