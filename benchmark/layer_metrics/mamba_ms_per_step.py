"""Device time per traced step under the program's ``mamba`` scope (a
Mamba-2 mixer whole: the input projection to ``[z | xBC | dt]``, the
4-tap causal filter with its bias and SiLU, the state-space recurrence,
the gated grouped norm, the output projection), forward, backward and the
recomputed forward, every Mamba layer: a cross-cut of
``attn_ms_per_step``, which holds every mixer. None from a program without
the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "mamba")
