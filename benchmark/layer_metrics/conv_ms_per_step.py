"""Device time per traced step under the program's ``conv_mix`` scope (a
gated short convolution mixer whole: the input projection to three times
the width, the two gates around the depthwise causal convolution, the
output projection), forward, backward and the recomputed forward, every
conv layer: a cross-cut of ``attn_ms_per_step``, which holds every mixer.
None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "conv_mix")
