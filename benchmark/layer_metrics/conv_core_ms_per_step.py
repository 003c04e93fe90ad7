"""Device time per traced step under the program's ``conv_core`` scope
(what lies between a conv mixer's two projections: the split in thirds,
the two element-wise gates and the 3-tap depthwise causal convolution),
forward, backward and the recomputed forward, every conv layer: a
cross-cut of ``conv_ms_per_step``. It holds only the passes XLA leaves
ALONE: a gate or a shifted term fused into a projection's matmul is named
by the matmul (``phases.py``'s rule) and leaves this scope, so this reads
the core's stand-alone element-wise passes (``z = B * u`` forward, the
gates' products backward), not all the core costs; it rises when a change
breaks that fusion. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "conv_core")
