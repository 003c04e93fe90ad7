"""Device time per traced step under the program's ``attn_gate`` scope (a
GATED softmax attention's output gate, inside ``attention`` and outside
``attn_core``: the gate projection of the layer's normed input to every
head's features, its sigmoid and the product with the core's output, forward,
backward and a recomputed forward, every gated layer): a cross-cut of
``attn_ms_per_step`` beside the cores'. A fusion is named by its hero
(``phases.py``), so where XLA fuses the sigmoid and the product into the
output projection's operand that part reads under ``attention`` alone and
this is the gate's three matmuls and whatever element-wise passes stand on
their own. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "attn_gate")
