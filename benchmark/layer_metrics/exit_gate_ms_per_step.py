"""Device time per traced step under the program's ``exit_gate`` scope (a
looped model's exit gate in the loss: the gate's logits on the passes'
normed states, the exit distribution, its entropy and the weighted sum of
the passes' losses), forward and backward. Element-wise work on [T,
tokens] float32 beside one [tokens, d] x [d] product a pass: it rises when
a change makes the gate read the states a second time. None from a program
without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "exit_gate")
