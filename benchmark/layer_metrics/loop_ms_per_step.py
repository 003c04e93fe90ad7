"""Device time per traced step under the program's ``loop`` scope (a looped
model's passes: every block and the final norm once a pass over shared
weights), forward, backward and the recomputed forward,
all the passes: what the loop costs of ``fwd_ms_per_step`` and
``bwd_ms_per_step``, beside the head's and the exit gate's. None from a
program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "loop")
