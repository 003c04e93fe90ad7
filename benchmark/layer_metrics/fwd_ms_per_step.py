"""Device time per traced step in the forward pass of the loss: ops whose
scope is the loss's ``jvp(loss)`` and not a recomputation
(``benchmark/phases.py`` has the rule)."""
from benchmark.phases import phase_ms


def read(rec, ctx):
    return phase_ms(rec, "fwd")
