"""Device time per traced step in the backward pass of the loss: ops under
``transpose(jvp(loss))``, recomputed forward ops included (their part is
``remat`` in the diagnostics' ``phase_ms_per_step``)."""
from benchmark.phases import phase_ms


def read(rec, ctx):
    return phase_ms(rec, "bwd")
