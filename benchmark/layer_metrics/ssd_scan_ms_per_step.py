"""Device time per traced step under the program's ``ssd_scan`` scope (the
state-space recurrence's core alone, ``ops/ssd.py``: the decays, the
chunked dual form's four products, the states carried from chunk to chunk
and ``D x``), forward, backward and the recomputed forward, every Mamba
layer: a cross-cut of ``mamba_ms_per_step``. By ``phases.py``'s rule a pass
that XLA fuses into a projection's matmul is named by the matmul and
leaves this scope. None from a program without the scope."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "ssd_scan")
