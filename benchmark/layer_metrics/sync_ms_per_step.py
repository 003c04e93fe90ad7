"""Device time per traced step under the ``grad_sync`` scope, collective
or not: less ``coll_ms_per_step`` it is the packing, casting and scaling
around the exchange."""
from benchmark.phases import phase_ms


def read(rec, ctx):
    if rec.get("chips", 1) < 2:
        return None
    return phase_ms(rec, "sync")
