"""Device time per traced step under the ``optimizer`` scope: the update
and its apply."""
from benchmark.phases import phase_ms


def read(rec, ctx):
    return phase_ms(rec, "opt")
