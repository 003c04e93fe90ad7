"""Programs that compiled or were loaded from the compile cache INSIDE the
window, any program, counted from inside: ``compile.backend_compiles`` +
``compile.cache_hits`` among the window's counters (the recorder is
cleared before the window). Each is also a ``jax.*`` span under the
``dstep.dispatch`` of its step. None where the program keeps no set-up
account (it then has no such counters) or the run recorded no telemetry."""
from benchmark import setup_account as sa


def read(rec, ctx):
    counters = rec.get("counters")
    if counters is None or sa.account() is None:
        return None
    return (counters.get("compile.backend_compiles", 0.0)
            + counters.get("compile.cache_hits", 0.0))
