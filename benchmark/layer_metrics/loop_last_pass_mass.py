"""The share of the exit distribution's mass a looped model's LAST pass
holds, batch mean, over the steps of the window that were read back: the
device counter ``loop.exit_mass_<T>`` (T the configuration's
``total_ut_steps``) over the sum of all the passes' masses, which is the
number of steps read (``loop_exit_entropy.py:steps_read``). 1 / T where
the gate is uniform; near 1 a gate that has learned to exit nowhere early,
near 0 one that never runs the loop out. None where the program counts no
such thing.

A diagnostic of the training dynamics like ``loop_exit_entropy`` (see
there): all the passes always run, so it moves no throughput until early
exit or stage II exists; on uniform random ids it reads seed noise
(0.0005-0.18 over PR 44's runs), and no direction is to be read into it
from one PR to the next."""
from benchmark.layer_metrics.loop_exit_entropy import steps_read


def read(rec, ctx):
    counters = rec.get("counters") or {}
    steps = steps_read(counters)
    last = counters.get("loop.exit_mass_%d" % ctx.config.get(
        "total_ut_steps", 0))
    if not steps or last is None:
        return None
    return last / steps
