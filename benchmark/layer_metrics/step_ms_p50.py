"""Median of the window's readings of the time a step takes
(``readers.step_times_ms``): in the per-step loop the wall time between the
ends of consecutive steps, each of which ends in a value readback; under
``metrics_every`` the time per step at which the device ran a group."""
from benchmark.readers import percentile, step_times_ms


def read(rec, ctx):
    if rec["kind"] != "train_fit":
        return None
    return percentile(step_times_ms(rec), 50)
