"""Tokens of one optimizer step, all chips together, over the median of
the window's readings of the time a step takes (``readers.step_times_ms``:
130-150 intervals between the ends of consecutive steps in the per-step
loop, one reading per group of queued steps under ``metrics_every``).

The median of readings and not the window's tokens over its wall time: a
one-chip machine shares its host's cores, and with the per-step loop the
window's total moved by 5.7 % between runs of the same code (the driver's
first check of PR 22).
"""
from benchmark.readers import percentile, step_times_ms


def read(rec, ctx):
    if rec["kind"] != "train_fit":
        return None
    step_ms = percentile(step_times_ms(rec), 50)
    if not step_ms:
        return None
    return rec["tokens_per_step"] / (step_ms / 1e3)
