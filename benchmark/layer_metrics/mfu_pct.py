"""Model-FLOP utilization in the steady state: the family's closed-form
FLOPs per token (forward + backward, recompute excluded) x tokens per step
over the median step wall time, over chips x the device's bf16 peak from
``peaks.json``. From the median step and not the window's tokens/s, so
that the profiler's start and stop inside a traced window do not count."""
from benchmark.layer_metrics.step_ms_p50 import read as step_ms_p50


def read(rec, ctx):
    step_ms = step_ms_p50(rec, ctx)
    if not step_ms or ctx.peaks is None:
        return None
    tok_s = rec["tokens_per_step"] / (step_ms / 1e3)
    return 100.0 * rec["flops_per_token"] * tok_s / (
        rec["chips"] * ctx.peaks["bf16_flops"])
