"""The delta rule's core's share of its roofline: the least time the chip
could take for a step's core (the larger of the family's closed-form
FLOPs, ``kda_scan_flops_per_step``, over the bf16 peak and of its
closed-form bytes, ``kda_scan_bytes_per_step``, over the HBM bandwidth of
``peaks.json``) over the device time under the program's ``kda_scan``
scope. The time holds the blocks' recomputation in the backward pass and
the closed forms do not, so the share is of the model's work, whether the
core is a kernel or ``lax``. At the published widths the bytes bound it
(about 110 FLOP a byte against the chip's 240)."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "kda_scan")
    if not ms or ctx.peaks is None:
        return None
    tokens = rec["tokens_per_step"] / rec["chips"]
    least_s = max(
        ctx.family.kda_scan_flops_per_step(ctx.config, tokens)
        / ctx.peaks["bf16_flops"],
        ctx.family.kda_scan_bytes_per_step(ctx.config, tokens)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
