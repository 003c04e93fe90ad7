"""The state-space recurrence's share of its roofline: the least time the
chip could take for a step's recurrence (the larger of the family's
closed-form FLOPs, ``ssd_scan_flops_per_step``, over the bf16 peak and of
its closed-form bytes, ``ssd_scan_bytes_per_step``, over the HBM bandwidth
of ``peaks.json``) over the device time under the program's ``ssd_scan``
scope (``ssd_scan_ms_per_step``). The time holds the blocks' recomputed
forward and the closed forms do not, and the closed forms count the
MODEL's work (inputs read and outputs written once a direction, the
causal half of a chunk's square), so the share is of that work whatever
implements the core, ``jnp`` or a kernel. At the published widths the
bytes bound it (about 150 FLOP a byte against the chip's 240). None where
the program has no such scope or the family no such closed forms."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    ms = scope_ms(rec, "ssd_scan")
    flops = getattr(ctx.family, "ssd_scan_flops_per_step", None)
    nbytes = getattr(ctx.family, "ssd_scan_bytes_per_step", None)
    if not ms or ctx.peaks is None or flops is None or nbytes is None:
        return None
    tokens = rec["tokens_per_step"] / rec["chips"]
    least_s = max(flops(ctx.config, tokens) / ctx.peaks["bf16_flops"],
                  nbytes(ctx.config, tokens) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
