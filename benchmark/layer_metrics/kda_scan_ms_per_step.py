"""Device time per traced step under ``kda_scan``, inside ``kda``: the
chunked gated delta rule alone (``autodist_tpu/ops/kda.py``), forward,
backward and recomputed: what a kernel would replace."""
from benchmark.layer_metrics.moe_ms_per_step import scope_ms


def read(rec, ctx):
    return scope_ms(rec, "kda_scan")
