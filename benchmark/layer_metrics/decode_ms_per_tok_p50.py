"""Median over the requests due in the window of (completion - due time)
/ output tokens."""
from benchmark.readers import norm_latency_ms, percentile


def read(rec, ctx):
    if rec["kind"] != "decode_open_loop":
        return None
    return percentile(norm_latency_ms(rec), 50)
