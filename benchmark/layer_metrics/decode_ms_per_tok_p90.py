"""90th percentile over the requests due in the window of (completion -
due time) / output tokens. A judged tail wants ten samples beyond it: a
30 s window at 2 requests/s holds 60 requests, six beyond the p90."""
from benchmark.readers import norm_latency_ms, percentile


def read(rec, ctx):
    if rec["kind"] != "decode_open_loop":
        return None
    return percentile(norm_latency_ms(rec), 90)
