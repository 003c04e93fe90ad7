"""How late the generator ran: time ``submit`` was called minus the time
the request was due, 95th percentile. A starved generator must not read
as a fast server."""
from benchmark.readers import percentile


def read(rec, ctx):
    if rec["kind"] != "decode_open_loop":
        return None
    return percentile([(r["submitted"] - r["due"]) * 1e3
                       for r in rec["requests"]], 95)
