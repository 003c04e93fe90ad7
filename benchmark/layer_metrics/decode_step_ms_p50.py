"""``engine.stats()["token_p50_ms"]``: the decode step's wall time as the
engine times it (dispatch to the next-token readback; admission excluded)."""


def read(rec, ctx):
    return (rec.get("engine_stats") or {}).get("token_p50_ms")
