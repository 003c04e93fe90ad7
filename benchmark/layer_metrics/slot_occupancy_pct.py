"""Tokens produced by decode steps over steps x slots, from
``engine.stats()`` (``tokens`` less the first tokens the prefills emit)."""


def read(rec, ctx):
    st = rec.get("engine_stats")
    if not st or not st["steps"]:
        return None
    return 100.0 * (st["tokens"] - st["prefill_admits"]) / (
        st["steps"] * st["slots"])
