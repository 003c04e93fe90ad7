"""Compiled specializations added inside the window (``_step_fn._cache_size()``
after minus before; ``recompiles_after_warmup()`` for the engine)."""


def read(rec, ctx):
    return rec.get("recompiles")
