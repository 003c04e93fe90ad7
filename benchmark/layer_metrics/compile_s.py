"""Host clock around the first step (training) or ``DecodeEngine(...)`` +
``engine.warmup()`` (serving): lowering and compile, or the cache load."""


def read(rec, ctx):
    return rec.get("compile_s")
