"""Host clock around ``AutoDist(...).build(...)`` + ``runner.init``."""


def read(rec, ctx):
    return rec.get("build_s")
