"""Process start to the first measured step or request."""


def read(rec, ctx):
    return rec["setup_s"]
