"""Device time inside all-reduce / reduce-scatter / all-gather /
collective-permute / all-to-all ops per traced step, mean over chips."""
from benchmark.readers import traced
from benchmark.trace import reduce as tr


def per_step(rec, index):
    got = traced(rec)
    if got is None or rec.get("chips", 1) < 2 or not rec.get("traced_steps"):
        return None
    _, window, planes = got
    ns = [tr.collective_ns(p, window)[index] for p in planes]
    return sum(ns) / len(ns) / 1e6 / rec["traced_steps"]


def read(rec, ctx):
    return per_step(rec, 0)
