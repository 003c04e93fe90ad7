"""Median gap from the end of one ``serve.decode_step`` span to the start
of the next when a ``serve.prefill`` span lies between AND the next step
has more live slots than that admission brought in: how long every
sequence already in flight waits through one admission (an engine that
was idle and woke for a request is not a stall)."""
from benchmark.readers import percentile, spans_named


def read(rec, ctx):
    steps = sorted(spans_named(rec, "serve.decode_step"), key=lambda s: s[1])
    prefills = sorted(spans_named(rec, "serve.prefill"), key=lambda s: s[1])
    if not steps or not prefills:
        return None
    gaps, j = [], 0
    for prev, nxt in zip(steps, steps[1:]):
        while j < len(prefills) and prefills[j][1] < prev[2]:
            j += 1
        admitted, k = 0, j
        while k < len(prefills) and prefills[k][1] < nxt[1]:
            admitted += prefills[k][3].get("n", 0)
            k += 1
        if k > j and nxt[3].get("live", 0) > admitted:
            gaps.append((nxt[1] - prev[2]) / 1e6)
    return percentile(gaps, 50)
