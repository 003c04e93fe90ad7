"""Helpers the metric readers share (``end_to_end/`` and ``layer_metrics/``).
A reader is ``read(rec, ctx) -> number or None``; None (nothing to read)
leaves the metric out of the line."""
import numpy as np

from benchmark.trace import reduce as tr


def percentile(values, q):
    """Unrounded percentile, None on no samples."""
    values = [v for v in values if v is not None]
    return float(np.percentile(values, q)) if values else None


def traced(rec):
    """(table, window on the trace clock, device planes) or None."""
    tracer = rec.get("tracer")
    if tracer is None or tracer.table is None:
        return None
    window = tracer.window_on_trace_clock()
    planes = tr.device_planes(tracer.table)
    if window is None or not planes:
        return None
    return tracer.table, window, planes


def device_busy(rec):
    """(busy seconds, mean over chips; window seconds) or None."""
    got = traced(rec)
    if got is None:
        return None
    _, window, planes = got
    busy = [tr.busy_ns(p, window) for p in planes]
    return sum(busy) / len(busy) / 1e9, (window[1] - window[0]) / 1e9


def spans_named(rec, *names):
    return [s for s in rec.get("spans") or () if s[0] in names]


def step_intervals_ms(rec):
    """Wall time between the ends of consecutive steps of the window, as
    the ``fit`` callback saw them, but the first (no start stamp) and those
    the profiler's start/stop fell into. ``fit`` calls back when a step's
    values are on the host. In the per-step loop that is before the next
    step is dispatched, so an interval is the device's time plus the
    host's between two steps. Under ``metrics_every=n`` it is while the
    group's later steps are queued on the device, so an interval is the
    device's time alone, but for one in n, which holds the host's visit."""
    ends, skip = rec["step_ends"], set(rec["profiler_steps"])
    return [(ends[k] - ends[k - 1]) * 1e3 for k in range(1, len(ends))
            if k not in skip]


def back_to_back_ms(ends):
    """Time per step of steps that were queued on the device together, from
    the times at which the host saw each of them end.

    The host sees an end late or in time, never early. With busy
    neighbours on its cores it saw one 50 ms late and the next eight 200 ms
    apart until it had caught up, on a device that ended a step every
    206.7 ms throughout (``tests/data/v5e_step_ends_busy_host.json``), so
    most intervals were short and their median read 1-2 % fast. The ends
    lie on or above a straight line whose slope is the device's time per
    step; that slope is the longest edge of their lower convex hull."""
    hull = []
    for p in enumerate(ends):
        while len(hull) > 1 and (
                (hull[-1][0] - hull[-2][0]) * (p[1] - hull[-2][1])
                <= (hull[-1][1] - hull[-2][1]) * (p[0] - hull[-2][0])):
            hull.pop()
        hull.append(p)
    (i0, t0), (i1, t1) = max(zip(hull, hull[1:]),
                             key=lambda edge: edge[1][0] - edge[0][0])
    return (t1 - t0) / (i1 - i0) * 1e3


def step_times_ms(rec):
    """The window's readings of the time a step takes. In the per-step
    loop every step waits for the host's visit, and each interval between
    two ends is a reading. Under ``metrics_every=n`` a reading is the time
    per step at which the device ran a whole group of n queued steps
    (``back_to_back_ms``); the host's visit between two groups, once in n
    steps, is left out, and with it the host's lateness."""
    every = rec.get("fit", {}).get("metrics_every", 1)
    if every == 1:
        return step_intervals_ms(rec)
    ends = rec["step_ends"]
    return [back_to_back_ms(ends[g:g + every])
            for g in range(0, len(ends) - every + 1, every)]


def norm_latency_ms(rec):
    """Per request due in the window (the ramp's are left out) (completion
    - due) / output tokens, in ms/token. A request that did not finish is
    charged up to the end of the grace."""
    out = []
    for r in rec["requests"]:
        if not r.get("in_window", True):
            continue
        done = r["done"] if r["done"] is not None else \
            rec["w0"] + rec["window_s"] + rec["drained_s"]
        out.append((done - r["due"]) / r["cap"] * 1e3)
    return out
