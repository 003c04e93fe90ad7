"""From a trace table (``xplane.py``) to numbers: busy and idle time, the
op table, collective time and its exposed part, and idle gaps attributed
to the host spans that cover them. Pure functions over intervals in
nanoseconds, so every PR computes the same numbers the same way.
"""
import re

OPS_LINE = "XLA Ops"          # what the core executes, one op at a time
ASYNC_LINE = "Async XLA Ops"  # start-to-done intervals of asynchronous ops
MODULES_LINE = "XLA Modules"
ALIGN_EVENT = "bench.align"
# an op is a collective by its HLO opcode (``stats["op"]``) or, where the
# table has only names, by its name: XLA names an instruction after its
# opcode or after the JAX primitive it came from (an all-reduce is
# "all-reduce.3", "all-reduce-start.3" or "psum.1069")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|psum|pmax|pmin|all_gather|reduce_scatter|"
    r"ppermute|all_to_all|pbroadcast)")


def is_collective(name, stats):
    return bool(COLLECTIVE.match(stats.get("op") or "")
                or COLLECTIVE.match(name))


def device_planes(table):
    """Per-chip planes, in device order. TensorCore planes only: a chip's
    SparseCore or host-offload planes are not where the step runs."""
    planes = [p for p in table["planes"]
              if re.match(r"^/device:TPU:\d+$", p["name"])]
    return sorted(planes, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of ``union(intervals)`` not covered by ``union(holes)``."""
    out = []
    holes = union(holes)
    for a, b in union(intervals):
        cur = a
        for ha, hb in holes:
            if hb <= cur:
                continue
            if ha >= b:
                break
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def op_intervals(plane, window, want=None, line=OPS_LINE):
    """Clipped intervals of a line's events; ``want(name, stats)`` selects."""
    lo, hi = window
    return clip([(s, s + d) for name, s, d, stats in line_events(plane, line)
                 if want is None or want(name, stats)], lo, hi)


def busy_ns(plane, window):
    """Time in which at least one op ran on this chip inside ``window``."""
    return total(union(op_intervals(plane, window)))


def idle_gaps(plane, window):
    return subtract([window], op_intervals(plane, window))


def self_times(events, window):
    """{name: self ns} over one line whose events nest (a ``while`` op
    spans its body's ops): an event's self time is its duration minus its
    direct children's, so a loop and its body are not counted twice."""
    lo, hi = window
    out, stack = {}, []  # stack of [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, d, stats in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        close(a)
        if stack:
            stack[-1][2] -= b - a
        label = name
        where = stats.get("tf_op") or stats.get("source") or stats.get("shape")
        if where:
            label = "%s [%s]" % (name, str(where)[-90:])
        stack.append([label, b, b - a])
    close(float("inf"))
    return out


def top_ops(table, window, n=10):
    """[[name, seconds], ...]: the ops with most self time, mean over chips."""
    planes = device_planes(table)
    acc = {}
    for plane in planes:
        for name, ns in self_times(line_events(plane, OPS_LINE),
                                   window).items():
            acc[name] = acc.get(name, 0.0) + ns
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(planes) / 1e9] for name, ns in ranked]


def collective_ns(plane, window):
    """(ns inside collective ops, ns of those during which no other op ran
    on this chip)."""
    # an asynchronous collective is in flight from its -start to its -done:
    # that whole interval (the async line) is collective time, and what the
    # core computes meanwhile hides it
    coll = (op_intervals(plane, window, is_collective)
            + op_intervals(plane, window, is_collective, ASYNC_LINE))
    other = op_intervals(
        plane, window,
        lambda n, st: not is_collective(n, st) and not is_container(n))
    return total(union(coll)), total(subtract(coll, other))


def is_container(name):
    """Ops that only hold other ops (their body's ops are on the line too)."""
    return bool(re.match(r"^(while|conditional|call)(\.|$)", name))


def module_runs(plane, window, prefix):
    """Executions of the XLA modules whose name starts with ``prefix``
    inside the window: [(start, end), ...]."""
    lo, hi = window
    return [(s, s + d) for name, s, d, _ in line_events(plane, MODULES_LINE)
            if name.startswith(prefix) and s >= lo and s + d <= hi]


def align_offset_ns(table, host_t0_ns):
    """Trace clock minus host clock, from the annotation the benchmark
    wrapped around ``host_t0_ns`` (its own perf_counter_ns reading)."""
    for plane in table["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, s, _, _ in line["events"]:
                if name == ALIGN_EVENT:
                    return s - host_t0_ns
    return None


def attribute_gaps(gaps, spans, n=5):
    """Name each of the ``n`` longest gaps by what the host was doing.

    ``spans`` are (name, start_ns, end_ns) on the SAME clock as the gaps.
    Every instant of a gap belongs to the shortest span that covers it (a
    span's own time, not its children's) or to "no span"; the gap is named
    by the holders of at least 15 % of it, largest first, with their
    shares. Returns [[name, seconds], ...], longest gap first.
    """
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        inside = [(e - s, name, max(s, a), min(e, b))
                  for name, s, e in spans if min(e, b) > max(s, a)]
        cuts = sorted({a, b} | {x for _, _, s, e in inside for x in (s, e)})
        held = {}
        for lo, hi in zip(cuts, cuts[1:]):
            covering = [(dur, name) for dur, name, s, e in inside
                        if s <= lo and e >= hi]
            name = min(covering)[1] if covering else "no span"
            held[name] = held.get(name, 0.0) + hi - lo
        parts = [(ns, name) for name, ns in held.items()
                 if ns >= 0.15 * (b - a)]
        label = " + ".join("%s %d%%" % (name, round(100 * ns / (b - a)))
                           for ns, name in sorted(parts, reverse=True))
        out.append([label or "no span", (b - a) / 1e9])
    return out
