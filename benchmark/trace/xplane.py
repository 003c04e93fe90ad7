"""Read a ``jax.profiler`` trace (``*.xplane.pb``) into a plain table.

The table is JSON-able, so a recorded one can be kept as a test fixture
and every reduction in ``reduce.py`` runs on it without a profiler:

  {"planes": [{"name": "/device:TPU:0", "lines": [
      {"name": "XLA Ops", "events": [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}

Times are nanoseconds on the trace's own clock (relative to its start).
"""
import glob
import os
import re

KEPT_STATS = ("tf_op", "source", "hlo_category", "program_id")
# a TPU op event is named by its whole HLO instruction,
# "%fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(...), kind=kLoop, calls=...":
# the table keeps the instruction's name, its result type and its opcode
HLO_TEXT = re.compile(
    r"^%?(?P<name>[^\s=]+) = (?P<shape>\(.*\)|[^\s{(]+)\S* (?P<op>[a-z][\w\-]*)\(")


def short_name(name, stats):
    m = HLO_TEXT.match(name)
    if m:
        shape = m.group("shape")
        stats["shape"] = "tuple" if shape.startswith("(") else shape
        stats["op"] = m.group("op")  # the opcode: an all-reduce may be named psum.7
        return m.group("name")
    return name.lstrip("%")

# host lines hold one event per Python/C++ call when the tracers are on;
# only the benchmark's own annotations are needed from them
HOST_EVENT_PREFIX = "bench."


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes, stat_keys = [], set()
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_EVENT_PREFIX):
                    continue
                stats = {}
                if device:
                    for key, value in ev.stats:
                        stat_keys.add(key)
                        if key in KEPT_STATS:
                            stats[key] = value
                name = short_name(ev.name, stats) if device else ev.name
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "device_stat_keys": sorted(stat_keys)}
