#!/usr/bin/env python3
"""Cut one step out of a trace table dumped by ``run.py --dump-trace`` and
keep it, with the numbers the reduction gives on it, as a test fixture.

    python3 benchmark/tools/make_fixture.py <dump.trace.json.gz> <out.json.gz> [planes]

The window runs from the start of the second module execution on chip 0 to
the start of the third: one whole step period, its idle gap included.
"""
import gzip
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmark.trace import reduce as tr  # noqa: E402
from benchmark.trace import xplane  # noqa: E402


def main(src, dst, n_planes="4"):
    with gzip.open(src, "rt") as f:
        table = json.load(f)
    planes = tr.device_planes(table)[:int(n_planes)]
    mods = tr.line_events(planes[0], tr.MODULES_LINE)
    window = (mods[1][1], mods[2][1])
    kept = []
    for plane in planes:
        lines = []
        for line in plane["lines"]:
            events = []
            for name, s, d, stats in line["events"]:
                if s + d > window[0] and s < window[1]:
                    stats = dict(stats)
                    events.append([xplane.short_name(name, stats), s, d,
                                   stats])
            lines.append({"name": line["name"], "events": events})
        kept.append({"name": plane["name"], "lines": lines})
    out = {"comment": "one step of %s, recorded on the chip; window = "
           "[start of module run 2, start of module run 3)"
           % os.path.basename(src), "planes": kept}
    busy = [tr.busy_ns(p, window) for p in kept]
    coll = [tr.collective_ns(p, window) for p in kept]
    out["expected"] = {
        "chips": len(kept), "window": list(window),
        "module_ns": mods[1][2],
        "busy_ns_mean": sum(busy) / len(busy),
        "coll_ns_mean": sum(c[0] for c in coll) / len(coll),
        "coll_exposed_ns_mean": sum(c[1] for c in coll) / len(coll),
        "top3_ops": [n for n, _ in tr.top_ops(out, window, 3)]}
    with gzip.open(dst, "wt") as f:
        json.dump(out, f)
    print(json.dumps(out["expected"], indent=1), os.path.getsize(dst))


if __name__ == "__main__":
    main(*sys.argv[1:])
