#!/usr/bin/env python3
"""Spread of each end-to-end metric over sets of runs, as the driver reads
it: the distance between the quartiles over the median, per set; the wider
of the sets; and how far the second set's median lies from the first's.

    python3 benchmark/tools/spread.py benchmark/records/set1_*.jsonl benchmark/records/set2_*.jsonl

Each file is one set (``runs.py``'s ``results.jsonl``). Traced runs, runs
that failed and runs beside ``--burn`` processes are left out. The first run of a cell in a set whose
``setup_s`` is more than twice the set's median is a compiling run and is
left out of ``setup_s`` only.
"""
import json
import sys

import numpy as np


def load(path):
    cells = {}
    for line in open(path):
        row = json.loads(line)
        if "what" in row:  # a records file's heading line
            continue
        if row["trace"] or row["rc"] or not row["result"] or row.get("burn"):
            continue
        for name, m in row["result"]["metrics"].items():
            cells.setdefault(row["cell"], {}).setdefault(name, []).append(
                m["value"])
    return cells


def stats(values, setup):
    v = np.asarray(values, float)
    if setup and len(v) > 2 and v[0] > 2 * np.median(v[1:]):
        v = v[1:]
    q1, med, q3 = np.percentile(v, [25, 50, 75])
    return {"n": len(v), "median": med, "q1": q1, "q3": q3,
            "spread": (q3 - q1) / med}


def main(paths):
    sets = [load(p) for p in paths]
    out = {}
    for cell in sorted({c for s in sets for c in s}):
        for metric in sorted({m for s in sets for m in s.get(cell, {})}):
            per_set = [stats(s[cell][metric], metric == "setup_s")
                       for s in sets if metric in s.get(cell, {})]
            row = {"sets": per_set,
                   "widest_spread": max(p["spread"] for p in per_set)}
            if len(per_set) > 1:
                row["second_vs_first"] = (per_set[1]["median"]
                                          / per_set[0]["median"] - 1.0)
            out.setdefault(cell, {})[metric] = row
            print("%-24s %-22s n=%s median=%s spread=%s 2nd/1st=%+.4f" % (
                cell, metric, [p["n"] for p in per_set],
                ["%.5g" % p["median"] for p in per_set],
                ["%.4f" % p["spread"] for p in per_set],
                row.get("second_vs_first", 0.0)))
    widest = {}
    for cell, metrics in out.items():
        for metric, row in metrics.items():
            widest[metric] = max(widest.get(metric, 0.0), row["widest_spread"])
    print("widest spread per metric over the cells, and five times it:")
    for metric, w in sorted(widest.items()):
        print("  %-22s %.4f  -> bound %.3f" % (metric, w, max(5 * w, 0.01)))
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
