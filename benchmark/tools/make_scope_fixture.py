#!/usr/bin/env python3
"""Keep the scope map of a recorded step as a test fixture.

    python3 benchmark/tools/make_scope_fixture.py <trace.json.gz> <module.hlo> <out.json.gz>

``<module.hlo>`` is ``compiled.as_text()`` of the step program
the trace was recorded from (instruction names do not depend on the
tree's metadata, so a device-less compile of today's tree for a described
v5e names the instructions a trace of an earlier tree holds). The fixture
keeps the map's entries for the instructions in the trace only, strings
once, and what ``phases.sum_phases`` gives on the pair.
"""
import gzip
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from autodist_tpu.telemetry.scopes import parse_scope_map  # noqa: E402
from benchmark import phases  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402


def load(path):
    """(the map, its module name, what sum_phases gave when it was made)."""
    with gzip.open(path, "rt") as f:
        fx = json.load(f)
    strings = fx["strings"]
    return ({name: [strings[i] for i in ids]
             for name, ids in fx["map"].items()}, fx["module"],
            fx["expected"])


def main(trace_path, hlo_path, dst):
    with gzip.open(trace_path, "rt") as f:
        table = json.load(f)
    with open(hlo_path) as f:
        full = parse_scope_map(f.read())
    names = {e[0] for p in tr.device_planes(table)
             for e in tr.line_events(p, tr.OPS_LINE)}
    strings, index, kept = [], {}, {}
    for name in sorted(names & set(full)):
        ids = []
        for s in full[name]:
            if s not in index:
                index[s] = len(strings)
                strings.append(s)
            ids.append(index[s])
        kept[name] = ids
    scope_map = {n: [strings[i] for i in ids] for n, ids in kept.items()}
    window = tuple(table["expected"]["window"])
    out = {"comment": "scope map of the step in %s, from a device-less "
           "compile of the same program for a described v5e:2x2"
           % os.path.basename(trace_path),
           "module": phases.STEP_MODULE, "strings": strings, "map": kept,
           "missing_from_map": sorted(names - set(full)),
           "expected": phases.sum_phases(table, window, scope_map)}
    with gzip.open(dst, "wt") as f:
        json.dump(out, f)
    print(json.dumps(out["expected"], indent=1), len(out["missing_from_map"]),
          os.path.getsize(dst))


if __name__ == "__main__":
    main(*sys.argv[1:])
