"""Device time per traced step under the program's ``moe`` scope (the
routed feed-forward: routing, dispatch, the grouped expert matmuls, the
combine, the router losses), forward and backward: a cross-cut of
``fwd_ms_per_step`` and ``bwd_ms_per_step`` like ``head_ms_per_step``.

``scope_ms`` is what the three moe readers share: self time of the step
module's ops whose ``op_name`` path holds a scope, per run, mean over
chips, by ``phases.py``'s rule for naming a fusion (its own ``op_name``
where that has a phase, else the majority of its members'). None where
the program has no such scope (a checkout from before it) or the run no
device trace.
"""
from benchmark import phases
from benchmark.readers import traced
from benchmark.trace import reduce as tr


def in_scope(op_names, scope):
    if not op_names:
        return False
    own = op_names[0]
    if phases.tag(own)[0] is not None or len(op_names) == 1:
        return scope in own.split("/")
    votes = [scope in name.split("/") for name in op_names[1:]
             if phases.tag(name)[0] is not None]
    return 2 * sum(votes) > len(votes)


def scope_ms(rec, scope):
    key = "scope_ms_per_step"
    if scope in rec.get(key, {}):
        return rec[key][scope]
    got = traced(rec)
    scope_map = phases.program_map(phases.STEP_MODULE) \
        if got is not None and rec.get("kind") == "train_fit" else None
    out = None
    if scope_map and any(scope in name.split("/")
                         for names in scope_map.values() for name in names):
        table, window, _ = got
        per_plane = []
        for plane in tr.device_planes(table):
            runs = tr.module_runs(plane, window, phases.STEP_MODULE)
            if not runs:
                continue
            inside = [e for e in tr.line_events(plane, tr.OPS_LINE)
                      if any(a <= e[1] and e[1] + e[2] <= b for a, b in runs)]
            ns = sum(t for label, t in tr.self_times(inside, window).items()
                     if in_scope(scope_map.get(label.split(" [", 1)[0]) or (),
                                 scope))
            per_plane.append(ns / len(runs))
        if per_plane:
            out = sum(per_plane) / len(per_plane) / 1e6
    rec.setdefault(key, {})[scope] = out  # kept in the run's diagnostics
    return out


def read(rec, ctx):
    return scope_ms(rec, "moe")
