"""Device time per traced step under the program's ``blocks`` scope and
under NONE of ``attention``, ``moe``, ``dense_ffn``: the norms, residual
adds, dropout and casts that XLA left alone (what it fused into a matmul
is the matmul's, as with ``conv_core``), a looped model's final norm once
a pass. Self time of the step's ops, a fusion named by
``moe_ms_per_step.in_scope``'s rule, mean over chips, summed op by op and
not by subtraction, so that the tiling is a check: in the run's
diagnostics (``scope_ms_per_step``) ``attention`` + ``moe`` + ``dense_ffn``
+ ``block_rest`` - ``named_outside_blocks`` = ``blocks``, where
``named_outside_blocks`` is the time of ops that carry one of the three
names and NOT ``blocks`` (0 but in a looped model, whose loop-invariant
weight casts and rotary tables JAX hoists out of the scanned body with
the body's own names alone). The rest's ten largest ops lie beside them
(``block_rest_ops``, ms a step on the first chip). None where the
program's map holds no ``attn_core`` (a program from before the
``dense_ffn`` scope: its rest would silently hold the feed-forwards) or
the run no device trace."""
from benchmark import phases
from benchmark.layer_metrics.moe_ms_per_step import in_scope, scope_ms
from benchmark.readers import traced
from benchmark.trace import reduce as tr

WHOLE = "blocks"
NAMED = ("attention", "moe", "dense_ffn")
REST = "block_rest"
OUTSIDE = "named_outside_blocks"


def place(op_names):
    """REST, OUTSIDE or None: where an op's time goes besides the sums
    ``scope_ms`` makes of ``blocks`` and the three named parts."""
    named = any(in_scope(op_names, scope) for scope in NAMED)
    if in_scope(op_names, WHOLE):
        return None if named else REST
    return OUTSIDE if named else None


def read(rec, ctx):
    kept = rec.setdefault("scope_ms_per_step", {})
    if REST in kept:
        return kept[REST]
    got = traced(rec)
    scope_map = phases.program_map(phases.STEP_MODULE) \
        if got is not None and rec.get("kind") == "train_fit" else None
    out = None
    if scope_map and any("attn_core" in name.split("/")
                         for names in scope_map.values() for name in names):
        table, window, _ = got
        per_plane = []
        for plane in tr.device_planes(table):
            runs = tr.module_runs(plane, window, phases.STEP_MODULE)
            if not runs:
                continue
            inside = [e for e in tr.line_events(plane, tr.OPS_LINE)
                      if any(a <= e[1] and e[1] + e[2] <= b for a, b in runs)]
            ms = {REST: {}, OUTSIDE: {}}
            for label, ns in tr.self_times(inside, window).items():
                where = place(scope_map.get(label.split(" [", 1)[0]) or ())
                if where is not None:
                    ms[where][label] = ns / len(runs) / 1e6
            per_plane.append(ms)
        if per_plane:
            out, outside = (sum(sum(ms[key].values()) for ms in per_plane)
                            / len(per_plane) for key in (REST, OUTSIDE))
            kept[OUTSIDE] = outside
            rec["block_rest_ops"] = sorted(
                per_plane[0][REST].items(), key=lambda kv: -kv[1])[:10]
            for scope in (WHOLE,) + NAMED:  # the tiling, for the diagnostics
                scope_ms(rec, scope)
    kept[REST] = out
    return out
