"""The decode-step program's share of its roofline: the least time the
chip could take for a mean step of this window (the larger of the
family's closed-form bytes over the HBM peak and FLOPs over the bf16
peak; at these sizes the bytes bound it) over the device time of one run
of the ``jit_local_decode`` module in the trace."""
from benchmark.readers import traced
from benchmark.trace import reduce as tr

MODULE = "jit_local_decode"  # graph_transformer.py's decode_builder


def read(rec, ctx):
    got = traced(rec)
    steps = (rec.get("engine_stats") or {}).get("steps")
    if got is None or not steps or ctx.peaks is None:
        return None
    _, window, planes = got
    runs = tr.module_runs(planes[0], window, MODULE)
    if not runs:
        return None
    step_s = sum(b - a for a, b in runs) / len(runs) / 1e9
    # a request holds a slot for cap - 1 steps; the step at cursor c reads
    # c + 1 rows of its K and V. Requests unfinished at the end are left
    # out of the rows though their steps count: the bytes are a floor
    rows = sum((r["cap"] - 1) * r["prompt_len"] + (r["cap"] - 1) * r["cap"] / 2
               for r in rec["requests"] if r["ok"])
    live = (rec["engine_stats"]["tokens"]
            - rec["engine_stats"]["prefill_admits"]) / steps
    family, config = ctx.family, ctx.config
    least = max(
        family.decode_bytes_per_step(config, rows / steps)
        / ctx.peaks["hbm_bytes_per_s"],
        family.decode_flops_per_step(config, live, rows / steps)
        / ctx.peaks["bf16_flops"])
    return 100.0 * least / step_s
