#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: the configuration file, ``traffic/<mix>.json``,
``workloads/<cell>.json``, the driver ``drivers/<kind>.py`` of the traffic's
kind, the family ``families/<family>.py`` of the configuration, and one
reader per metric (``end_to_end/<name>.py``, ``layer_metrics/<name>.py``).
A later PR adds files and entries; it edits none.

The last line of stdout is the result, one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced). Diagnostics go to stderr or to ``--diag``.
There is no CPU path: without the cell's TPU chips the run fails and
prints no result. ``--rehearse-on-cpu`` (with a tiny ``--config-file`` and
``--traffic-set`` overrides, as ``benchmark/tests`` does) runs the same
code on the CPU backend to debug it and prints no result line either.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    sys.exit("benchmark: no %s named %r in BENCHMARK.json" % (what, name))


def load_cell(workload, config_file=None):
    """(BENCHMARK.json, the cell, its configuration, its traffic mix), each
    found by the name the one before gives."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = by_name(bench["workloads"], workload, "workload")
    config_entry = by_name(bench["configs"], entry["config"], "config")
    config = load_json(ROOT, config_file or config_entry["file"])
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")
    cell = dict(load_json(HERE, "workloads", workload + ".json"), **entry)
    return bench, cell, config, traffic


def applies(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def reader(package, name):
    return importlib.import_module("benchmark.%s.%s" % (package, name)).read


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--diag", help="write the run's diagnostics (every "
                    "metric of both lists, the checks, notes) to this file")
    ap.add_argument("--dump-trace", help="with --trace 1: write the trace "
                    "table (gzipped JSON) here, to look at or to keep as a "
                    "test fixture")
    ap.add_argument("--traffic-set", action="append", default=[],
                    metavar="KEY=JSON", help="override one traffic "
                    "parameter for a sweep; never passed by the driver")
    ap.add_argument("--config-file", help="another configuration file "
                    "(the tiny ones of benchmark/tests)")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run on the CPU backend to debug the harness; "
                    "proves nothing about the chip, prints no result line")
    return ap.parse_args(argv)


def device_gate(chips, rehearse):
    """The machine as JAX reports it; refuse anything that is not the
    cell's TPU chips in the benchmark's own table of peaks."""
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}
    want = "cpu" if rehearse else "tpu"
    if dev.platform != want:
        sys.exit("benchmark: JAX's default platform is %r, need %r"
                 % (dev.platform, want))
    if info["count"] != chips:
        sys.exit("benchmark: the cell asks for %d chip(s), JAX sees %d"
                 % (chips, info["count"]))
    if rehearse:
        return info, None  # no peaks: the CPU has no device metric
    table = load_json(HERE, "peaks.json")
    if dev.device_kind not in table:
        sys.exit("benchmark: device kind %r is not in benchmark/peaks.json"
                 % dev.device_kind)
    return info, table[dev.device_kind]


def main(argv=None):
    args = parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload, args.config_file)
    for item in args.traffic_set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    # the program's scratch files (strategy JSON, logs) default to a fixed
    # /tmp path; keep them under this run's own TMPDIR
    os.environ.setdefault("ADT_WORKING_DIR", os.path.join(
        tempfile.gettempdir(), "autodist_tpu"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % cell["chips"])
        print("CPU REHEARSAL: proves nothing about the chip, prints no "
              "result line", file=sys.stderr)
    sys.path[0] = ROOT  # not benchmark/: its folders must not shadow modules
    try:
        import jax
        import autodist_tpu  # noqa: F401
        from autodist_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit("benchmark: cannot import the system under test from %s "
                 "(%s)" % (ROOT, e))
    device, peaks = device_gate(cell["chips"], args.rehearse_on_cpu)
    cache_dir = enable_compile_cache()
    # cache every program, also those that compile in under a second: a
    # warm run then compiles nothing and its set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    marks = [("imports_and_gate", time.perf_counter() - T_START)]
    ctx = types.SimpleNamespace(
        mark=lambda name: marks.append((name, time.perf_counter() - T_START)),
        t_start=T_START, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), cell=cell, config=config, traffic=traffic,
        peaks=peaks, root=ROOT,
        family=importlib.import_module(
            "benchmark.families." + config["family"]))
    driver = importlib.import_module("benchmark.drivers." + traffic["kind"])
    rec = driver.run(ctx)

    values = {}
    for package, key in (("end_to_end", "end_to_end"),
                         ("layer_metrics", "per_layer")):
        for m in bench[key]:
            if applies(m, args.workload):
                v = reader(package, m["name"])(rec, ctx)
                if v is not None:
                    values[m["name"]] = {"value": float(v), "unit": m["unit"],
                                         "list": key}
    listed = "per_layer" if args.trace else "end_to_end"
    metrics = {k: {"value": v["value"], "unit": v["unit"]}
               for k, v in values.items() if v["list"] == listed}
    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    result = {"correct": rec["correct"], "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}

    diag = {"workload": args.workload, "seed": args.seed, "seconds": seconds,
            "trace": args.trace, "cache_dir": cache_dir, "values": values,
            "traffic": traffic, "total_s": None,
            "setup_marks_s": marks}  # seconds since process start
    if args.trace:
        from benchmark import readers
        from benchmark.trace import reduce as tr
        tracer = rec.get("tracer")
        if args.dump_trace and tracer is not None and tracer.table:
            import gzip
            with gzip.open(args.dump_trace, "wt") as f:
                json.dump(dict(tracer.table, window_ns=tracer.window_ns,
                               offset_ns=tracer.offset_ns,
                               spans=rec.get("spans")), f)
        busy = readers.device_busy(rec)
        got = readers.traced(rec)
        if busy is not None:
            device["busy_s"], device["window_s"] = busy
            table, window, planes = got
            off = rec["tracer"].offset_ns
            spans = [(n, s + off, e + off) for n, s, e, _ in rec["spans"]]
            result["breakdown"] = {
                "device_ops": tr.top_ops(table, window, 10),
                "idle_gaps": tr.attribute_gaps(
                    tr.idle_gaps(planes[0], window), spans, 5)}
            diag["align_error_us"] = rec["tracer"].align_error_us
            diag["breakdown"] = result["breakdown"]
            print("benchmark: spans and device trace aligned to within "
                  "%.1f us" % rec["tracer"].align_error_us, file=sys.stderr)
        elif not args.rehearse_on_cpu:
            sys.exit("benchmark: the trace holds no device op")
    skip = ("tracer", "spans", "step_ends")
    diag.update({k: v for k, v in rec.items() if k not in skip})
    diag["total_s"] = time.perf_counter() - T_START
    text = json.dumps(diag, sort_keys=True, default=str)
    if args.diag:
        os.makedirs(os.path.dirname(os.path.abspath(args.diag)), exist_ok=True)
        with open(args.diag, "w") as f:
            f.write(text + "\n")
    else:
        print("benchmark diag: " + text, file=sys.stderr)
    sys.stdout.flush()
    if args.rehearse_on_cpu:
        print("benchmark rehearsal: " + json.dumps(result), file=sys.stderr)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
