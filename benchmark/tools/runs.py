#!/usr/bin/env python3
"""Run a plan of cells one after another, each in a process of its own, and
keep every result line: how this PR's sweeps and agreement runs were made.

    python3 benchmark/tools/runs.py --out chiprun_out/set1 --seconds 30 \
        lm1b_train_1chip:11:0 lm1b_train_1chip:12:1 \
        'lm1b_train_1chip:5:0:batch_per_chip=32'

A plan item is ``cell:seed:trace[:key=json[,key=json...]]``; the trailing
pairs go to ``run.py --traffic-set`` (a sweep). This process never touches
JAX, so each child gets the chip. ``results.jsonl`` in ``--out`` gets one
line per run (the child's result line plus its exit code and wall time),
``<n>_<cell>.diag.json`` the diagnostics, ``<n>_<cell>.err`` the end of
its stderr.

``--burn N --burn-after S`` starts N busy processes S seconds into every
run's measured window (the driver's "benchmark: window starts" line on
stderr) and stops them with the run: a noisy neighbour on the host's
cores, to see in one run's diagnostics what a metric makes of one. The
result rows carry ``"burn": N``; such a run is never a measurement.
"""
import argparse
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("plan", nargs="+")
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--dump-trace", action="store_true")
    ap.add_argument("--cwd", default=ROOT, help="checkout to run from")
    ap.add_argument("--burn", type=int, default=0,
                    help="busy processes to start inside every run's window")
    ap.add_argument("--burn-after", type=float, default=0.0,
                    help="seconds into the window at which they start")
    ap.add_argument("--rehearse", action="store_true",
                    help="the plan on the CPU at the tests' tiny size")
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    for n, item in enumerate(args.plan):
        cell, seed, trace, *rest = item.split(":", 3)
        label = "%02d_%s" % (n, cell)
        cmd = [sys.executable, "benchmark/run.py", "--workload", cell,
               "--seed", seed, "--trace", trace,
               "--diag", os.path.join(out, label + ".diag.json")]
        if args.seconds is not None:
            cmd += ["--seconds", str(args.seconds)]
        if args.rehearse:
            cmd += ["--rehearse-on-cpu", "--config-file",
                    "benchmark/tests/configs/lm_tiny.json"]
        if args.dump_trace and trace == "1":
            cmd += ["--dump-trace", os.path.join(out, label + ".trace.json.gz")]
        for pair in (rest[0].split(",") if rest else []):
            cmd += ["--traffic-set", pair]
        t0 = time.time()
        proc = subprocess.Popen(cmd, cwd=args.cwd, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)
        burners, timers, err = [], [], []

        def burn():
            burners.extend(
                subprocess.Popen([sys.executable, "-c", "while 1: pass"])
                for _ in range(args.burn))

        def watch_stderr():
            for line in proc.stderr:
                err.append(line)
                if args.burn and not timers and \
                        line.startswith("benchmark: window starts"):
                    timers.append(threading.Timer(args.burn_after, burn))
                    timers[0].start()

        watcher = threading.Thread(target=watch_stderr)
        watcher.start()
        try:
            lines = proc.stdout.read().strip().splitlines()
            proc.wait()
            watcher.join()
        finally:
            for t in timers:
                t.cancel()
                t.join()
            for b in burners:
                b.kill()
            for b in burners:
                b.wait()
        wall = time.time() - t0
        stderr = "".join(err)
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = {"unparsed": lines[-1][:500]}
        with open(os.path.join(out, label + ".err"), "w") as f:
            f.write(stderr[-20000:])
        row = {"n": n, "cell": cell, "seed": int(seed), "trace": int(trace),
               "traffic_set": rest[0] if rest else None,
               "seconds": args.seconds, "burn": args.burn,
               "rc": proc.returncode,
               "wall_s": round(wall, 2), "result": result}
        with open(os.path.join(out, "results.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row)[:1500], flush=True)
        if proc.returncode:
            print(stderr[-3000:], flush=True)


if __name__ == "__main__":
    main()
