"""Training driver: the loop ``docs/performance.md`` documents,

    runner.fit(DevicePrefetcher(<host batches>, runner, depth=2), **fit)

through ``AutoDist(strategy_builder=...).build`` and ``runner.init`` over
every chip of the machine. ``fit`` is the traffic mix's own dict of
``Runner.fit`` knobs, empty where it names none: ``fit``'s defaults
(``fuse_steps=1``, ``metrics_every=1``) are the per-step loop, in which
the host reads a value back after every step before it dispatches the
next; ``{"metrics_every": n}`` is the documented loop for throughput, in
which n steps are dispatched ahead and the device waits for the host once
in n steps. Every knob the cell does not name stays at the program's
default.

Returns the run record the metric readers take their numbers from.
"""
import math
import sys
import time

import numpy as np

from benchmark import readers
from benchmark.drivers import common


def run(ctx):
    import jax
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy, telemetry
    from autodist_tpu.data.prefetch import DevicePrefetcher

    traffic, config, family = ctx.traffic, ctx.config, ctx.family
    chips = jax.device_count()
    global_batch = traffic["batch_per_chip"] * chips
    tokens_per_step = global_batch * family.tokens_per_row(traffic)
    fit_knobs = dict(traffic.get("fit") or {})
    if set(fit_knobs) - {"metrics_every"}:
        # fuse_steps runs another program: its cell needs a driver that
        # warms that program and counts its recompiles
        sys.exit("benchmark: drivers/train_fit.py passes only "
                 "metrics_every to fit, not %s" % sorted(fit_knobs))
    # steps between two visits of the host: the profiler starts and stops
    # on such a visit, with the device idle and whole steps in between
    group = fit_knobs.get("metrics_every", 1)
    if traffic["trace_from_step"] % group or traffic["trace_steps"] % group:
        sys.exit("benchmark: trace_from_step and trace_steps must be "
                 "multiples of metrics_every = %d" % group)
    rec = {"kind": "train_fit", "chips": chips, "fit": fit_knobs,
           "tokens_per_step": tokens_per_step, "notes": []}

    if ctx.trace:
        telemetry.configure("1")

    loss_fn, params, example = family.train_setup(
        config, traffic, global_batch, ctx.seed)
    pool = family.host_batches(config, traffic, global_batch, ctx.seed,
                               traffic["pool"])

    ctx.mark("weights_and_batches")

    # the float32 reference first, alone on the device and freed before the
    # system allocates its state, so that the memory peak reported below is
    # the system's own
    ref = family.reference
    ref0, ref1 = ref.train_check(ref.nll_sum, ref.batch_weight, params,
                                 pool[0], pool[1], jax.devices())
    ctx.mark("reference_losses")
    rec["peak_bytes_after_reference"] = common.memory_peak_bytes()

    t0 = time.perf_counter()
    builder = getattr(strategy, ctx.cell["strategy"])()
    ad = adt.AutoDist(strategy_builder=builder)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, example)
    runner.init(params)
    del params
    rec["build_s"] = time.perf_counter() - t0
    dstep = runner.distributed_step
    ctx.mark("build_and_init")

    # steps 0 and 1 on the first two batches: the first compiles (or loads
    # the program from the cache), both feed the correctness check
    t0 = time.perf_counter()
    loss0 = float(runner.run(pool[0])["loss"])
    rec["compile_s"] = time.perf_counter() - t0
    loss1 = float(runner.run(pool[1])["loss"])
    ctx.mark("first_two_steps")

    tol = ctx.cell["loss_rtol"]
    err = max(abs(loss0 - ref0) / abs(ref0), abs(loss1 - ref1) / abs(ref1))
    rec["loss_check"] = {"system": [loss0, loss1], "reference": [ref0, ref1],
                         "max_rel_err": err, "rtol": tol}
    correct = err <= tol
    if not correct:
        rec["notes"].append("loss disagrees with the float32 reference")

    mesh_devices = set(dstep.mesh.devices.flat)
    placed = all(set(leaf.sharding.device_set) == mesh_devices
                 for leaf in jax.tree_util.tree_leaves(runner.state))
    if len(mesh_devices) != chips or not placed:
        correct = False
        rec["notes"].append("state does not live on every chip")

    for i in range(traffic["warm_steps"]):
        runner.run(pool[(2 + i) % len(pool)])
    compiled_before = dstep._step_fn._cache_size()

    # ---- the measured window
    tracer = common.TraceWindow() if ctx.trace else None
    ends, profiler_steps = [], []
    first, last = traffic["trace_from_step"], \
        traffic["trace_from_step"] + traffic["trace_steps"]

    def on_step(i, metrics):
        ends.append(time.perf_counter())
        if tracer is not None:
            if i + 1 == first:
                tracer.start()
                profiler_steps.append(i + 1)
            elif i + 1 == last:
                tracer.stop()
                profiler_steps.append(i + 1)

    deadline = [None]

    def batches():
        i = 0
        while time.perf_counter() < deadline[0]:
            yield pool[i % len(pool)]
            i += 1

    if ctx.trace:
        telemetry.get_recorder().clear()
    print("benchmark: window starts", file=sys.stderr, flush=True)
    rec["setup_s"] = time.perf_counter() - ctx.t_start
    fit_t0 = time.perf_counter()
    deadline[0] = fit_t0 + ctx.seconds
    history = runner.fit(DevicePrefetcher(batches(), runner, depth=2),
                         callbacks=[on_step], **fit_knobs)
    fit_t1 = time.perf_counter()
    if tracer is not None:
        tracer.load()

    losses = np.asarray([float(m["loss"]) for m in history])
    finite = bool(np.all(np.isfinite(losses)))
    recompiles = dstep._step_fn._cache_size() - compiled_before
    if not finite:
        rec["notes"].append("non-finite loss in the window")
    if recompiles:
        rec["notes"].append("%d recompile(s) in the window" % recompiles)
    rec.update(
        correct=bool(correct and finite and recompiles == 0 and len(history)),
        attempted=len(history), failed=int(np.sum(~np.isfinite(losses))),
        steps=len(history), window_s=fit_t1 - fit_t0,
        step_ends=ends, fit_t0=fit_t0, profiler_steps=profiler_steps,
        traced_steps=(max(0, min(last, len(history)) - first)
                      if tracer is not None and tracer.started else 0),
        recompiles=recompiles, first_loss=float(losses[0]) if len(losses) else math.nan,
        last_loss=float(losses[-1]) if len(losses) else math.nan,
        memory_peak_bytes=common.memory_peak_bytes(),
        flops_per_token=family.train_flops_per_token(config, traffic),
        tracer=tracer)
    rec["step_intervals_ms"] = [round(v, 3)
                                for v in readers.step_intervals_ms(rec)]
    if ctx.trace:
        common.add_telemetry(rec)
    return rec
