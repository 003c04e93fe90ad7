"""Serving driver: an open loop against ``DecodeEngine.submit``.

``DecodeEngine`` over a built and initialised ``Runner`` (no training step
is run or compiled), ``DecodeConfig(slots, prefill_len, max_new_tokens)``
from the traffic file and every other knob at the program's default.
Arrival times and lengths come from the seed; each request is timed from
the moment it was DUE, so a stall is charged to every request it delays.
One generator thread (the main one) sleeps to each due time and submits;
completion times are taken in the futures' done-callbacks.

Load starts ``ramp_s`` before the measured window (part of set-up, as a
cache filled for long contexts is), so that the window sees the engine
with its slots and queue as the offered rate leaves them and not filling
from empty: in PR 22's runs a request took 17 s at the median, and a 30 s
window that starts empty completes a third of what is due in it.
``decode_tok_s`` counts the tokens of the requests that COMPLETED inside
the window, whenever they were due, over the window; nothing completed
after the window's end counts. ``attempted`` / ``failed`` are the requests
due inside the window: one fails on an error, a wrong token count, a shed
submit, or, where the traffic file gives a ``grace_s``, when it is still
unfinished that long after the window (a mix above capacity gives null:
there the queue grows by design and unfinished requests are not failures).

The tokens are checked after the window against the float32 reference, on
weights made again from the seed, and the memory peak is read before that,
so that ``memory_peak_bytes`` is the system's own.
"""
import threading
import time
from statistics import NormalDist

import numpy as np

from benchmark.drivers import common


def stratified_lengths(spec, n, rng):
    """n lengths at the (i + 0.5) / n quantiles of a clipped log-normal,
    shuffled: the same multiset for every seed."""
    if spec["dist"] != "lognormal":
        raise ValueError("unknown length distribution %r" % spec["dist"])
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    lengths = np.clip(np.rint(lengths), spec["lo"], spec["hi"]).astype(int)
    rng.shuffle(lengths)
    return lengths


def schedule(traffic, vocab, seconds, seed):
    """The run's requests, (due offset s, prompt ids, output cap), sorted
    by due time: round(rate x seconds) arrivals uniform over the window
    and round(rate x ramp_s) over the ramp before it (offsets under 0)."""
    rng = np.random.RandomState(seed)
    ramp = float(traffic.get("ramp_s") or 0.0)
    n_win = max(int(round(traffic["rate_rps"] * seconds)), 1)
    n_ramp = int(round(traffic["rate_rps"] * ramp))
    due = np.sort(np.concatenate([rng.uniform(0.0, seconds, n_win),
                                  rng.uniform(-ramp, 0.0, n_ramp)]))
    n = n_win + n_ramp
    plens = stratified_lengths(traffic["prompt_len"], n, rng)
    caps = stratified_lengths(traffic["output_len"], n, rng)
    return [(float(t), rng.randint(0, vocab, p).astype(np.int32), int(c))
            for t, p, c in zip(due, plens, caps)]


def run(ctx):
    import jax
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy, telemetry
    from autodist_tpu.serving.decode import DecodeConfig, DecodeEngine

    traffic, config, family = ctx.traffic, ctx.config, ctx.family
    chips = jax.device_count()
    rec = {"kind": "decode_open_loop", "chips": chips, "notes": []}
    if ctx.trace:
        telemetry.configure("1")

    loss_fn, params, example = family.decode_train_stub(config, ctx.seed,
                                                        chips)
    ctx.mark("weights")
    t0 = time.perf_counter()
    builder = getattr(strategy, ctx.cell["strategy"])()
    runner = adt.AutoDist(strategy_builder=builder).build(
        loss_fn, optax.adam(1e-3), params, example)
    runner.init(params)
    del params  # the runner holds its own; the check makes them again
    rec["build_s"] = time.perf_counter() - t0
    ctx.mark("build_and_init")

    cfg, setup = family.decode_setup(config)
    t0 = time.perf_counter()
    engine = DecodeEngine(runner, setup, DecodeConfig(
        slots=traffic["slots"], prefill_len=traffic["prefill_len"],
        max_new_tokens=traffic["max_new_tokens"]))
    engine.warmup()
    rec["compile_s"] = time.perf_counter() - t0
    ctx.mark("engine_and_warmup")

    # arrivals and lengths from the traffic file's own seed where it has
    # one (a fixed sample path, replayed); prompts' token ids and the
    # weights always from --seed
    reqs = schedule(traffic, cfg.vocab_size, ctx.seconds,
                    traffic.get("schedule_seed", ctx.seed))
    ids = np.random.RandomState(ctx.seed)
    reqs = [(t, ids.randint(0, cfg.vocab_size, len(p)).astype(np.int32), c)
            for t, p, c in reqs]
    n = len(reqs)
    done_t, submit_t, futures = [None] * n, [None] * n, [None] * n
    ramp = float(traffic.get("ramp_s") or 0.0)
    grace = traffic.get("grace_s")
    tracer = common.TraceWindow() if ctx.trace else None
    prof = None

    def profile(t_from, t_for):
        time.sleep(max(t_from - time.perf_counter(), 0))
        tracer.start()
        time.sleep(t_for)
        tracer.stop()

    try:
        if tracer is not None:
            telemetry.get_recorder().clear()
        stats0 = engine.stats()
        # ---- the ramp (set-up), then the measured window from w0
        w0 = time.perf_counter() + ramp
        w1 = w0 + ctx.seconds
        rec["setup_s"] = w0 - ctx.t_start
        if tracer is not None:
            prof = threading.Thread(
                target=profile, name="bench-profiler", daemon=True,
                args=(w0 + traffic["trace_from_share"] * ctx.seconds,
                      min(traffic["trace_seconds"], 0.5 * ctx.seconds)))
            prof.start()
        for i, (due, prompt, cap) in enumerate(reqs):
            wait = w0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            submit_t[i] = time.perf_counter()
            try:
                fut = engine.submit(prompt, cap)
            except Exception as e:  # noqa: BLE001 - a shed request fails
                rec["notes"].append("submit %d: %s" % (i, type(e).__name__))
                continue
            fut.add_done_callback(
                lambda f, i=i: done_t.__setitem__(i, time.perf_counter()))
            futures[i] = fut
        rest = w1 - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        backlog_at_end = engine.queue_depth()
        unfinished_at_end = sum(1 for t in done_t if t is None)
        deadline = w1 + (grace or 0.0)
        for fut in futures if grace else ():
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            try:
                if fut is not None:
                    fut.result(timeout=left)
            except Exception:  # noqa: BLE001 - counted as failed below
                pass
        t_end = time.perf_counter()
        if prof is not None:
            prof.join(timeout=60)
            tracer.load()
        stats = engine.stats()
        rec["memory_peak_bytes"] = common.memory_peak_bytes()
    finally:
        engine.close(timeout=60)

    def outcome(i):
        fut = futures[i]
        if fut is None:
            return "shed"
        if done_t[i] is None or done_t[i] > deadline:
            return "unfinished"  # resolved, if at all, by close()'s drain
        if fut.cancelled() or fut.exception() is not None \
                or len(fut.result()["tokens"]) != reqs[i][2]:
            return "error"
        return "ok"

    outcomes = [outcome(i) for i in range(n)]
    in_window = [i for i in range(n) if reqs[i][0] >= 0.0]
    failing = {"shed", "error"} | ({"unfinished"} if grace is not None
                                   else set())
    failed = sum(1 for i in in_window if outcomes[i] in failing)

    # ---- the tokens against the reference, on weights made again from
    # the seed, after the memory peak was read
    good = [i for i in range(n) if outcomes[i] == "ok"]
    pick = np.random.RandomState(ctx.seed + 1).permutation(len(good))
    sample = [good[j] for j in pick[:traffic["check_requests"]]]
    margin = ctx.cell["logit_margin"]
    if len(sample) < traffic["check_requests"]:
        correct, worst, checked = False, None, 0
        rec["notes"].append("too few completed requests to check")
    else:
        params = family.decode_train_stub(config, ctx.seed, chips)[1]
        seqs = [np.concatenate([reqs[i][1], futures[i].result()["tokens"]])
                for i in sample]
        worst, checked = family.reference.decode_deficits(
            params, seqs, [len(reqs[i][1]) for i in sample],
            traffic["prefill_len"] + traffic["max_new_tokens"])
        del params
        correct = worst <= margin
        if not correct:
            rec["notes"].append("engine tokens disagree with the reference")
    rec["token_check"] = {"requests": len(sample), "tokens": checked,
                          "max_logit_deficit": worst, "margin": margin}

    recompiles = stats["recompiles_after_warmup"]
    errors = stats["errors"] - stats0["errors"]
    if recompiles:
        rec["notes"].append("%d recompile(s) after warm-up" % recompiles)
    if errors:
        rec["notes"].append("%d engine error(s)" % errors)
    rec.update(
        correct=bool(correct and recompiles == 0 and errors == 0
                     and failed == 0),
        attempted=len(in_window), failed=failed, window_s=ctx.seconds,
        w0=w0, ramp_s=ramp,
        requests=[{"due": w0 + due, "submitted": submit_t[i],
                   "done": done_t[i], "prompt_len": len(prompt), "cap": cap,
                   "ok": outcomes[i] == "ok", "outcome": outcomes[i],
                   "in_window": due >= 0.0}
                  for i, (due, prompt, cap) in enumerate(reqs)],
        backlog_at_end=backlog_at_end, unfinished_at_end=unfinished_at_end,
        drained_s=t_end - w1,
        # the engine's counters from the ramp's start to the end of the
        # grace (its percentiles are over its recent steps)
        engine_stats={k: (stats[k] - stats0[k]
                          if k in ("steps", "tokens", "prefill_admits",
                                   "completed") else stats[k])
                      for k in ("steps", "tokens", "prefill_admits",
                                "completed", "slots", "token_p50_ms",
                                "token_p99_ms", "peak_occupancy")},
        recompiles=recompiles, tracer=tracer)
    if ctx.trace:
        common.add_telemetry(rec)
    return rec
