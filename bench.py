"""Benchmark: framework train-step throughput vs. plain-jit baselines.

Prints cumulative JSON result lines to stdout — one after EVERY model
completes (last line wins): {"metric", "value", "unit", "vs_baseline",
"models"}. Three flagship models (the BASELINE.md bar): resnet50 (batch
256), bert_base (bf16), and the lm1b-config transformer LM (bf16). For
each, the framework's full stack (strategy build -> lowering -> Runner
step) races a hand-written jit data-parallel step on the identical
model/optimizer/batch. ``vs_baseline`` >= 1.0 means the framework matches
or beats hand-written JAX; the headline ``vs_baseline`` is the MINIMUM
ratio across models that ran (the conservative claim), per-model detail in
"models" (each with examples/sec and MFU).

Process layout:
- each model runs in its OWN subprocess with a hard parent-side timeout —
  a wedged compile costs one model, never the artifact. The parent never
  imports JAX: an accelerator belongs to one process at a time, and a
  parent that had touched it would starve its children;
- the parent prints the cumulative result after every model and on
  SIGTERM/SIGINT, so a kill at any point still leaves the most recent
  complete line on stdout — and exits non-zero when any model failed or
  none ran;
- children share the persistent XLA compile cache
  (``autodist_tpu.utils.compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
  when set, else ``<repo>/.jax_cache``);
- inside a model, the pair loop checks a soft deadline and emits with the
  pairs it has rather than running past its budget;
- every result line names the device it ran on (``device``: platform,
  device_kind, count). The modes run where JAX's own ``JAX_PLATFORMS``
  puts them; MFU is only computed on a TPU the chip table knows.

Methodology (unchanged from round 2):
- batches are device-resident for BOTH paths; both donate state buffers;
- vs_baseline is the MEDIAN over order-alternated paired phases;
- MFU = (compiled cost-analysis FLOPs per step) / step time / chip peak
  (``resource_spec.CHIP_TABLE``), from the framework path's best phase,
  with the median alongside.
"""
import contextlib
import functools
import json
import os
import signal
import statistics
import subprocess
import sys
import time

import numpy as np

MODEL_LABELS = ["resnet50", "bert_base", "lm1b"]
RESULT_TAG = "ADT_MODEL_RESULT\t"


def _sync(out) -> float:
    """Read a scalar back to the host: waits for the device, and returns
    the value the accuracy legs compare."""
    import jax
    import numpy as np
    return float(np.asarray(jax.device_get(out)))


def _device_info() -> dict:
    """The device a result was taken on, as JAX reports it."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": len(jax.devices())}


def _print_result(result: dict):
    """Print one tagged result line, stamped with the device."""
    print(RESULT_TAG + json.dumps(dict(result, device=_device_info())),
          flush=True)


def _phase_rate(fn, iters):
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    _sync(out)
    return iters / (time.perf_counter() - t0)


def _chip_peak() -> float:
    """Peak bf16 FLOP/s of the attached TPU from the one chip table;
    raises on a device kind the table does not know (CPUs included — an
    MFU against an assumed peak is not a measurement)."""
    import jax
    from autodist_tpu.resource_spec import CHIP_TABLE, chip_kind_of
    return CHIP_TABLE[chip_kind_of(
        jax.devices()[0].device_kind)].peak_bf16_flops


def _compiled_flops(lowered_compiled) -> float:
    ca = lowered_compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0))


def _model_spec(label, batch_size=None):
    """(registry name, setup kwargs, batch key, flops_extra) for a
    flagship label. ``flops_extra`` corrects XLA cost-analysis blind
    spots (it counts a ``lax.scan`` body ONCE regardless of trip count)
    with closed-form hand counts, so memory-lean scanned ops can be
    benched at their best operating point without misreporting MFU."""
    import jax.numpy as jnp
    if label == "resnet50":
        # batch 256: a realistic v5e operating point (batch 64 leaves the
        # MXU underfed)
        return "resnet50", dict(batch_size=batch_size or 256), "image", 0.0
    if label == "bert_base":
        # bf16 like every real TPU deployment; the driver's child benches
        # batch 64 AND 128 as paired phases in one run and headlines the
        # artifact winner (batch 256 RESOURCE_EXHAUSTs on the 16 GB v5e)
        return "bert_base", dict(batch_size=batch_size or 128, seq_len=128,
                                 dtype=jnp.bfloat16), "input_ids", 0.0
    if label == "lm1b":
        from autodist_tpu.models.lm import LMConfig
        cfg = LMConfig.lm1b(dtype=jnp.bfloat16)
        # seq 128, not 256: at 256 the lean-head compile plus the pair
        # phases regularly overran the per-model budget and lm1b reported
        # NOTHING (the worst outcome — ROADMAP pain point); half the
        # tokens per step lands the compile and >= 2 pairs inside the
        # budget. ADT_BENCH_LM1B_SEQ=256 restores the full-length run
        # when the budget allows.
        batch = batch_size or 64
        seq = int(os.environ.get("ADT_BENCH_LM1B_SEQ", "128"))
        # lean (chunked) LM head: the ONLY head that fits batch 64 on the
        # 16 GB chip (the standard head OOMs). XLA's cost analysis counts
        # its vocab-chunk scan body
        # once, so the head FLOPs are hand-computed in closed form:
        # fwd logits matmul 2*T*D*V + backward dx and dW matmuls (4*T*D*V)
        # = 6*T*D*V total, of which XLA sees one chunk's worth.
        from autodist_tpu.ops.xent import _layout
        chunk_eff, _n = _layout(cfg.vocab_size, 8192)
        tokens = batch * seq
        flops_extra = 6.0 * tokens * cfg.d_model * (cfg.vocab_size
                                                    - chunk_eff)
        return "lm", dict(config=cfg, batch_size=batch, seq_len=seq,
                          lean_head=True), "tokens", flops_extra
    if label == "smoke":  # tiny CPU-runnable config for harness tests
        return ("resnet18", dict(batch_size=batch_size or 4, image_size=32),
                "image", 0.0)
    raise ValueError(label)


def bench_model(label, pairs=8, iters=4, deadline=None, batch_size=None):
    import jax
    name, setup_kw, batch_key, flops_extra = _model_spec(label, batch_size)
    print("bench_model:", label, setup_kw, file=sys.stderr, flush=True)
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.models import make_train_setup

    loss_fn, params, batch_np, _ = make_train_setup(name, **setup_kw)
    opt = optax.adam(1e-3)
    batch_size = int(np.shape(batch_np[batch_key])[0])

    # ---- baseline: plain jit data-parallel step, donated state,
    #      device-resident batch
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def baseline_step(p, s, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    base_batch = jax.device_put(batch_np)
    # the baseline donates its state buffers, so it needs its OWN copies
    # (the originals feed the framework path later) — copied ON DEVICE,
    # no host round trip
    import jax.numpy as jnp
    copy_tree = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    base_box = [copy_tree(params), jax.jit(opt.init)(params)]
    t0 = time.perf_counter()
    # AOT-compile once and call the executable directly: one compile serves
    # both the FLOPs count and the baseline steps
    baseline_exec = baseline_step.lower(
        base_box[0], base_box[1], base_batch).compile()
    flops = _compiled_flops(baseline_exec)
    if flops:
        flops += flops_extra  # closed-form scan-body correction
    print("  baseline compiled in %.1fs, flops/step=%.3g"
          % (time.perf_counter() - t0, flops), file=sys.stderr, flush=True)

    def run_baseline():
        p, s, loss = baseline_exec(base_box[0], base_box[1], base_batch)
        base_box[0], base_box[1] = p, s
        return loss

    # ---- framework: AllReduce strategy through the full stack
    adt.reset()
    ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
    runner = ad.build(loss_fn, opt, params, batch_np)
    runner.init(params)
    sharded = runner.remapper.remap_feed(batch_np)
    state_box = [runner.state]

    def run_fw():
        st, m = runner.distributed_step(state_box[0], sharded)
        state_box[0] = st
        return m["loss"]

    # warmup (compile + a few steps each)
    t0 = time.perf_counter()
    for _ in range(3):
        lb = run_baseline()
        lf = run_fw()
    _sync(lb), _sync(lf)
    print("  warmup done in %.1fs" % (time.perf_counter() - t0),
          file=sys.stderr, flush=True)

    # adaptive phase length: short steps need more iterations per phase
    # or timer and dispatch jitter dominates the pair ratio. The probe is
    # a median of 3 so one slow probe step can't pin iters low.
    probes = []
    for _ in range(3):
        t0 = time.perf_counter()
        _sync(run_fw())
        probes.append(time.perf_counter() - t0)
    step_s = max(statistics.median(probes), 1e-4)
    iters = max(iters, min(64, int(round(1.0 / step_s))))
    print("  step=%.0fms -> %d iters/phase" % (step_s * 1e3, iters),
          file=sys.stderr, flush=True)

    ratios, fw_rates = [], []
    for k in range(pairs):
        if deadline is not None and ratios and time.perf_counter() > deadline:
            print("  deadline: stopping after %d pairs" % len(ratios),
                  file=sys.stderr, flush=True)
            break
        if k % 2 == 0:
            rb = _phase_rate(run_baseline, iters)
            rf = _phase_rate(run_fw, iters)
        else:
            rf = _phase_rate(run_fw, iters)
            rb = _phase_rate(run_baseline, iters)
        ratios.append(rf / rb)
        fw_rates.append(rf)
    fused_extra = _maybe_fused_phases(runner, state_box, sharded, run_fw,
                                      iters)
    wire_extra = _wire_dtype_phases(loss_fn, opt, params, batch_np,
                                    run_fw, iters)
    zero_extra = _zero_phases(loss_fn, opt, params, batch_np, run_fw,
                              iters)
    bf16_extra = _bf16_phases(loss_fn, opt, params, batch_np, run_fw,
                              iters)
    adt.reset()
    search_extra = _search_phases(loss_fn, opt, params, batch_np, iters,
                                  fw_rates, deadline)
    best_rate = max(fw_rates)
    # flops is the GLOBAL per-step count; aggregate peak scales with the
    # device count the framework step runs over. MFU is a device metric:
    # off-TPU it is not measured (None), never priced at an assumed peak
    if jax.devices()[0].platform == "tpu" and flops:
        agg_peak = _chip_peak() * len(jax.devices())
        mfu = round(flops * best_rate / agg_peak, 4)
        mfu_median = round(flops * statistics.median(fw_rates) / agg_peak, 4)
    else:
        mfu = mfu_median = None
    out = {
        "examples_per_sec": round(statistics.median(fw_rates) * batch_size, 2),
        "vs_baseline": round(statistics.median(ratios), 4),
        "mfu": mfu,
        "mfu_median": mfu_median,
        "flops_per_step": flops,
        "batch_size": batch_size,
        "pairs": len(ratios),
    }
    out.update(fused_extra)
    out.update(wire_extra)
    out.update(zero_extra)
    out.update(bf16_extra)
    out.update(search_extra)
    return out


def _paired_strategy_phases(builder, loss_fn, opt, params, batch_np,
                            run_fw, iters, steps, tol, leg):
    """Shared mechanics of the opt-in paired strategy harnesses
    (`_wire_dtype_phases`, `_zero_phases`): build the SAME model under
    ``builder``, train a short accuracy leg, snapshot the telemetry
    counters, train a FRESH fp32 `AllReduce()` reference from identical
    params on the identical batch (the main `run_fw` runner has already
    trained through warmup/probe/pair phases — comparing against it
    would measure training progress, not the variant's error), assert
    final-loss parity within ``tol``, then run order-alternated paired
    throughput phases against the main framework path. Returns
    ``(variant_losses, ref_losses, median_ratio, counters,
    variant_runner)`` — callers add their leg-specific assertions."""
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.telemetry import spans as tel
    adt.reset()
    ad = adt.AutoDist(strategy_builder=builder)
    vrunner = ad.build(loss_fn, opt, params, batch_np)
    vrunner.init(params)
    vsharded = vrunner.remapper.remap_feed(batch_np)
    vbox = [vrunner.state]

    def run_v():
        st, m = vrunner.distributed_step(vbox[0], vsharded)
        vbox[0] = st
        return m["loss"]

    v_losses = [_sync(run_v()) for _ in range(steps)]
    counters = dict(tel.counters())
    adt.reset()
    ad_fp = adt.AutoDist(strategy_builder=strategy.AllReduce())
    frunner = ad_fp.build(loss_fn, opt, params, batch_np)
    frunner.init(params)
    fsharded = frunner.remapper.remap_feed(batch_np)
    fbox = [frunner.state]
    f_losses = []
    for _ in range(steps):
        st, m = frunner.distributed_step(fbox[0], fsharded)
        fbox[0] = st
        f_losses.append(_sync(m["loss"]))
    final_gap = abs(v_losses[-1] - f_losses[-1]) / max(
        abs(f_losses[-1]), 1e-9)
    assert final_gap <= tol, (
        "%s broke loss parity: %.6g vs fp32 %.6g (gap %.3f > tol %.3f)"
        % (leg, v_losses[-1], f_losses[-1], final_gap, tol))
    ratios = []
    for j in range(4):
        if j % 2 == 0:
            rv = _phase_rate(run_v, iters)
            rf = _phase_rate(run_fw, iters)
        else:
            rf = _phase_rate(run_fw, iters)
            rv = _phase_rate(run_v, iters)
        ratios.append(rv / rf)
    return (v_losses, f_losses, statistics.median(ratios), counters,
            vrunner)


def _wire_dtype_phases(loss_fn, opt, params, batch_np, run_fw, iters):
    """Opt-in (ADT_BENCH_WIRE_DTYPE=int8) quantized-wire accuracy +
    throughput harness for the artifact rounds: builds the SAME model
    under ``AllReduce(wire_dtype="int8")``, runs order-alternated paired
    phases against the fp32 framework path, trains a short paired leg
    from identical params on identical batches, and ASSERTS loss-curve
    parity (final loss within the harness tolerance,
    ADT_BENCH_WIRE_TOL, default 10%). Reports the telemetry-measured
    wire reduction (wire.bytes_quantized / wire.bytes_saved — the >= 3x
    payload-drop criterion reads straight off these). Best-effort: a
    failure is recorded, never fatal to the model's main result."""
    mode = (os.environ.get("ADT_BENCH_WIRE_DTYPE", "") or "").strip()
    if mode not in ("int8", "1"):
        return {}
    from autodist_tpu import strategy
    tol = float(os.environ.get("ADT_BENCH_WIRE_TOL", "0.1"))
    steps = int(os.environ.get("ADT_BENCH_WIRE_STEPS", "8"))
    try:
        q_losses, f_losses, ratio, counters, _ = _paired_strategy_phases(
            strategy.AllReduce(wire_dtype="int8"), loss_fn, opt, params,
            batch_np, run_fw, iters, steps, tol, "quantized wire")
        quantized = counters.get("wire.bytes_quantized", 0.0)
        saved = counters.get("wire.bytes_saved", 0.0)
        assert quantized > 0 and saved > 0, counters
        reduction = (quantized + saved) / quantized
        return {"wire_dtype": "int8",
                "wire_reduction_x": round(reduction, 3),
                "wire_bytes_quantized": quantized,
                "wire_bytes_saved": saved,
                "wire_loss_final": [round(q_losses[-1], 6),
                                    round(f_losses[-1], 6)],
                "wire_vs_fp32": round(ratio, 4)}
    except Exception as e:  # noqa: BLE001 — opt-in extra, never fatal
        print("  wire-dtype phases failed: %s" % e, file=sys.stderr,
              flush=True)
        return {"wire_dtype": "int8",
                "wire_error": "%s: %s" % (type(e).__name__, str(e)[:160])}


def _zero_phases(loss_fn, opt, params, batch_np, run_fw, iters):
    """Opt-in (ADT_BENCH_ZERO=1) ZeRO-sharded-update harness for the
    artifact rounds: builds the SAME model under ``ZeroSharded()``,
    trains a short paired leg from identical params on identical batches
    and ASSERTS loss parity with the fp32 AllReduce path (the fp32
    sharded update is exact modulo float reassociation — tolerance
    ADT_BENCH_ZERO_TOL, default 2%), checks the projected per-chip
    opt-state saving is positive (zero.hbm_saved_bytes — the number the
    ADT501 gate stops charging), and runs order-alternated paired
    throughput phases against the plain AllReduce framework path (rs+ag
    move the same ring bytes, so the ratio isolates launch overhead).
    Best-effort: a failure is recorded, never fatal."""
    if (os.environ.get("ADT_BENCH_ZERO", "") or "").strip() not in ("1",):
        return {}
    from autodist_tpu import strategy
    tol = float(os.environ.get("ADT_BENCH_ZERO_TOL", "0.02"))
    steps = int(os.environ.get("ADT_BENCH_ZERO_STEPS", "8"))
    try:
        z_losses, f_losses, ratio, counters, zrunner = \
            _paired_strategy_phases(
                strategy.ZeroSharded(), loss_fn, opt, params, batch_np,
                run_fw, iters, steps, tol, "sharded update")
        meta = zrunner.distributed_step.metadata
        saved = float(meta.get("zero_hbm_saved_bytes", 0.0))
        assert meta.get("zero_sharded"), "no variable took the zero path"
        assert saved > 0, "zero leg projects no opt-state HBM saving"
        assert counters.get("zero.rs_bytes", 0.0) > 0, counters
        assert counters.get("zero.ag_bytes", 0.0) > 0, counters
        return {"zero_sharded_vars": len(meta["zero_sharded"]),
                "zero_hbm_saved_bytes": saved,
                "zero_rs_bytes": counters.get("zero.rs_bytes", 0.0),
                "zero_ag_bytes": counters.get("zero.ag_bytes", 0.0),
                "zero_loss_final": [round(z_losses[-1], 6),
                                    round(f_losses[-1], 6)],
                "zero_vs_allreduce": round(ratio, 4)}
    except Exception as e:  # noqa: BLE001 — opt-in extra, never fatal
        print("  zero phases failed: %s" % e, file=sys.stderr, flush=True)
        return {"zero_error": "%s: %s" % (type(e).__name__, str(e)[:160])}


def _bf16_phases(loss_fn, opt, params, batch_np, run_fw, iters):
    """Opt-in (ADT_BENCH_BF16=1) managed-bf16-compute harness for the
    artifact rounds: builds the SAME model under
    ``AllReduce(compute_dtype="bf16")`` — bf16 forward/backward beside
    the f32 master params the ADT60x analyzer certifies — trains a short
    paired leg from identical params on identical batches, ASSERTS
    final-loss parity with the f32 path (tolerance ADT_BENCH_BF16_TOL,
    default 5%), checks the lowered step really runs the half tier
    (metadata ``compute_dtype``), and reports the order-alternated
    paired throughput ratio — the bf16-vs-f32 pair the search's compute
    axis is priced against. Best-effort: a failure is recorded, never
    fatal to the model's main result."""
    if (os.environ.get("ADT_BENCH_BF16", "") or "").strip() not in ("1",):
        return {}
    from autodist_tpu import strategy
    tol = float(os.environ.get("ADT_BENCH_BF16_TOL", "0.05"))
    steps = int(os.environ.get("ADT_BENCH_BF16_STEPS", "8"))
    try:
        b_losses, f_losses, ratio, _counters, brunner = \
            _paired_strategy_phases(
                strategy.AllReduce(compute_dtype="bf16"), loss_fn, opt,
                params, batch_np, run_fw, iters, steps, tol,
                "bf16 compute")
        meta = brunner.distributed_step.metadata
        assert meta.get("compute_dtype") == "bf16", meta
        return {"bf16_compute": True,
                "bf16_loss_final": [round(b_losses[-1], 6),
                                    round(f_losses[-1], 6)],
                "bf16_vs_f32": round(ratio, 4)}
    except Exception as e:  # noqa: BLE001 — opt-in extra, never fatal
        print("  bf16 phases failed: %s" % e, file=sys.stderr, flush=True)
        return {"bf16_error": "%s: %s" % (type(e).__name__, str(e)[:160])}


def _maybe_fused_phases(runner, state_box, sharded, run_fw, iters):
    """Opt-in (ADT_BENCH_FUSED=k) paired fused-vs-per-step phases for the
    artifact rounds: the fused engine runs k microsteps per dispatch over
    a [k, ...] stack of the SAME batch, so the ratio isolates the per-step
    host round-trip the fusion removes. Best-effort — a failure here is
    recorded, never fatal to the model's main result."""
    fuse_k = int(os.environ.get("ADT_BENCH_FUSED", "0") or 0)
    if fuse_k <= 1:
        return {}
    import jax
    try:
        import numpy as np
        host = jax.tree_util.tree_map(
            lambda v: np.stack([np.asarray(jax.device_get(v))] * fuse_k),
            sharded)
        stacked = runner.remapper.remap_feed_stack(host)

        def run_fw_fused():
            st, m = runner.distributed_step.run_multi(state_box[0], stacked)
            state_box[0] = st
            return m["loss"][-1]

        _sync(run_fw_fused())  # compile + one superstep
        fused_iters = max(1, iters // fuse_k)
        ratios = []
        for j in range(4):
            if j % 2 == 0:
                rp = _phase_rate(run_fw, iters)
                rf = _phase_rate(run_fw_fused, fused_iters)
            else:
                rf = _phase_rate(run_fw_fused, fused_iters)
                rp = _phase_rate(run_fw, iters)
            # rf counts SUPERSTEPS; x k converts to microsteps/s
            ratios.append(rf * fuse_k / rp)
        return {"fuse_steps": fuse_k,
                "fused_vs_per_step": round(statistics.median(ratios), 4)}
    except Exception as e:  # noqa: BLE001 — opt-in extra, never fatal
        print("  fused phases failed: %s" % e, file=sys.stderr, flush=True)
        return {"fuse_steps": fuse_k,
                "fused_error": "%s: %s" % (type(e).__name__, str(e)[:160])}


def _search_phases(loss_fn, opt, params, batch_np, iters, fw_rates,
                   deadline):
    """Searched-vs-zoo leg of each model bench: run the per-variable plan
    search (autodist_tpu/search/) on the bench model, scored through the
    calibrated cost model — static only, NO candidate is compiled, so this
    is seconds even for the flagship models — and record the searched and
    best-zoo ESTIMATED step times side by side. With ADT_BENCH_SEARCH=1
    the chosen plan is additionally compiled through the full stack and
    timed, recording the MEASURED searched step rate beside the main
    path's rates (sequential phases, not paired: the process holds one
    AutoDist at a time). Best-effort — a failure here is recorded, never
    fatal to the model's main result."""
    if deadline is not None and time.perf_counter() > deadline:
        return {"search": {"skipped": "model budget exhausted"}}
    try:
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.search.drivers import SearchConfig, run_search
        from autodist_tpu.search.scoring import zoo_best
        from autodist_tpu.simulator.simulator import Simulator

        item = ModelItem(loss_fn=loss_fn, optimizer=opt, params=params,
                         example_batch=batch_np).prepare()
        spec = ResourceSpec.from_local()
        sim = Simulator(item, spec)
        budget = int(os.environ.get("ADT_BENCH_SEARCH_BUDGET", "64"))
        res = run_search(item, spec, config=SearchConfig(budget=budget),
                         simulator=sim)
        if not res.ok:
            return {"search": {"error": "all %d candidates pruned (%s)"
                               % (res.candidates,
                                  res.trace.prune_reasons())}}
        zoo_label, zoo_score, zoo = zoo_best(item, spec, sim)
        doc = {"plan": res.trace.result["plan"],
               "est_searched_ms": round(res.record.step_time_s * 1e3, 4),
               "zoo_best": zoo_label,
               "est_zoo_ms": round(zoo.step_time_s * 1e3, 4),
               "beats_zoo": bool(res.record.score_s <= zoo_score + 1e-12),
               "candidates": res.candidates, "pruned": res.pruned,
               "search_s": round(res.wall_s, 3)}
        print("  search: %s est %.3f ms vs zoo %s %.3f ms (%.1fs)"
              % (doc["plan"], doc["est_searched_ms"], zoo_label,
                 doc["est_zoo_ms"], res.wall_s),
              file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001 — extra leg, never fatal
        print("  search leg failed: %s" % e, file=sys.stderr, flush=True)
        return {"search": {"error": "%s: %s" % (type(e).__name__,
                                                str(e)[:160])}}
    if (os.environ.get("ADT_BENCH_SEARCH", "0") or "0") != "0":
        doc.update(_measured_search_phases(loss_fn, opt, params, batch_np,
                                           res.strategy, iters, fw_rates))
    return {"search": doc}


def _measured_search_phases(loss_fn, opt, params, batch_np, strategy,
                            iters, fw_rates):
    """Opt-in (ADT_BENCH_SEARCH=1) measured side of the search leg:
    compile the searched plan through the full stack and time it."""
    import autodist_tpu as adt
    from autodist_tpu.strategy.base import StrategyBuilder

    class _Fixed(StrategyBuilder):
        def __init__(self, s):
            self._s = s

        def build(self, model_item, resource_spec):
            return self._s

    try:
        adt.reset()
        ad = adt.AutoDist(strategy_builder=_Fixed(strategy))
        runner = ad.build(loss_fn, opt, params, batch_np)
        runner.init(params)
        sharded = runner.remapper.remap_feed(batch_np)
        box = [runner.state]

        def run_searched():
            st, m = runner.distributed_step(box[0], sharded)
            box[0] = st
            return m["loss"]

        lo = None
        for _ in range(2):
            lo = run_searched()
        _sync(lo)
        rates = [_phase_rate(run_searched, iters) for _ in range(4)]
        adt.reset()
        r = statistics.median(rates)
        return {"measured_searched_steps_per_s": round(r, 4),
                "measured_vs_zoo": round(r / statistics.median(fw_rates),
                                         4)}
    except Exception as e:  # noqa: BLE001 — opt-in extra, never fatal
        print("  measured search phases failed: %s" % e, file=sys.stderr,
              flush=True)
        return {"measured_error": "%s: %s" % (type(e).__name__,
                                              str(e)[:160])}


def smoke_main(fused: bool = False):
    """CI leg (``bench.py --smoke [--fused]``): a tiny MLP through the
    full stack — seconds, not minutes (CI exports ``JAX_PLATFORMS=cpu``). With ``--fused`` it also
    compiles the fused multi-step engine (``fit(fuse_steps=4,
    metrics_every=2)``), asserts parity with the per-step loop AND the
    k× dispatch reduction, and reports the paired fused-vs-per-step
    throughput ratio — so the scan-fused lowering path compiles (and
    stays numerically honest) on every PR.

    Under ``ADT_TRACE=1`` the run also exports a Perfetto-loadable trace
    (``ADT_TRACE_FILE`` or ``<trace dir>/smoke-trace.json``), validates
    it against the chrome-trace schema, and embeds a per-subsystem
    timing breakdown + the registry counters in the BENCH json — future
    rounds get phase-level attribution of where the smoke seconds went."""
    # >= 2 virtual devices so a REAL gradient wire exists for the
    # quantized-AR leg (takes effect as long as the backend has not
    # initialized yet; the leg falls back to the host-PS wire otherwise)
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy

    rng = np.random.RandomState(0)
    params = {"w1": rng.randn(16, 32).astype(np.float32) * 0.1,
              "b1": np.zeros((32,), np.float32),
              "w2": rng.randn(32, 4).astype(np.float32) * 0.1}

    def loss_fn(p, b):
        import jax.numpy as jnp
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    batches = [{"x": rng.randn(32, 16).astype(np.float32),
                "y": rng.randn(32, 4).astype(np.float32)}
               for _ in range(16)]

    def build():
        adt.reset()
        ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
        runner = ad.build(loss_fn, optax.adam(1e-2), params, batches[0])
        runner.init(params)
        return runner

    # sentinel + quantized-wire legs FIRST: their builds reset the
    # telemetry recorder, and the exported smoke trace / phase breakdown
    # must cover the main plain+fused legs below (the same ordering
    # constraint the serve bench documents for its per-model resets)
    sentinel_result = _smoke_sentinel(loss_fn, params, batches,
                                      len(batches))
    quantized_result = _smoke_quantized_wire(loss_fn, params, batches)
    zero_result = _smoke_zero(loss_fn, params, batches)
    bf16_result = _smoke_bf16(loss_fn, params, batches)

    t0 = time.perf_counter()
    r1 = build()
    h1 = r1.fit(list(batches))
    per_step_s = time.perf_counter() - t0
    result = {"metric": "smoke", "per_step_loop_s": round(per_step_s, 3),
              "steps": len(h1), "final_loss": round(float(h1[-1]["loss"]), 6)}
    if fused:
        k = 4
        t0 = time.perf_counter()
        r2 = build()
        h2 = r2.fit(list(batches), fuse_steps=k, metrics_every=2)
        result["fused_loop_s"] = round(time.perf_counter() - t0, 3)
        d1, d2 = (r1.distributed_step.dispatches,
                  r2.distributed_step.dispatches)
        assert d2 == d1 // k, "dispatches %d != %d/%d" % (d2, d1, k)
        np.testing.assert_allclose([m["loss"] for m in h1],
                                   [m["loss"] for m in h2],
                                   rtol=1e-5, atol=1e-6)
        # snapshot stats BEFORE the paired loops: the registry (process-
        # global) still holds exactly r2's fused fit here, so the
        # telemetry section agrees with the per-runner step counts beside
        # it — after loop_plain it would also count r1's per-step work
        fused_stats = r2.step_stats()
        # steady-state paired ratio (post-compile): per-step vs fused
        def loop_plain():
            r1.fit(list(batches))
        def loop_fused():
            r2.fit(list(batches), fuse_steps=k, metrics_every=4)
        t0 = time.perf_counter(); loop_plain(); tp = time.perf_counter() - t0
        t0 = time.perf_counter(); loop_fused(); tf = time.perf_counter() - t0
        result.update(fuse_steps=k, dispatches=[d1, d2],
                      fused_vs_per_step=round(tp / max(tf, 1e-9), 4),
                      stats=fused_stats)
    result["sentinel"] = sentinel_result
    result["quantized_wire"] = quantized_result
    result["zero_sharded"] = zero_result
    result["bf16_compute"] = bf16_result
    result["search"] = _smoke_search(loss_fn, params, batches[0])
    result["topology"] = _smoke_topology(loss_fn, params, batches[0])
    # trace export BEFORE the elastic leg: its builds reset the recorder
    # (and its reconfigure clears the XLA backend — rebuilt on demand,
    # but the paired timing legs above must not pay that), so it runs
    # dead last with the main legs' telemetry already harvested
    result.update(_smoke_telemetry())
    result["elastic"] = _smoke_elastic(loss_fn, params, batches)
    result["preempt"] = _smoke_preempt(loss_fn, params, batches)
    result["autoscale"] = _smoke_autoscale(loss_fn, params, batches)
    adt.reset()
    _print_result(result)


@contextlib.contextmanager
def _inrun_elastic_sandbox(extra_env=None):
    """Shared harness of the elastic/preempt smoke legs: a fresh
    coordination service on a free port, the in-run elastic knobs
    exported (restored afterwards), and a clean AutoDist registry on
    entry AND exit. Yields the service port."""
    import socket

    import autodist_tpu as adt
    from autodist_tpu.runtime.coordination import CoordinationServer

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {"ADT_COORDSVC_PORT": str(port), "ADT_ELASTIC": "1",
           "ADT_ELASTIC_SYNC": "1", "ADT_ELASTIC_INRUN": "1",
           "ADT_ELASTIC_POLL_S": "0.01"}
    env.update(extra_env or {})
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    srv = None
    try:
        # INSIDE the try: a bind race / failed service start must still
        # restore the exported elastic knobs, or they silently apply to
        # everything that runs after this leg in the same process
        srv = CoordinationServer(port)
        srv.start()
        adt.reset()
        yield port
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        adt.reset()
        if srv is not None:
            srv.stop()


def _smoke_preempt(loss_fn, params, batches):
    """Preemption leg of the smoke bench: two symmetric shrink legs of a
    2-member roster (this process + a phantom peer) down to 1 — one
    PLANNED (the peer announces its departure: cluster-agreed rescue
    checkpoint, pre-staged snapshot, ``planned`` reconfigure) and one
    UNPLANNED (no notice; the snapshot is taken inside the reconfigure
    span) — so every BENCH round records rescue-save latency and
    planned-handoff downtime NEXT TO the unplanned-shrink downtime, plus
    the detection floor (``ADT_HEARTBEAT_TIMEOUT_S``) only the
    un-announced death pays end to end. The planned leg runs FIRST (any
    process-level cache warming then favors the baseline). Asserted on
    the planned leg: exactly one rescue save, zero ``ckpt.fallback``
    restores."""
    import tempfile

    import optax
    import autodist_tpu as adt
    from autodist_tpu import const, strategy
    from autodist_tpu.runtime import elastic, preemption
    from autodist_tpu.runtime.coordination import CoordinationClient
    from autodist_tpu.telemetry import spans as tel

    def shrink_leg(planned):
        """Fresh service + runner: pre-published [me, phantom] roster,
        then a shrink to [me] — announced (notice first) or not.
        Returns (downtime_s, step_stats)."""
        ckpt_dir = tempfile.mkdtemp(prefix="adt-preempt-smoke-")
        with _inrun_elastic_sandbox({"ADT_PREEMPT_POLL_S": "0.01",
                                     "ADT_CKPT_DIR": ckpt_dir}) as port:
            client = CoordinationClient("127.0.0.1", port)
            me = "127.0.0.1"
            elastic.publish_epoch(client, 1, [me, "peer-evicted"])
            ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
            runner = ad.build(loss_fn, optax.adam(1e-2), params,
                              batches[0])
            runner.init(params)
            n = len(batches)
            for i, b in enumerate(batches):
                runner.run(b)
                if planned and i == 2:
                    # the peer's eviction is announced: rescue
                    # checkpoint at the agreed boundary + pre-stage
                    preemption.publish_notice(client, "peer-evicted",
                                              deadline_s=60,
                                              reason="maintenance")
                    time.sleep(0.05)
                elif i == n // 2:
                    # the shrink epoch (for the planned leg: published
                    # while the announced leaver is still "alive")
                    elastic.publish_epoch(client, 2, [me])
                    time.sleep(0.05)
            client.close()
            stats = runner.step_stats()
            assert stats["elastic"]["reconfigs"] == 1, stats["elastic"]
            # counters/histograms must be read INSIDE the sandbox: its
            # teardown resets the telemetry recorder
            leg_telemetry = (tel.counters().get("ckpt.fallback", 0.0),
                             tel.hist_quantile("preempt.rescue_save_ms",
                                               0.5))
            return (stats["elastic"]["last_reconfigure_s"], stats,
                    leg_telemetry)

    try:
        planned_s, planned_stats, (fallback, rescue_ms) = \
            shrink_leg(planned=True)
        assert planned_stats["preempt"]["rescue_saves"] == 1.0, \
            planned_stats["preempt"]
        assert fallback == 0.0, "planned handoff touched ckpt.fallback"
        unplanned_s, _, _ = shrink_leg(planned=False)
        # the structural gap: an UN-announced death is invisible until
        # the watchdog's heartbeat window expires, so its end-to-end
        # downtime floors at detection + reconfigure; an announced
        # departure pays reconfigure alone (the notice precedes the
        # death). The reconfigure spans are recorded raw side by side;
        # the *_total_* fields add that detection floor.
        detect_floor = const.ENV.ADT_HEARTBEAT_TIMEOUT_S.val
        return {
            "rescue_save_ms": round(rescue_ms or 0.0, 2),
            "planned_handoff_downtime_s": round(planned_s, 4),
            "unplanned_shrink_downtime_s": round(unplanned_s, 4),
            "unplanned_detection_floor_s": round(detect_floor, 1),
            "planned_total_downtime_s": round(planned_s, 4),
            "unplanned_total_downtime_s": round(unplanned_s + detect_floor,
                                                4),
            "notices": planned_stats["preempt"]["notices"],
            "rescue_saves": planned_stats["preempt"]["rescue_saves"],
            "ckpt_fallback": fallback,
        }
    except Exception as e:  # noqa: BLE001 — a broken preempt leg must
        # not sink the whole smoke round; surface it in the json instead
        print("[bench] preempt smoke leg failed: %s" % e, file=sys.stderr,
              flush=True)
        return {"error": "%s: %s" % (type(e).__name__, str(e)[:160])}


def _smoke_autoscale(loss_fn, params, batches, osc=False):
    """Autoscale leg (``bench.py --autoscale``, and the smoke round):
    the REAL serving stack (engine + micro-batcher) under a seeded load
    ramp, with a :class:`FleetAutoscaler` closing the loop against a
    phantom-peer fleet — launch roster ``[me, replica-b]``, pool
    ``[replica-c, replica-d]``, so the 2→4→2 ramp exercises the real
    admission/retirement wire without extra processes (the phantom
    pattern the preempt leg established). The engine gets a synthetic
    per-batch service time so a burst SUSTAINS a backlog on CPU.

    Ramp leg asserts: >= 1 grow under sustained queue depth, >= 1
    planned shrink (preemption notice + survivor epoch) back down, zero
    ``ckpt.fallback``, zero sheds OUTSIDE the overload window, at least
    one brownout entry and one deadline shed (the degradation paths),
    and every observed shed carrying a populated ``retry_after_s``.
    Oscillating leg (``osc=True``): bursts shorter than the policy's
    sustain window must produce at most 2 scale events — the hysteresis
    band + sustain window bound flap, which is the whole point."""
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.runtime import elastic
    from autodist_tpu.runtime.coordination import CoordinationClient
    from autodist_tpu.serving import (AutoscalePolicy, FleetAutoscaler,
                                      InferenceEngine, MicroBatcher,
                                      ServingConfig, ServingUnavailable)
    from autodist_tpu.telemetry import spans as tel

    try:
        with _inrun_elastic_sandbox({"ADT_PREEMPT_POLL_S": "0.01"}) as port:
            client = CoordinationClient("127.0.0.1", port)
            me = "127.0.0.1"
            elastic.publish_epoch(client, 1, [me, "replica-b"])
            ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
            runner = ad.build(loss_fn, optax.adam(1e-2), params,
                              batches[0])
            runner.init(params)
            import jax.numpy as jnp

            def serve_fn(p, b):
                h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
                return {"y": h @ p["w2"]}

            replicas = runner.remapper.num_replicas
            engine = InferenceEngine(
                runner, serve_fn, {"x": batches[0]["x"][0]},
                ServingConfig(buckets=(replicas, 8 * replicas),
                              max_delay_ms=2.0, max_queue=64,
                              brownout_queue_frac=0.5,
                              brownout_sustain_s=0.02,
                              brownout_delay_factor=4.0)).warmup()
            mb = MicroBatcher(engine)
            # synthetic service time: the smoke MLP would drain any
            # burst instantly on CPU, and the controller needs a backlog
            # that SUSTAINS past its window to have anything to measure
            real_run = engine.run_batch

            def slow_run(reqs):
                time.sleep(0.015)
                return real_run(reqs)

            engine.run_batch = slow_run
            if osc:
                # sustain window LONGER than any burst: the leg proves
                # the window + hysteresis band bound scale events
                policy = AutoscalePolicy(min_replicas=2, max_replicas=4,
                                         queue_high=8, queue_low=2,
                                         sustain_s=0.5,
                                         grow_cooldown_s=30.0,
                                         shrink_cooldown_s=30.0)
            else:
                policy = AutoscalePolicy(min_replicas=2, max_replicas=4,
                                         queue_high=8, queue_low=2,
                                         sustain_s=0.05,
                                         grow_cooldown_s=0.02,
                                         shrink_cooldown_s=0.02)
            scaler = FleetAutoscaler(client, policy, me,
                                     pool=["replica-c", "replica-d"],
                                     notice_deadline_s=60.0)
            shed_hints, unset_hints = [], 0
            futures = []

            def burst(n, deadline_every=0):
                for i in range(n):
                    dl = (0.001 if deadline_every
                          and i % deadline_every == 0 else None)
                    try:
                        futures.append(mb.submit(
                            {"x": batches[i % len(batches)]["x"][0]},
                            deadline_s=dl))
                    except ServingUnavailable as e:
                        shed_hints.append(e.retry_after_s)

            def settle(fs):
                nonlocal unset_hints
                for f in fs:
                    try:
                        f.result(timeout=30)
                    except ServingUnavailable as e:
                        shed_hints.append(e.retry_after_s)
                        if e.retry_after_s is None:
                            unset_hints += 1
                fs.clear()

            try:
                if osc:
                    # bursts shorter than the sustain window, drained
                    # between spikes — the fleet must NOT move
                    deadline = time.perf_counter() + 2.0
                    while time.perf_counter() < deadline:
                        burst(12)
                        scaler.step()
                        time.sleep(0.05)
                    settle(futures)
                    st = scaler.stats()
                    events = st["grows"] + st["shrinks"]
                    assert events <= 2, (
                        "oscillating load flapped the fleet: %d scale "
                        "events despite sustain %.1fs > burst length"
                        % (events, policy.sustain_s))
                    assert st["holds"] >= 10, st
                    mb.close()
                    return {"mode": "oscillating",
                            "scale_events": events,
                            "holds": st["holds"],
                            "decisions": st["decisions"]}
                # ---- overload window: sustained backlog, fleet 2 -> 4
                overload_t0 = time.perf_counter()
                grow_deadline = overload_t0 + 10.0
                while ((scaler.stats()["grows"] < 2
                        or mb.stats()["brownout"]["entries"] < 1)
                       and time.perf_counter() < grow_deadline):
                    burst(24, deadline_every=8)
                    scaler.step()
                    time.sleep(0.01)
                shed_in_overload = len(shed_hints)
                settle(futures)
                overload_s = time.perf_counter() - overload_t0
                c_shed_after_overload = tel.counters().get("serve.shed",
                                                           0.0)
                # ---- idle window: no traffic, fleet 4 -> 2 via the
                # planned-departure path
                idle_deadline = time.perf_counter() + 10.0
                while (scaler.stats()["shrinks"] < 2
                       and time.perf_counter() < idle_deadline):
                    scaler.step()
                    time.sleep(0.02)
                idle_shed = (tel.counters().get("serve.shed", 0.0)
                             - c_shed_after_overload)
                st = scaler.stats()
                info = elastic.read_epoch(client)
                stats = mb.stats()
                counters = tel.counters()
                mb.close()
                assert st["grows"] >= 1, "no grow under sustained load: %s" % st
                assert st["shrinks"] >= 1, "no shrink under idle: %s" % st
                assert counters.get("preempt.notices", 0.0) >= 1, (
                    "shrink did not go through the planned-departure "
                    "notice path")
                assert counters.get("ckpt.fallback", 0.0) == 0, (
                    "autoscale shrink touched the checkpoint fallback")
                assert idle_shed == 0, (
                    "%d sheds OUTSIDE the overload window" % idle_shed)
                assert unset_hints == 0 and all(
                    h is not None for h in shed_hints), (
                    "a shed was raised without a populated retry_after_s")
                assert info is not None and len(info[1]) == 2, (
                    "fleet did not return to 2 replicas: %s" % (info,))
                assert stats["brownout"]["entries"] >= 1, (
                    "sustained overload never entered brownout: %s"
                    % stats["brownout"])
                assert stats["deadline_shed"] >= 1, (
                    "expired-deadline requests were not shed: %s"
                    % stats["deadline_shed"])
                return {
                    "mode": "ramp",
                    "grows": st["grows"], "shrinks": st["shrinks"],
                    "holds": st["holds"], "refusals": st["refusals"],
                    "final_epoch": info[0],
                    "final_replicas": len(info[1]),
                    "overload_window_s": round(overload_s, 3),
                    "sheds_in_overload": shed_in_overload,
                    "sheds_outside_overload": idle_shed,
                    "deadline_sheds": stats["deadline_shed"],
                    "brownout_entries": stats["brownout"]["entries"],
                    "notices": counters.get("preempt.notices", 0.0),
                    "ckpt_fallback": counters.get("ckpt.fallback", 0.0),
                    "retry_after_hints": len(shed_hints),
                }
            finally:
                mb.close()  # idempotent; a failed assert must not leak
                # the worker thread into the next leg
                client.close()
    except Exception as e:  # noqa: BLE001 — surfaced in the json; the
        # CLI entry (autoscale_main) re-raises so CI stays strict
        print("[bench] autoscale smoke leg failed: %s" % e,
              file=sys.stderr, flush=True)
        return {"error": "%s: %s" % (type(e).__name__, str(e)[:160])}


def _smoke_elastic(loss_fn, params, batches):
    """Elastic leg of the smoke bench: run the smoke MLP under an in-run
    membership, publish a same-roster epoch bump mid-run, and record what
    one reconfiguration event COSTS — span-derived downtime seconds and
    the steps it blocked (downtime / steady median step) — plus the
    fenced-write counter, so BENCH rounds track the price of an elastic
    event alongside throughput."""
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.runtime import elastic
    from autodist_tpu.runtime.coordination import CoordinationClient
    from autodist_tpu.telemetry import spans as tel

    try:
        with _inrun_elastic_sandbox() as port:
            ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
            runner = ad.build(loss_fn, optax.adam(1e-2), params, batches[0])
            runner.init(params)
            client = CoordinationClient("127.0.0.1", port)
            m = elastic.current()
            assert m is not None, "elastic membership was not armed"
            for i, b in enumerate(batches):
                runner.run(b)
                if i == len(batches) // 2:
                    elastic.publish_epoch(client, m.epoch + 1, m.roster)
                    time.sleep(0.05)  # let the poll window lapse
            client.close()
            stats = runner.step_stats()
            assert stats["elastic"]["reconfigs"] == 1, stats["elastic"]
            spans = tel.get_recorder().durations_s("elastic.reconfigure")
            downtime = spans[0] if spans else stats["elastic"][
                "last_reconfigure_s"]
            steady = stats["steady_median_s"] or 0.0
            return {
                "reconfigs": stats["elastic"]["reconfigs"],
                "epoch": stats["elastic"]["epoch"],
                "reconfigure_downtime_s": round(float(downtime or 0.0), 4),
                "steps_blocked": (int(np.ceil(downtime / steady))
                                  if downtime and steady else None),
                "fenced_writes": stats["elastic"]["fenced_writes"],
            }
    except Exception as e:  # noqa: BLE001 — a broken elastic leg must
        # not sink the whole smoke round; surface it in the json instead
        print("[bench] elastic smoke leg failed: %s" % e, file=sys.stderr,
              flush=True)
        return {"error": "%s: %s" % (type(e).__name__, str(e)[:160])}


def _smoke_sentinel(loss_fn, params, batches, plain_steps):
    """Health-sentinel leg of the smoke bench: train the smoke MLP with
    in-graph guards armed and a NaN gradient injected at step 3
    (``ADT_GRAD_FAULT_PLAN``) — the poisoned step must be discarded
    in-graph (``sentinel.skips == 1``), the final loss must stay finite,
    and the guarded program must dispatch exactly as often as the
    unguarded loop beside it (the zero-overhead contract: the verdict
    rides the existing metrics readback). Gates every PR on the
    detect-and-skip path actually compiling."""
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.telemetry import spans as tel

    plan = json.dumps({"faults": [{"var": "w1", "mode": "nan", "step": 3}]})
    prev = os.environ.get("ADT_GRAD_FAULT_PLAN")
    os.environ["ADT_GRAD_FAULT_PLAN"] = plan
    try:
        adt.reset()
        ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
        runner = ad.build(loss_fn, optax.adam(1e-2), params, batches[0],
                          sentinel=True)
        runner.init(params)
        hist = runner.fit(list(batches))
        stats = runner.step_stats()["sentinel"]
        final_loss = float(hist[-1]["loss"])
        assert np.isfinite(final_loss), "sentinel failed to contain the NaN"
        assert stats["skips"] == 1, stats
        assert tel.counters()["sentinel.skips"] == 1
        assert len(hist) == plain_steps
        d = runner.distributed_step.dispatches
        assert d == plain_steps, (
            "guards changed the dispatch count: %d for %d steps"
            % (d, plain_steps))
        return {"skips": stats["skips"], "final_loss": round(final_loss, 6),
                "dispatches": d,
                "last_grad_norm": round(stats["last_grad_norm"], 4)}
    finally:
        if prev is None:
            os.environ.pop("ADT_GRAD_FAULT_PLAN", None)
        else:
            os.environ["ADT_GRAD_FAULT_PLAN"] = prev


def _smoke_quantized_wire(loss_fn, params, batches):
    """Quantized-wire leg of the smoke bench: train the smoke MLP twice —
    fp32 wire vs the blockwise-int8 wire (``AllReduce(wire_dtype=
    "int8")``) — and ASSERT (a) the quantized leg actually saved wire
    bytes (``wire.bytes_saved > 0``, the telemetry counters the lowering
    credits per dispatch), (b) it dispatched exactly as often as the fp32
    leg (the codec lives inside the one program — no extra host
    round-trips), and (c) error feedback kept the loss curve in parity.
    Gates every PR on the two-phase quantized collective compiling AND
    staying honest about its payload reduction."""
    import jax
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.telemetry import spans as tel

    # single-device fallback: no gradient collective exists, but the
    # host-PS pull/push wire does — quantize that instead
    family = (strategy.AllReduce if len(jax.devices()) > 1
              else strategy.PS)

    def leg(wire):
        adt.reset()
        ad = adt.AutoDist(strategy_builder=family(wire_dtype=wire))
        runner = ad.build(loss_fn, optax.adam(1e-2), params, batches[0])
        runner.init(params)
        hist = runner.fit(list(batches))
        return ([float(m["loss"]) for m in hist],
                runner.distributed_step.dispatches,
                dict(tel.counters()))

    fp_losses, fp_dispatches, _ = leg("fp32")
    q_losses, q_dispatches, counters = leg("int8")
    saved = counters.get("wire.bytes_saved", 0.0)
    quantized = counters.get("wire.bytes_quantized", 0.0)
    assert saved > 0, "quantized leg saved no wire bytes: %s" % counters
    assert q_dispatches == fp_dispatches, (
        "quantized wire changed the dispatch count: %d vs %d"
        % (q_dispatches, fp_dispatches))
    # loss-curve parity: error feedback keeps the quantized trajectory on
    # the fp32 curve (loose per-step band + matching final loss)
    np.testing.assert_allclose(q_losses, fp_losses, rtol=0.2, atol=1e-3)
    assert abs(q_losses[-1] - fp_losses[-1]) <= (
        0.1 * max(abs(fp_losses[-1]), 1e-3) + 1e-3), (q_losses[-1],
                                                      fp_losses[-1])
    reduction = (quantized + saved) / max(quantized, 1.0)
    return {"final_loss_fp32": round(fp_losses[-1], 6),
            "final_loss_int8": round(q_losses[-1], 6),
            "bytes_quantized": quantized, "bytes_saved": saved,
            "wire_reduction_x": round(reduction, 3),
            "dispatches": q_dispatches}


def _smoke_bf16(loss_fn, params, batches):
    """Managed-bf16-compute leg of the smoke bench: train the smoke MLP
    twice — f32 vs ``AllReduce(compute_dtype="bf16")`` with the health
    sentinel armed (the ADT604 contract: half precision ships WITH the
    skip/rollback net) — and ASSERT (a) the bf16 step program really ran
    the half tier (``step_stats()["compute_dtype"] == "bf16"``), (b) the
    master params stayed float32 end to end (the f32-master discipline
    ADT602 certifies), (c) loss-curve parity within the sentinel's
    bounds with ZERO guards tripped (bf16 rounding alone must never look
    like a health fault), and (d) the dispatch count is unchanged (the
    casts live inside the one program). Gates every PR on the bf16
    lowering compiling and staying numerically honest."""
    import jax
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy

    def leg(compute_dtype, sentinel=None):
        adt.reset()
        ad = adt.AutoDist(strategy_builder=strategy.AllReduce(
            compute_dtype=compute_dtype))
        runner = ad.build(loss_fn, optax.adam(1e-2), params, batches[0],
                          sentinel=sentinel)
        runner.init(params)
        hist = runner.fit(list(batches))
        return ([float(m["loss"]) for m in hist], runner)

    f_losses, f_runner = leg("f32")
    f_dispatches = f_runner.distributed_step.dispatches
    b_losses, b_runner = leg("bf16", sentinel=True)
    stats = b_runner.step_stats()
    assert stats["compute_dtype"] == "bf16", stats
    leaf_dtypes = {str(x.dtype)
                   for x in jax.tree_util.tree_leaves(
                       b_runner.gather_params())}
    assert leaf_dtypes == {"float32"}, (
        "bf16 compute leaked into the master params: %s" % leaf_dtypes)
    # parity within the sentinel's bounds: bf16 rounds every activation,
    # so the band is wider than the int8 wire's error-feedback leg, but
    # the curve must track and the final losses must agree
    np.testing.assert_allclose(b_losses, f_losses, rtol=0.3, atol=5e-3)
    assert abs(b_losses[-1] - f_losses[-1]) <= (
        0.1 * max(abs(f_losses[-1]), 1e-3) + 1e-3), (b_losses[-1],
                                                     f_losses[-1])
    assert stats["sentinel"]["skips"] == 0, stats["sentinel"]
    assert stats["sentinel"]["rollbacks"] == 0, stats["sentinel"]
    b_dispatches = b_runner.distributed_step.dispatches
    assert b_dispatches == f_dispatches, (
        "bf16 tier changed the dispatch count: %d vs %d"
        % (b_dispatches, f_dispatches))
    return {"final_loss_f32": round(f_losses[-1], 6),
            "final_loss_bf16": round(b_losses[-1], 6),
            "sentinel_skips": stats["sentinel"]["skips"],
            "dispatches": b_dispatches}


def _smoke_zero(loss_fn, params, batches):
    """ZeRO-sharded-update leg of the smoke bench: train the smoke MLP
    under ``ZeroSharded()`` and ASSERT (a) per-step parity with the
    AllReduce loop (the fp32 sharded update is exact modulo float
    reassociation), (b) fused k=4 matches the per-step zero loop with
    the k x dispatch reduction (the sharded opt state rides the scan
    carry), (c) dispatch parity with AllReduce (rs + sharded apply + ag
    all live inside the one program), and (d) the projected per-chip
    opt-state saving is positive (zero.hbm_saved_bytes — what the
    ADT501 plan gate stops charging). Gates every PR on the sharded
    update compiling and staying numerically honest."""
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.telemetry import spans as tel

    def leg(builder, fuse=0):
        adt.reset()
        ad = adt.AutoDist(strategy_builder=builder)
        runner = ad.build(loss_fn, optax.adam(1e-2), params, batches[0])
        runner.init(params)
        if fuse:
            hist = runner.fit(list(batches), fuse_steps=fuse,
                              metrics_every=1)
        else:
            hist = runner.fit(list(batches))
        return ([float(m["loss"]) for m in hist], runner,
                dict(tel.counters()))

    ar_losses, ar_runner, _ = leg(strategy.AllReduce())
    z_losses, z_runner, counters = leg(strategy.ZeroSharded())
    meta = z_runner.distributed_step.metadata
    assert meta["zero_sharded"], "no variable took the zero path"
    saved = float(meta.get("zero_hbm_saved_bytes", 0.0))
    assert saved > 0, "zero leg projects no opt-state HBM saving"
    assert counters.get("zero.rs_bytes", 0.0) > 0, counters
    assert counters.get("zero.ag_bytes", 0.0) > 0, counters
    assert (z_runner.distributed_step.dispatches
            == ar_runner.distributed_step.dispatches), (
        "sharded update changed the dispatch count")
    np.testing.assert_allclose(z_losses, ar_losses, rtol=1e-4, atol=1e-6)
    zf_losses, zf_runner, _ = leg(strategy.ZeroSharded(), fuse=4)
    np.testing.assert_allclose(zf_losses, z_losses, rtol=1e-5, atol=1e-6)
    assert zf_runner.distributed_step.dispatches == \
        z_runner.distributed_step.dispatches // 4
    return {"final_loss_allreduce": round(ar_losses[-1], 6),
            "final_loss_zero": round(z_losses[-1], 6),
            "zero_sharded_vars": len(meta["zero_sharded"]),
            "hbm_saved_bytes": saved,
            "rs_bytes": counters.get("zero.rs_bytes", 0.0),
            "ag_bytes": counters.get("zero.ag_bytes", 0.0),
            "dispatches": z_runner.distributed_step.dispatches}


def _smoke_search(loss_fn, params, batch):
    """Auto-search leg of the smoke bench: run the per-variable plan
    search on the smoke MLP and ASSERT the searched plan's estimated
    step time is <= the best zoo candidate's under the same cost model
    (both scored with the ranking's lossy-compression premium). No
    candidate is compiled — this is seconds of pure static scoring, and
    it gates every PR on the searched-beats-zoo contract."""
    import optax
    from autodist_tpu.analysis.cli import default_spec
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.search.drivers import SearchConfig, run_search
    from autodist_tpu.search.scoring import zoo_best
    from autodist_tpu.simulator.simulator import Simulator

    item = ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-2),
                     params=params, example_batch=batch).prepare()
    spec = default_spec(4)
    sim = Simulator(item, spec)
    t0 = time.perf_counter()
    res = run_search(item, spec, config=SearchConfig(budget=48),
                     simulator=sim)
    search_s = time.perf_counter() - t0
    assert res.ok, "smoke search produced no plan"
    zoo_label, zoo_score, zoo = zoo_best(item, spec, sim)
    assert res.record.score_s <= zoo_score + 1e-12, (
        "searched plan scores %.3e but zoo %s scores %.3e"
        % (res.record.score_s, zoo_label, zoo_score))
    return {"chosen": res.trace.result["plan"],
            "est_search_ms": round(res.record.step_time_s * 1e3, 4),
            "zoo_best": zoo_label,
            "est_zoo_ms": round(zoo.step_time_s * 1e3, 4),
            "candidates": res.candidates, "pruned": res.pruned,
            "search_s": round(search_s, 3)}


def _smoke_topology(loss_fn, params, batch):
    """Topology-ranking leg: price the synthesized collective schedules
    (flat ring / recursive halving-doubling / hierarchical two-level) on
    a simulated 8-host x 8-chip pod with a slow inter-host level — pure
    static scoring, zero hardware — and ASSERT the hierarchical route is
    strictly cheapest AND its plan-level profile crosses strictly fewer
    inter-host bytes than the flat ring's. The per-PR gate on the ADT52x
    analyzer's ranking contract (docs/performance.md)."""
    import optax
    from autodist_tpu.analysis.cli import topology_spec
    from autodist_tpu.analysis.topology import plan_level_bytes
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.resource_spec import Topology
    from autodist_tpu.search.space import PlanSpace, VarChoice
    from autodist_tpu.simulator.cost_model import CostModel

    topo = Topology.from_dict(
        {"hosts": 8, "chips_per_host": 8,
         "levels": [{"name": "ici", "bandwidth_gbps": 400},
                    {"name": "dcn", "bandwidth_gbps": 25}]})
    item = ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-2),
                     params=params, example_batch=batch).prepare()
    spec = topology_spec(topo)
    space = PlanSpace(item, spec)
    cm = CostModel(item, spec)
    ar_s, inter_bytes = {}, {}
    for sched in ("ring", "rhd", "hier"):
        plan = space.make_plan(
            {n: VarChoice(schedule=sched) for n in space.var_names})
        strat = space.build(plan)
        ar_s[sched] = cm.estimate(strat).allreduce_s
        inter_bytes[sched] = plan_level_bytes(
            strat, item, topo).get("dcn", 0.0)
    assert ar_s["hier"] < ar_s["ring"], ar_s
    assert 0 < inter_bytes["hier"] < inter_bytes["ring"], inter_bytes
    return {"allreduce_ms": {k: round(v * 1e3, 5)
                             for k, v in ar_s.items()},
            "inter_host_bytes": {k: round(v) for k, v in inter_bytes.items()},
            "inter_bytes_ratio": round(
                inter_bytes["ring"] / inter_bytes["hier"], 2)}


def _smoke_telemetry():
    """Trace export + phase breakdown for the smoke result (ADT_TRACE=1).
    Per-subsystem total seconds come from the recorded span categories,
    and the ATTRIBUTED goodput buckets (telemetry/goodput.py self-time
    decomposition: compute / collective-wait / PS-wire / host-input /
    readback / checkpoint / rollback-replay) ride beside them, so a
    BENCH reader sees WHERE the smoke wall time went — per bucket, with
    the buckets summing to the recorded wall time — plus the straggler
    summary (EWMA flags + last z), not just ex/s and MFU."""
    from autodist_tpu import const
    from autodist_tpu.telemetry import export, goodput, spans
    if not spans.tracing_enabled():
        return {}
    rec = spans.get_recorder()
    by_cat = {}
    for row in rec.summary().values():
        agg = by_cat.setdefault(row["cat"], {"count": 0, "total_s": 0.0})
        agg["count"] += row["count"]
        agg["total_s"] = round(agg["total_s"] + row["total_s"], 6)
    path = (const.ENV.ADT_TRACE_FILE.val
            or os.path.join(const.DEFAULT_TRACE_DIR, "smoke-trace.json"))
    gp = goodput.build_report(rec)
    # attributed buckets land INSIDE phase_breakdown (the r06+ trajectory
    # key) plus the full report (wall/coverage/dispatch stats) beside it
    by_cat["attributed"] = {k: round(v, 6) for k, v in gp.buckets.items()}
    counters = rec.counters()
    gauges = rec.gauges()
    out = {"phase_breakdown": by_cat,
           "goodput": gp.to_dict(),
           "straggler": {
               "flags": counters.get("telemetry.straggler_flags", 0.0),
               "gauge_z": gauges.get("telemetry.straggler"),
           },
           "telemetry_counters": {k: v for k, v in counters.items()
                                  if v}}
    try:
        export.write_trace(path)
        errors = export.validate_chrome_trace(export.load_trace(path))
        if errors:
            raise ValueError("; ".join(errors))
        out["trace_file"] = path
        out["trace_events"] = len(rec.events())
    except Exception as e:  # noqa: BLE001 — telemetry must not fail smoke
        out["trace_error"] = "%s: %s" % (type(e).__name__, str(e)[:160])
    return out


# ------------------------------------------------------------- serving leg


SERVE_MODELS = ["dlrm", "ncf"]


def _serve_setup(label, smoke):
    """(loss_fn, params, example_batch, serve_fn, feature_keys, builder)
    for one serving bench model. DLRM rides Parallax (tables on
    load-balanced PS, dense MLPs on AllReduce — the canonical
    recommendation split); NCF rides host-PS. Both are zoo strategies."""
    from autodist_tpu import strategy as S
    if label == "dlrm":
        from autodist_tpu.models.dlrm import DLRMConfig, make_train_setup
        cfg = (DLRMConfig.tiny() if smoke else
               DLRMConfig(table_sizes=(100_000, 50_000, 10_000, 1_000)))
        loss_fn, params, batch, apply_fn = make_train_setup(
            cfg, batch_size=64 if smoke else 256)
        serve_fn = lambda p, b: {  # noqa: E731
            "score": apply_fn(p, b["dense"], b["sparse"])}
        return loss_fn, params, batch, serve_fn, ("dense", "sparse"), \
            S.Parallax()
    if label == "ncf":
        from autodist_tpu.models.ncf import NCFConfig, make_train_setup
        cfg = NCFConfig.tiny() if smoke else NCFConfig()
        loss_fn, params, batch, apply_fn = make_train_setup(
            cfg, batch_size=64 if smoke else 256)
        serve_fn = lambda p, b: {  # noqa: E731
            "score": apply_fn(p, b["user"], b["item"])}
        return loss_fn, params, batch, serve_fn, ("user", "item"), S.PS()
    raise ValueError(label)


def _request_pool(batch, feature_keys):
    """Per-example request pytrees (label leaves dropped) from the
    synthetic example batch — the traffic generator's working set."""
    import jax
    feats = {k: batch[k] for k in feature_keys}
    n = int(np.shape(next(iter(feats.values())))[0])
    return [jax.tree_util.tree_map(lambda a, _i=i: np.asarray(a)[_i],
                                   feats) for i in range(n)]


def _drive_traffic(mb, requests, duration_s, concurrency):
    """Closed-loop clients: ``concurrency`` threads each submit one
    request and wait for its result, for ``duration_s``. Returns
    (completed, shed, errors, wall_s) — QPS is completed/wall."""
    import threading
    from autodist_tpu.serving import ServingUnavailable
    stop_at = time.perf_counter() + duration_s
    done = [0] * concurrency
    shed = [0] * concurrency
    errors = [0] * concurrency

    def client(i):
        rng = np.random.RandomState(i)
        while time.perf_counter() < stop_at:
            req = requests[rng.randint(len(requests))]
            try:
                mb.submit(req).result(timeout=60)
                done[i] += 1
            except ServingUnavailable:
                shed[i] += 1
                time.sleep(0.002)  # back off as a real client would
            except Exception:  # noqa: BLE001 — count, keep driving
                errors[i] += 1
    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 120)
    return sum(done), sum(shed), sum(errors), time.perf_counter() - t0


def _serve_fault_leg(runner, engine, mb, requests, duration_s,
                     concurrency):
    """Degraded-but-alive leg (runs when ``ADT_FAULT_PLAN`` is set): the
    runner's PS store is re-wired as a NON-OWNING serving replica that
    fetches every value group over the real coordination wire — through
    a FaultyProxy executing the fault plan — while a second store (the
    owner) publishes the authoritative values. Faults surface exactly
    where production would see them (resets/delays/truncation on real
    sockets); the assertion is behavioral: traffic keeps completing,
    degraded reads and shed requests are COUNTED, nothing hangs."""
    from autodist_tpu.parallel.ps import PSStore
    from autodist_tpu.runtime import ps_service as pss
    from autodist_tpu.runtime.coordination import CoordinationServer
    from autodist_tpu.runtime.faultinject import FaultPlan, FaultyProxy
    from autodist_tpu.runtime.resilience import ResilientCoordinationClient
    from autodist_tpu.telemetry import spans as tel

    plan = FaultPlan.from_env()
    if not plan.rules:
        return None
    store = runner.distributed_step.ps_store
    if store is None:
        return {"skipped": "no host-PS store (AllReduce-only strategy)"}
    hosts = {d.split(":")[0]
             for p in store.plans.values() for d in p.destinations if d}
    if len(hosts) > 1:
        return {"skipped": "multi-owner plans: one-process fault leg "
                           "models a single owner host"}
    owner_host = hosts.pop() if hosts else "127.0.0.1"

    import socket as socket_lib
    with socket_lib.socket() as s:
        s.bind(("127.0.0.1", 0))
        svc_port = s.getsockname()[1]
    server = CoordinationServer(port=svc_port)
    server.start()
    proxy = FaultyProxy("127.0.0.1", svc_port, plan=plan).start()
    owner = PSStore(dict(store.plans), store._var_infos, store._optimizer)
    try:
        def factory(host):
            return pss.CoordPSService(
                lambda: ResilientCoordinationClient(
                    "127.0.0.1", proxy.port, rpc_timeout=2.0,
                    max_retries=2, seed=0),
                prefix="ps:" + host)
        # the owner publishes the CURRENT trained values on the real wire
        owner.init_params(store.full_values())
        owner.enable_serving(factory, my_host=owner_host)
        # the serving replica owns nothing: every snapshot refresh now
        # crosses the faulted wire
        store.enable_serving(factory, my_host="bench-serve-replica")
        engine.config.snapshot_max_age_s = 0.0  # refresh every batch
        c0 = tel.counters()
        done, shed, errors, wall = _drive_traffic(
            mb, requests, duration_s, concurrency)
        c1 = tel.counters()
        return {
            "qps": round(done / wall, 2),
            "completed": done, "shed": shed, "errors": errors,
            "alive": done > 0,
            "degraded_snapshots":
                c1.get("serve.degraded", 0) - c0.get("serve.degraded", 0),
            "degraded_ps_pulls": c1.get("ps.degraded_pulls", 0)
                - c0.get("ps.degraded_pulls", 0),
            "shed_requests":
                c1.get("serve.shed", 0) - c0.get("serve.shed", 0),
            "faults_injected": len(plan.injected),
        }
    finally:
        proxy.stop()
        owner.close()
        server.stop()


def _serve_bench_model(label, smoke, fault):
    """One model's serving leg: build the strategy-compiled engine, warm
    every bucket, drive closed-loop traffic, report QPS + latency
    percentiles (+ the fault leg when a plan is set)."""
    import optax
    import autodist_tpu as adt
    from autodist_tpu.serving import (InferenceEngine, MicroBatcher,
                                      ServingConfig)
    from autodist_tpu.telemetry import spans as tel

    loss_fn, params, batch, serve_fn, feature_keys, builder = _serve_setup(
        label, smoke)
    adt.reset()
    ad = adt.AutoDist(strategy_builder=builder)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    runner.run(batch)  # one train step: serve values that actually moved
    requests = _request_pool(batch, feature_keys)
    replicas = runner.remapper.num_replicas
    buckets = ((4 * replicas, 8 * replicas) if smoke else None)
    engine = InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=buckets,
                      max_delay_ms=1.0 if smoke else 2.0))
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    duration = float(os.environ.get("ADT_SERVE_DURATION_S",
                                    "2" if smoke else "10"))
    concurrency = int(os.environ.get("ADT_SERVE_CONCURRENCY",
                                     "8" if smoke else "32"))
    mb = MicroBatcher(engine)
    try:
        done, shed, errors, wall = _drive_traffic(mb, requests, duration,
                                                  concurrency)
        stats = mb.stats()
        result = {
            "strategy": type(builder).__name__,
            "buckets": stats["buckets"],
            "warmup_s": round(warmup_s, 3),
            "qps": round(done / wall, 2),
            "completed": done, "shed": shed, "errors": errors,
            "p50_ms": (round(stats["p50_ms"], 3)
                       if stats["p50_ms"] is not None else None),
            "p99_ms": (round(stats["p99_ms"], 3)
                       if stats["p99_ms"] is not None else None),
            "batches": stats["batches"],
            "avg_batch_fill": round(stats["fan_out"]
                                    / max(stats["batches"], 1), 2),
            "padded_rows": stats["padded_rows"],
            "recompiles_after_warmup": stats["recompiles_after_warmup"],
        }
        assert result["recompiles_after_warmup"] == 0, (
            "steady-state serving recompiled %d time(s) after warmup"
            % result["recompiles_after_warmup"])
        assert errors == 0, "%d serving requests errored" % errors
        if fault:
            fault_res = _serve_fault_leg(runner, engine, mb, requests,
                                         duration, concurrency)
            if fault_res is not None:
                result["fault"] = fault_res
        # per-replica QPS: the millions-of-users scaling unit
        import jax
        result["qps_per_replica"] = round(result["qps"]
                                          / max(len(jax.devices()), 1), 2)
        result["latency_histogram"] = tel.histograms().get(
            "serve.latency_ms", {})
        return result
    finally:
        # close the batcher thread but do NOT adt.reset() here: the next
        # model's build-time reset (and serve_main's final one) handles
        # isolation, and resetting now would wipe the recorder before
        # serve_main exports the ADT_TRACE=1 trace artifact
        mb.close()


def serve_main(smoke: bool):
    """``bench.py --serve`` (and the ``--smoke --serve`` CI leg): serving
    QPS + p50/p99 latency for the recommendation flagships (DLRM, NCF)
    on zoo strategies, with the zero-recompile contract asserted and —
    under ``ADT_FAULT_PLAN`` — a degraded-but-alive fault leg on the
    real coordination wire. Under ``ADT_TRACE=1`` the run exports a
    validated Perfetto trace with the ``serve.*`` spans."""
    labels = [s for s in os.environ.get(
        "ADT_SERVE_MODELS", ",".join(SERVE_MODELS)).split(",") if s]
    fault = bool(os.environ.get("ADT_FAULT_PLAN"))
    from autodist_tpu.telemetry import export as tel_export, spans as tel
    models = {}
    traces = []
    for label in labels:
        try:
            models[label] = _serve_bench_model(label, smoke, fault)
            print("  serve %s: %s qps, p50 %s ms, p99 %s ms"
                  % (label, models[label]["qps"], models[label]["p50_ms"],
                     models[label]["p99_ms"]), file=sys.stderr, flush=True)
        except Exception as e:  # noqa: BLE001 — one model must not cost
            # the artifact; smoke re-raises below so CI stays strict
            models[label] = {"error": "%s: %s" % (type(e).__name__,
                                                  str(e)[:200])}
            if smoke:
                raise
            print("  serve %s FAILED: %s" % (label, models[label]["error"]),
                  file=sys.stderr, flush=True)
        # snapshot THIS model's spans now: the next model's build-time
        # adt.reset() wipes the recorder, and the exported artifact must
        # cover every model, not just the last
        if tel.tracing_enabled():
            traces.append(tel_export.chrome_trace())
    result = {"metric": "serve", "smoke": smoke, "models": models}
    result.update(_smoke_telemetry())
    if len(traces) > 1 and result.get("trace_file"):
        merged = tel_export.merge_traces(traces)
        if not tel_export.validate_chrome_trace(merged):
            with open(result["trace_file"], "w") as f:
                json.dump(merged, f)
            result["trace_events"] = len(merged["traceEvents"])
    import autodist_tpu as adt
    adt.reset()
    _print_result(result)


def _serve_decode_leg(runner, cfg, admission, smoke):
    """One admission policy's leg of the continuous-vs-static decode
    head-to-head: same runner, same request trace, same slot count —
    only the admission rule differs."""
    from autodist_tpu.models import lm
    from autodist_tpu.serving.decode import DecodeConfig, DecodeEngine

    replicas = runner.remapper.num_replicas
    r = max(replicas, 1)
    slots = max((4 if smoke else 8) // r, 1) * r
    groups = int(os.environ.get("ADT_DECODE_GROUPS", "6" if smoke else "12"))
    n_requests = groups * slots
    prefill_len = 8
    longest = min(48, max(8, cfg.max_seq_len - prefill_len))
    short = max(longest // 6, 2)
    setup = lm.make_decode_setup(cfg)
    engine = DecodeEngine(runner, setup, DecodeConfig(
        slots=slots, max_new_tokens=longest, prefill_len=prefill_len,
        admission=admission))
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    # mixed-length generations — one long sequence per slot group among
    # shorts — are the canonical serving workload: the static baseline
    # idles every freed slot until the longest sequence of its batch
    # finishes, exactly the waste continuous batching reclaims
    import numpy as np
    rng = np.random.RandomState(7)
    trace = [(rng.randint(0, cfg.vocab_size,
                          (1 + i % 6,)).astype(np.int32),
              longest if i % slots == 0 else short)
             for i in range(n_requests)]
    try:
        t0 = time.perf_counter()
        futures = [engine.submit(p, max_new_tokens=m) for p, m in trace]
        results = [f.result(timeout=600) for f in futures]
        wall = time.perf_counter() - t0
        stats = engine.stats()
        tokens = sum(len(r["tokens"]) for r in results)
        leg = {
            "admission": admission,
            "slots": slots,
            "sequences": len(results),
            "tokens": tokens,
            "tokens_per_s": round(tokens / wall, 2),
            "warmup_s": round(warmup_s, 3),
            "steps": stats["steps"],
            "prefill_admits": stats["prefill_admits"],
            "evictions": stats["evictions"],
            "peak_occupancy": round(stats["peak_occupancy"], 3),
            "token_p50_ms": (round(stats["token_p50_ms"], 3)
                             if stats["token_p50_ms"] is not None else None),
            "token_p99_ms": (round(stats["token_p99_ms"], 3)
                             if stats["token_p99_ms"] is not None else None),
            "errors": stats["errors"],
            "recompiles_after_warmup": stats["recompiles_after_warmup"],
        }
        assert leg["recompiles_after_warmup"] == 0, (
            "%s decode recompiled %d time(s) after warmup"
            % (admission, leg["recompiles_after_warmup"]))
        assert leg["errors"] == 0, (
            "%d decode errors (%s)" % (leg["errors"], admission))
        assert leg["tokens_per_s"] > 0, "no decode throughput"
        assert leg["peak_occupancy"] > 0, (
            "slot occupancy never moved (%s)" % admission)
        return leg
    finally:
        engine.close()


def serve_decode_main(smoke: bool):
    """``bench.py --serve-decode`` (and the ``--smoke --serve-decode``
    CI leg): continuous vs static batching head-to-head on the lm1b
    model family — same trained runner, same request trace, same slot
    count; report tokens/s and per-token p50/p99 per admission policy.
    Continuous batching must sustain strictly higher tokens/s at
    equal-or-better per-token p99, with zero recompiles after warmup
    asserted on both legs."""
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy as S
    from autodist_tpu.models import lm

    cfg = lm.LMConfig.tiny() if smoke else lm.LMConfig(
        vocab_size=8192, d_model=256, num_layers=4, num_heads=8,
        mlp_dim=1024, max_seq_len=64)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16 if smoke else 32, batch_size=8)
    adt.reset()
    ad = adt.AutoDist(strategy_builder=S.PS())
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    runner.run(batch)  # one train step: decode params that actually moved

    legs = {}
    for admission in ("continuous", "static"):
        legs[admission] = _serve_decode_leg(runner, cfg, admission, smoke)
        print("  decode %s: %s tokens/s, token p50 %s ms, p99 %s ms"
              % (admission, legs[admission]["tokens_per_s"],
                 legs[admission]["token_p50_ms"],
                 legs[admission]["token_p99_ms"]),
              file=sys.stderr, flush=True)
    cont, stat = legs["continuous"], legs["static"]
    speedup = cont["tokens_per_s"] / max(stat["tokens_per_s"], 1e-9)
    assert cont["tokens_per_s"] > stat["tokens_per_s"], (
        "continuous batching (%.1f tok/s) did not beat static (%.1f "
        "tok/s)" % (cont["tokens_per_s"], stat["tokens_per_s"]))
    # per-step compute is shape-fixed, so per-token p99 should be on par;
    # 25% covers scheduler jitter on shared CI runners
    if cont["token_p99_ms"] is not None and stat["token_p99_ms"]:
        assert cont["token_p99_ms"] <= stat["token_p99_ms"] * 1.25, (
            "continuous p99 %.2fms regressed past static %.2fms"
            % (cont["token_p99_ms"], stat["token_p99_ms"]))
    result = {"metric": "serve_decode", "smoke": smoke,
              "continuous": cont, "static": stat,
              "speedup": round(speedup, 3)}
    result.update(_smoke_telemetry())
    adt.reset()
    _print_result(result)


def autoscale_main(osc: bool = False):
    """``bench.py --autoscale [--osc]`` — the load-adaptive serving leg
    standalone: the seeded 2→4→2 phantom-peer ramp (CI), or the
    oscillating-load hysteresis leg (``--osc``, nightly chaos). Unlike
    the best-effort smoke wiring, a failed assertion here FAILS the
    process — this is the enforcement entry CI runs."""
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    rng = np.random.RandomState(0)
    params = {"w1": rng.randn(16, 32).astype(np.float32) * 0.1,
              "b1": np.zeros((32,), np.float32),
              "w2": rng.randn(32, 4).astype(np.float32) * 0.1}

    def loss_fn(p, b):
        import jax.numpy as jnp
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    batches = [{"x": rng.randn(32, 16).astype(np.float32),
                "y": rng.randn(32, 4).astype(np.float32)}
               for _ in range(16)]
    result = {"metric": "autoscale",
              "autoscale": _smoke_autoscale(loss_fn, params, batches,
                                            osc=osc)}
    if "error" in result["autoscale"]:
        _print_result(result)
        raise SystemExit("autoscale leg failed: %s"
                         % result["autoscale"]["error"])
    import autodist_tpu as adt
    adt.reset()
    _print_result(result)


def child_main(label):
    """Run one model and print its result dict, tagged, as the last line."""
    from autodist_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    budget = float(os.environ.get("ADT_BENCH_MODEL_BUDGET_S", "600"))
    deadline = time.perf_counter() + budget
    if label == "bert_base":
        # ALL candidate operating points measured in ONE artifact run;
        # the headline is the artifact winner — never a one-off probe
        # (VERDICT-r4 #4: the table must quote the artifact). 160 is the
        # probed sweet spot (192 flat, 256 RESOURCE_EXHAUSTs).
        # winner-first order: if the budget kills the child mid-sweep,
        # the headline operating point is already measured
        batches = (160, 128, 64)
        res, results = None, {}
        for i, bs in enumerate(batches):
            share = (deadline - time.perf_counter()) / (len(batches) - i)
            try:
                r = bench_model(label, deadline=time.perf_counter() + share,
                                batch_size=bs)
            except Exception as e:  # noqa: BLE001 — one operating point
                # near the OOM cliff must not discard the others' results
                r = {"error": "%s: %s" % (type(e).__name__, str(e)[:160])}
                print("  bert batch %d failed: %s" % (bs, r["error"]),
                      file=sys.stderr, flush=True)
            results["batch_%d" % bs] = r
            if "examples_per_sec" in r and (
                    res is None
                    or r["examples_per_sec"] > res["examples_per_sec"]):
                res = r
        if res is None:
            raise RuntimeError("every bert operating point failed: %s"
                               % results)
        res = dict(res)
        res.update(results)
    else:
        res = bench_model(label, deadline=deadline)
    _print_result(res)


def _run_tagged_child(args, timeout, child_box, env=None):
    """Spawn a tagged child of this script (one model), enforce the
    hard timeout (killing the child's whole process group, guarded
    against it exiting in the race window), and return
    (parsed result dict | None, error string | None)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)] + list(args),
        stdout=subprocess.PIPE, env=env, start_new_session=True, text=True)
    child_box[0] = proc
    try:
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.communicate()
            return None, "timeout"
    finally:
        child_box[0] = None
    tagged = [ln for ln in out.splitlines() if ln.startswith(RESULT_TAG)]
    if proc.returncode == 0 and tagged:
        return json.loads(tagged[-1][len(RESULT_TAG):]), None
    return None, "child rc=%s, no result" % proc.returncode


def _emit(models):
    """Print the cumulative result line (full schema, always valid)."""
    skipped = sorted(k for k, m in models.items() if "skipped" in m)
    failed = sorted(k for k, m in models.items() if "error" in m)
    ran = {k: m for k, m in models.items() if "vs_baseline" in m}
    worst = min((m["vs_baseline"] for m in ran.values()), default=0.0)
    # headline: resnet50 if it ran, else any model that did
    head_key = "resnet50" if "resnet50" in ran else (
        sorted(ran)[0] if ran else None)
    result = {
        "metric": ("%s_train_examples_per_sec" % head_key) if head_key
        else "bench_incomplete",
        "value": ran[head_key]["examples_per_sec"] if head_key else 0.0,
        "unit": "examples/s",
        # min across the models that RAN; "skipped_models" flags any the
        # budget dropped, so coverage is explicit
        "vs_baseline": worst,
        # the parent never touches JAX: the device is the one the
        # children report (identical across them — same machine)
        "device": next((m["device"] for m in ran.values()
                        if "device" in m), None),
        "models": models,
    }
    if skipped:
        result["skipped_models"] = skipped
    if failed:
        # crashes are NOT budget skips: flag them distinctly so a green
        # vs_baseline over the survivors cannot mask a real failure
        result["failed_models"] = failed
    print(json.dumps(result), flush=True)


def main():
    budget_s = float(os.environ.get("ADT_BENCH_BUDGET_S", "1380"))
    per_model_cap = float(os.environ.get("ADT_BENCH_MODEL_CAP_S", "600"))
    labels = [s for s in os.environ.get(
        "ADT_BENCH_MODELS", ",".join(MODEL_LABELS)).split(",") if s]
    t_start = time.perf_counter()
    models = {label: {"skipped": "not reached"} for label in labels}

    _emit(models)  # a parseable line exists from second zero

    child_box = [None]

    def _on_term(signum, frame):  # noqa: ARG001
        proc = child_box[0]
        if proc is not None and proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
        # the cumulative line for everything finished so far is already on
        # stdout; just leave cleanly
        sys.exit(1)

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    for i, label in enumerate(labels):
        elapsed = time.perf_counter() - t_start
        remaining = budget_s - elapsed
        # skip once out of budget after ANY attempt (a timed-out attempt
        # consumed the budget just the same as a success)
        if i and remaining < 180:
            models[label] = {"skipped": "bench budget"}
            _emit(models)
            print("  skipping %s: %.0fs elapsed, budget %.0fs"
                  % (label, elapsed, budget_s), file=sys.stderr, flush=True)
            continue
        _run_model(label, models, remaining, per_model_cap, child_box)
        _emit(models)
    # a benchmark that measured nothing, or lost a model, did not succeed
    if (any("error" in m for m in models.values())
            or not any("vs_baseline" in m for m in models.values())):
        sys.exit(1)


def _run_model(label, models, remaining, per_model_cap, child_box):
    """Run one model in a child subprocess with a hard timeout; record its
    result (or error) in ``models``."""
    floor = float(os.environ.get("ADT_BENCH_MODEL_FLOOR_S", "120"))
    grace = float(os.environ.get("ADT_BENCH_HARD_GRACE_S", "180"))
    soft = max(floor, min(remaining - 60.0, per_model_cap))
    hard = soft + grace  # grace for in-flight compile/phase to land
    env = dict(os.environ, ADT_BENCH_MODEL_BUDGET_S=str(soft))
    t_model = time.perf_counter()
    try:
        res, err = _run_tagged_child(["--model", label], hard, child_box,
                                     env=env)
        if err == "timeout":
            models[label] = {"error": "timeout after %.0fs" % hard}
            print("  %s TIMED OUT (%.0fs hard limit)" % (label, hard),
                  file=sys.stderr, flush=True)
        elif res is not None:
            models[label] = res
            print("  %s done in %.0fs" % (
                label, time.perf_counter() - t_model),
                file=sys.stderr, flush=True)
        else:
            models[label] = {"error": err}
    except Exception as e:  # noqa: BLE001 — one flaky model must not
        # cost the whole artifact
        models[label] = {"error": "%s: %s"
                         % (type(e).__name__, str(e)[:200])}


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--model":
        child_main(sys.argv[2])
    elif "--autoscale" in sys.argv[1:]:
        autoscale_main(osc="--osc" in sys.argv[1:])
    elif "--serve-decode" in sys.argv[1:]:
        serve_decode_main(smoke="--smoke" in sys.argv[1:])
    elif "--serve" in sys.argv[1:]:
        serve_main(smoke="--smoke" in sys.argv[1:])
    elif len(sys.argv) >= 2 and sys.argv[1] == "--smoke":
        smoke_main(fused="--fused" in sys.argv[2:])
    else:
        main()
