#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process drives the two program paths the system exists for, through
the entry points a user calls, at the FULL width of the lm1b transformer
(d1024 / L8 / H16 / mlp 4096 / vocab 99,183, bf16, batch 64, random
weights from a seed):

  P0  device gate      platform / device_kind / counts / versions / cache
  P1  train            make_train_setup -> AutoDist(AllReduce).build ->
                       Runner.init -> Runner.run over ALL local chips
  P3  decode           DecodeEngine on P1's trained runner
  P2  host PS          AutoDist() with no builder (PSLoadBalancing)
  P4  kernels          the pallas flash kernels, compiled, vs the reference

(P3 runs right after P1 because it serves P1's runner; the names follow
ISSUE 21.) One line per phase, ``CHIP_SMOKE <phase> PASS|FAIL {json}``,
a ``CHIP_SMOKE summary {json}`` line, ``CHIP_SMOKE PASS`` / ``CHIP_SMOKE
FAIL <phases>``, and as the LAST line of stdout the result, one JSON
object with exactly these keys:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``ok`` is true and the exit code 0 only if every phase passed. The
numbers printed are diagnostics of THIS run, not benchmark results.

There is no CPU path: without a TPU whose ``device_kind`` the chip table
knows — or without the ``autodist_tpu`` package beside this script — it
exits non-zero before building anything and prints no result line.
``--rehearse-on-cpu`` runs the same phases at a tiny size on the CPU
backend (kernels interpreted) to debug the script before spending chip
time; it proves nothing about the chip, prints no result line and is
never reached by fallback.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback
import warnings

import numpy as np

T_START = time.perf_counter()


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything the two modes size differently."""
    train_seq: int
    batch: int
    flash_seq: int          # make_train_setup(attention="flash") step
    kernel_seqs: tuple      # direct flash_attention shapes [2, S, H, D]
    prefill_len: int
    max_new: int


FULL = Sizes(train_seq=128, batch=64, flash_seq=256,
             kernel_seqs=(1024, 256), prefill_len=16, max_new=32)
REHEARSAL = Sizes(train_seq=16, batch=8, flash_seq=32,
                  kernel_seqs=(64, 32), prefill_len=8, max_new=12)

TRAIN_STEPS = 8
PS_STEPS = 3
N_PROMPTS = 20
# bf16 agreement bounds, each the one the repo's tests already use
BF16_FWD_ATOL = 3e-2        # tests/test_flash_attention.py bf16 forward
BF16_GRAD_TOL = 5e-2        # tests/test_flash_attention.py bf16 grads
BF16_LOSS_RTOL = 1e-2       # 1-chip vs n-chip loss, flash vs XLA loss


def say(phase, ok, **detail):
    print("CHIP_SMOKE %s %s %s" % (phase, "PASS" if ok else "FAIL",
                                   json.dumps(detail, sort_keys=True)),
          flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def result_line(p0, failed):
    """The last line of stdout: exactly these keys and no other, the
    device as JAX reports it (the driver's check reads nothing else)."""
    return json.dumps({
        "ok": not failed,
        "device": {"platform": p0["platform"], "kind": p0["device_kind"],
                   "count": p0["devices"]}})


# ------------------------------------------------------------------ P0

def phase_device_gate(rehearse):
    """Name the machine; refuse anything that is not a TPU the chip table
    knows (or, under --rehearse-on-cpu, anything that is not the CPU)."""
    import jax
    import jaxlib
    from autodist_tpu.resource_spec import CHIP_TABLE, ResourceSpec
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    info = {"platform": dev.platform, "device_kind": dev.device_kind,
            "local_devices": jax.local_device_count(),
            "devices": jax.device_count(),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": (
                len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
                else 0)}
    want = "cpu" if rehearse else "tpu"
    if dev.platform != want:
        say("P0", False, **info)
        sys.exit("chip_smoke: jax's default platform is %r, need %r%s"
                 % (dev.platform, want,
                    "" if rehearse else " (no accelerator found)"))
    # raises, naming slice.type / slice.hbm_gib, on a kind the chip table
    # does not know
    spec = ResourceSpec.from_local()
    chip = CHIP_TABLE[spec.chip_kind()]
    info.update(chip_kind=spec.chip_kind(),
                hbm_budget_bytes=spec.chip_hbm_bytes(),
                peak_bf16_flops=chip.peak_bf16_flops)
    say("P0", True, **info)
    return info


# ------------------------------------------------------------------ P1

def build_runner(cfg, sizes, builder=None, resource_spec=None, **setup_kw):
    import optax
    import autodist_tpu as adt
    from autodist_tpu.models import lm
    loss_fn, params, batch, apply_fn = lm.make_train_setup(
        cfg, seq_len=setup_kw.pop("seq_len", sizes.train_seq),
        batch_size=sizes.batch, **setup_kw)
    ad = adt.AutoDist(strategy_builder=builder, resource_spec=resource_spec)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    return runner, batch, apply_fn


def one_chip_losses(cfg, sizes, chip_kind, steps):
    """The same model, batch and seed on a ONE-chip resource spec in this
    process — the reference the all-chip losses must agree with."""
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.resource_spec import ResourceSpec
    node = {"address": "127.0.0.1", "chief": True}
    node.update({"cpus": [0]} if chip_kind == "cpu" else {"tpus": 1})
    spec = ResourceSpec.from_dict({"nodes": [node],
                                   "slice": {"type": chip_kind}})
    runner, batch, _ = build_runner(cfg, sizes, strategy.AllReduce(), spec)
    check(runner.remapper.num_replicas == 1, "one-chip spec built %d "
          "replicas" % runner.remapper.num_replicas)
    losses = [float(runner.run(batch)["loss"]) for _ in range(steps)]
    del runner
    adt.reset()
    gc.collect()
    return losses


def phase_train(cfg, sizes, p0, rehearse):
    import jax
    from autodist_tpu import strategy
    from autodist_tpu.resource_spec import CHIP_TABLE
    chip = CHIP_TABLE[p0["chip_kind"]]
    n = jax.device_count()
    out = {}
    ref_losses = None
    if n > 1:
        ref_losses = one_chip_losses(cfg, sizes, p0["chip_kind"], 4)
        out["one_chip_losses"] = [round(x, 4) for x in ref_losses]

    t0 = time.perf_counter()
    runner, batch, apply_fn = build_runner(cfg, sizes, strategy.AllReduce())
    dstep = runner.distributed_step
    losses = [float(runner.run(batch)["loss"])]
    out["setup_s"] = round(time.perf_counter() - t0, 2)
    losses += [float(runner.run(batch)["loss"]) for _ in range(3)]

    # steady window: dispatch without per-step readback, then wait for
    # the device once
    k = TRAIN_STEPS - len(losses)
    t0 = time.perf_counter()
    handles = [runner.run(batch, sync=False) for _ in range(k)]
    jax.block_until_ready(runner.state)
    step_s = (time.perf_counter() - t0) / k
    losses += [float(h["loss"]) for h in handles]
    # the same step timed by value readback
    t0 = time.perf_counter()
    losses.append(float(runner.run(batch)["loss"]))
    readback_step_s = time.perf_counter() - t0
    out.update(losses=[round(x, 4) for x in losses],
               step_ms_block_until_ready=round(step_s * 1e3, 2),
               step_ms_value_readback=round(readback_step_s * 1e3, 2))

    check(all(np.isfinite(losses)), "non-finite loss: %s" % losses)
    check(losses[-1] < losses[0], "loss did not fall: %s" % losses)
    check(runner.remapper.num_replicas == n,
          "%d replicas over %d devices" % (runner.remapper.num_replicas, n))
    out["replicas"] = runner.remapper.num_replicas

    mesh_devices = set(dstep.mesh.devices.flat)
    check(len(mesh_devices) == n, "mesh spans %d of %d devices"
          % (len(mesh_devices), n))
    for leaf in jax.tree_util.tree_leaves(runner.state):
        check(set(leaf.sharding.device_set) == mesh_devices
              and len(leaf.addressable_shards) == n,
              "a state leaf %s lives on %s, not on every mesh device"
              % (leaf.shape, sorted(d.id for d in leaf.sharding.device_set)))
    placed = runner.remapper.remap_feed(batch)
    shards = placed["tokens"].addressable_shards
    check(len({s.device for s in shards}) == n
          and len({str(s.index) for s in shards}) == n
          and all(s.data.shape[0] == sizes.batch // n for s in shards),
          "batch shards are not one distinct slice per device: %s"
          % [(s.device.id, s.index) for s in shards])
    out["batch_shard_devices"] = sorted(s.device.id for s in shards)

    compiled = dstep._step_fn._cache_size()
    check(compiled == 1, "step program compiled %d times" % compiled)
    out["step_compiles"] = compiled
    check(runner._hbm_budget == p0["hbm_budget_bytes"],
          "Runner HBM budget %s != chip table %s"
          % (runner._hbm_budget, p0["hbm_budget_bytes"]))

    if n > 1:
        text = runner.lowered_text(batch, donate=True)
        check("all_reduce" in text or "all-reduce" in text,
              "no all-reduce in the lowered %d-replica step" % n)
        out["all_reduce_in_step"] = True
        gap = max(abs(a - b) / abs(b)
                  for a, b in zip(losses[:4], ref_losses))
        out["one_chip_loss_max_rel_gap"] = float("%.3g" % gap)
        check(gap <= BF16_LOSS_RTOL, "%d-chip losses %s disagree with "
              "one-chip %s (rel gap %.3g)" % (n, losses[:4], ref_losses,
                                              gap))

    if rehearse:
        out["device_metrics"] = "not measured (CPU rehearsal)"
    else:
        peaks = [d.memory_stats()["peak_bytes_in_use"]
                 for d in jax.local_devices()]
        out["peak_bytes_in_use"] = peaks
        check(max(peaks) < chip.hbm_bytes, "peak device memory %s >= the "
              "chip table's HBM %g" % (peaks, chip.hbm_bytes))
        # the benchmark's closed form (recomputation is not counted)
        from benchmark.families import lm as family
        flops = sizes.batch * sizes.train_seq * family.train_flops_per_token(
            {k: getattr(cfg, k) for k in family.SIZE_KEYS},
            {"seq": sizes.train_seq})
        rate = flops / step_s / n
        out.update(model_flops_per_step=flops,
                   implied_flops_per_chip=float("%.4g" % rate),
                   implied_share_of_peak=round(rate / chip.peak_bf16_flops,
                                               3))
        # a step that "finished" faster than the chip's peak allows means
        # block_until_ready returned before the device did
        check(rate < chip.peak_bf16_flops, "step time %.4gs implies %.3g "
              "FLOP/s per chip, above the %g peak: block_until_ready "
              "is not waiting for the device"
              % (step_s, rate, chip.peak_bf16_flops))
    say("P1", True, **out)
    return runner, apply_fn


# ------------------------------------------------------------------ P3

def phase_decode(runner, apply_fn, cfg, sizes):
    import jax.numpy as jnp
    from autodist_tpu import telemetry
    from autodist_tpu.models import lm
    from autodist_tpu.serving.decode import DecodeConfig, DecodeEngine
    replicas = runner.remapper.num_replicas
    slots = max(8 // replicas, 1) * replicas
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (1 + (5 * i) % sizes.prefill_len,)).astype(np.int32)
               for i in range(N_PROMPTS)]
    # one long generation per few short ones: freed slots re-admit while
    # the long ones are still mid-generation
    caps = [sizes.max_new if i % 3 == 0 else 2 + (7 * i) % (sizes.max_new // 2)
            for i in range(N_PROMPTS)]

    telemetry.configure("1")  # the admit/step spans below are the proof
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        engine = DecodeEngine(runner, lm.make_decode_setup(cfg), DecodeConfig(
            slots=slots, prefill_len=sizes.prefill_len,
            max_new_tokens=sizes.max_new))
        engine.warmup()
        setup_s = time.perf_counter() - t0
        baseline = engine._caches_after_warmup
        futures = [engine.submit(p, c) for p, c in zip(prompts, caps)]
        results = [f.result(timeout=600) for f in futures]
        stats = engine.stats()
        engine.close()
    events = sorted((e for e in telemetry.get_recorder().events()
                     if e.name in ("serve.prefill", "serve.decode_step")),
                    key=lambda e: e.ts_ns)
    telemetry.configure(None)
    donation_warnings = sorted({str(w.message).split(":")[0] for w in caught
                                if "donated" in str(w.message)})

    check(len(results) == N_PROMPTS, "not every future resolved")
    check(stats["errors"] == 0, "decode loop caught %d dispatch failure(s)"
          % stats["errors"])
    check(stats["completed"] == N_PROMPTS, "completed %d of %d"
          % (stats["completed"], N_PROMPTS))
    for r, cap in zip(results, caps):
        check(len(r["tokens"]) == cap and r["finished"] == "length",
              "a sequence stopped at %d of %d tokens (%s)"
              % (len(r["tokens"]), cap, r["finished"]))
    # zero recompiles, with the introspection proven live: the baseline
    # is a real positive count of compiled specializations
    check(isinstance(baseline, int) and baseline >= 2
          and engine._prefill._cache_size_after_warmup >= 1,
          "jit cache introspection is not live (%r)" % (baseline,))
    check(stats["recompiles_after_warmup"] == 0,
          "%d recompile(s) after warmup" % stats["recompiles_after_warmup"])
    # in-flight admission: a prefill group of n after which MORE than n
    # slots decode — the surplus were mid-generation across the admission
    inflight = sum(
        1 for a, b in zip(events, events[1:])
        if a.name == "serve.prefill" and b.name == "serve.decode_step"
        and b.args["live"] > a.args["n"])
    check(inflight >= 1, "no admission happened while other slots were "
          "mid-generation")

    # full-sequence recompute through runner.predict, teacher-forced on
    # the engine's own tokens (causality makes that the greedy recompute):
    # at every generated position the engine's token must be the
    # recompute's argmax, or tie with it within bf16 rounding
    width = sizes.prefill_len + sizes.max_new
    rows = -(-N_PROMPTS // replicas) * replicas
    tokens = np.zeros((rows, width), np.int32)
    picked = np.zeros((rows, width), np.int32)
    mask = np.zeros((rows, width), bool)
    for i, (p, r) in enumerate(zip(prompts, results)):
        seq = np.concatenate([p, r["tokens"]])
        tokens[i, :len(seq)] = seq
        picked[i, len(p) - 1:len(seq) - 1] = r["tokens"]
        mask[i, len(p) - 1:len(seq) - 1] = True

    def serve_fn(params, batch):
        logits = apply_fn(params, batch["tokens"]).astype(jnp.float32)
        top = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, batch["picked"][..., None],
                                   axis=-1)[..., 0]
        return {"argmax": jnp.argmax(logits, axis=-1).astype(jnp.int32),
                "top": top, "deficit": top - mine}

    ref = runner.predict({"tokens": tokens, "picked": picked}, serve_fn)
    agree = np.asarray(ref["argmax"]) == picked
    exact = agree[mask]
    exact_seqs = int(np.all(agree | ~mask, axis=1)[:N_PROMPTS].sum())
    deficit = np.asarray(ref["deficit"])[mask]
    bound = BF16_FWD_ATOL * np.maximum(1.0, np.abs(np.asarray(ref["top"])[mask]))
    out = {"setup_s": round(setup_s, 2), "slots": slots,
           "requests": N_PROMPTS, "tokens": int(stats["tokens"]),
           "steps": int(stats["steps"]),
           "prefill_admits": int(stats["prefill_admits"]),
           "inflight_admissions": inflight,
           "peak_occupancy": stats["peak_occupancy"],
           "recompiles_after_warmup": stats["recompiles_after_warmup"],
           "compiled_programs_at_warmup":
               baseline + engine._prefill._cache_size_after_warmup,
           "token_p50_ms": stats["token_p50_ms"],
           "recompute_exact_tokens": "%d/%d" % (exact.sum(), exact.size),
           "recompute_exact_sequences": "%d/%d" % (exact_seqs, N_PROMPTS),
           "recompute_max_logit_deficit": float("%.3g" % deficit.max()),
           "donation_warnings": donation_warnings}
    check(exact_seqs >= 1, "no sequence equals its full-sequence greedy "
          "recompute token for token: %s" % out)
    check(bool(np.all(deficit <= bound)), "the engine picked a token whose "
          "recomputed logit trails the argmax beyond bf16 rounding: %s" % out)
    say("P3", True, **out)


# ------------------------------------------------------------------ P2

def phase_host_ps(cfg, sizes):
    import jax
    t0 = time.perf_counter()
    runner, batch, _ = build_runner(cfg, sizes)  # default: PSLoadBalancing
    dstep = runner.distributed_step
    store = dstep.ps_store
    check(store is not None, "the default builder lowered no host-PS store")
    losses = [float(runner.run(batch)["loss"])]
    setup_s = time.perf_counter() - t0
    losses += [float(runner.run(batch)["loss"]) for _ in range(PS_STEPS - 1)]
    dstep.flush_ps()
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          "host-PS loss is not finite and falling: %s" % losses)
    check(store.stats["pushes"] == PS_STEPS, "%d pushes after %d steps"
          % (store.stats["pushes"], PS_STEPS))
    check(store.resident_bytes() > 0, "the store holds no bytes")
    infos = dstep.model_item.var_infos
    ps_bytes = sum(infos[n].byte_size for n in store.var_names)
    device_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves((runner.state.params,
                                            runner.state.opt_state)))
    dev_var_bytes = sum(v.byte_size for n, v in infos.items()
                        if n not in store.plans)
    # adam keeps two moments per device-resident var; anything beyond
    # that (plus scalar counts) would be PS state that leaked onto HBM
    check(device_bytes <= 3 * dev_var_bytes + 4096,
          "device state holds %d bytes but only %d bytes of variables "
          "are device-resident" % (device_bytes, dev_var_bytes))
    say("P2", True, setup_s=round(setup_s, 2),
        losses=[round(x, 4) for x in losses], pushes=store.stats["pushes"],
        pulls=store.stats["pulls"], ps_vars=len(store.var_names),
        ps_resident_bytes=store.resident_bytes(), ps_var_bytes=ps_bytes,
        device_state_bytes=device_bytes,
        host_cpu_device=str(store._cpu))


# ------------------------------------------------------------------ P4

def phase_kernels(cfg, sizes, rehearse):
    import jax
    import jax.numpy as jnp
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.models.layers import causal_mask
    from autodist_tpu.ops import flash_attention as fa
    from autodist_tpu.ops.attention import (cached_attention,
                                            flash_cached_attention,
                                            reference_attention)
    heads, dim = cfg.num_heads, cfg.d_model // cfg.num_heads
    out = {"kernels": {}}

    def compiled_not_interpreted(fn, *args):
        """The lowered call holds a Mosaic custom call — it was compiled
        for the chip, not interpreted and not the XLA fallback."""
        if rehearse:
            return
        text = jax.jit(fn).lower(*args).as_text()
        check("tpu_custom_call" in text, "no tpu_custom_call in the "
              "lowered text of %s" % getattr(fn, "__name__", fn))

    def rand(shape, seed):
        return jax.random.normal(jax.random.PRNGKey(seed), shape,
                                 jnp.float32).astype(jnp.bfloat16)

    for seq in sizes.kernel_seqs:
        shape = (2, seq, heads, dim)
        q, k, v = (rand(shape, s) for s in range(3))
        check(fa._tileable(q, k), "seq %d does not tile" % seq)
        # two packed documents then padding, as segment ids
        seg = jnp.broadcast_to(jnp.asarray(
            np.repeat([1, 2, 0], [seq // 2, seq // 4, seq // 4]), jnp.int32),
            (2, seq))
        cases = {"causal": (True, None, causal_mask(seq)),
                 "segment": (False, seg,
                             (seg[:, :, None] == seg[:, None, :])[:, None])}
        for name, (causal, seg_ids, mask) in cases.items():
            def flash(q, k, v):
                return fa.flash_attention(q, k, v, causal=causal,
                                          segment_ids=seg_ids)

            # O(1) cotangent: with a mean-of-squares loss every gradient
            # would sit far below the tolerance and the check be vacuous
            cot = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)

            def flash_loss(q, k, v):
                return jnp.sum(flash(q, k, v).astype(jnp.float32) * cot)

            def ref_loss(q, k, v):
                return jnp.sum(reference_attention(q, k, v, mask) * cot)

            flash_grad = jax.grad(flash_loss, (0, 1, 2))
            compiled_not_interpreted(flash, q, k, v)
            compiled_not_interpreted(flash_grad, q, k, v)
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            with jax.default_matmul_precision("highest"):  # a true f32 ref
                ref_out = jax.jit(reference_attention)(*f32, mask)
                ref_grads = jax.jit(jax.grad(ref_loss, (0, 1, 2)))(*f32)
            fwd_err = float(jnp.max(jnp.abs(
                jax.jit(flash)(q, k, v).astype(jnp.float32) - ref_out)))
            check(fwd_err <= BF16_FWD_ATOL, "flash %s forward at seq %d is "
                  "%.3g from the reference" % (name, seq, fwd_err))
            grad_err = 0.0  # worst |a - b| - rtol * |b|, to stay <= atol
            for a, b in zip(jax.jit(flash_grad)(q, k, v), ref_grads):
                a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b)
                grad_err = max(grad_err, float(np.max(
                    np.abs(a - b) - BF16_GRAD_TOL * np.abs(b))))
            check(grad_err <= BF16_GRAD_TOL, "flash %s backward at seq %d "
                  "exceeds atol/rtol %g by %.3g"
                  % (name, seq, BF16_GRAD_TOL, grad_err))
            out["kernels"]["%s_s%d" % (name, seq)] = {
                "fwd_max_err": float("%.3g" % fwd_err),
                "bwd_err_minus_rtol": float("%.3g" % grad_err)}

    # the decode inner loop at P3's cache shape
    replicas = jax.device_count()
    slots = max(8 // replicas, 1) * replicas
    cache = (slots, cfg.max_seq_len, heads, dim)
    q1, kc, vc = rand((slots, heads, dim), 3), rand(cache, 4), rand(cache, 5)
    cursor = jnp.asarray(np.linspace(0, cfg.max_seq_len - 1, slots), jnp.int32)
    compiled_not_interpreted(flash_cached_attention, q1, kc, vc, cursor)
    got = jax.jit(flash_cached_attention)(q1, kc, vc, cursor)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(cached_attention)(
            *(x.astype(jnp.float32) for x in (q1, kc, vc)), cursor)
    cached_err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    check(cached_err <= BF16_FWD_ATOL, "flash_cached_attention is %.3g "
          "from cached_attention at %s" % (cached_err, cache))
    out["flash_cached_max_err"] = float("%.3g" % cached_err)
    out["cache_shape"] = list(cache)

    # one train step with the kernel forced, against the XLA-attention
    # loss of the same parameters and batch
    from autodist_tpu.models import lm
    t0 = time.perf_counter()
    runner, batch, _ = build_runner(cfg, sizes, strategy.AllReduce(),
                                    seq_len=sizes.flash_seq,
                                    attention="flash")
    if not rehearse:
        check("tpu_custom_call" in runner.lowered_text(batch, donate=True),
              "no tpu_custom_call in the attention=\"flash\" train step")
    flash_loss = float(runner.run(batch)["loss"])
    out["flash_step_setup_s"] = round(time.perf_counter() - t0, 2)
    del runner
    adt.reset()
    gc.collect()
    xla_loss_fn, params, xla_batch, _ = lm.make_train_setup(
        cfg, seq_len=sizes.flash_seq, batch_size=sizes.batch,
        attention="default")
    xla_loss = float(jax.jit(xla_loss_fn)(params, xla_batch))
    check(np.isfinite(flash_loss) and
          abs(flash_loss - xla_loss) <= BF16_LOSS_RTOL * abs(xla_loss),
          "attention=\"flash\" step loss %.5f vs XLA attention %.5f"
          % (flash_loss, xla_loss))
    out.update(flash_step_loss=round(flash_loss, 4),
               xla_attention_loss=round(xla_loss, 4),
               compiled_not_interpreted=not rehearse)
    say("P4", True, **out)


# ---------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="tiny-size run on the CPU backend to debug this "
                         "script; proves nothing about the chip")
    args = ap.parse_args(argv)
    rehearse = args.rehearse_on_cpu
    if rehearse:
        print("*" * 72 + "\nCPU REHEARSAL: tiny sizes, interpreted kernels, "
              "no device metric.\nThis run proves NOTHING about the chip and "
              "prints no result line.\n" + "*" * 72, flush=True)

    import jax
    import jax.numpy as jnp
    try:
        import autodist_tpu as adt
        from autodist_tpu.models.lm import LMConfig
    except ImportError as e:
        sys.exit("chip_smoke: no autodist_tpu package beside %s (%s) — "
                 "this script drives the repo, it is nothing alone"
                 % (os.path.abspath(__file__), e))

    cache_events = {}
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.__setitem__(
            event, cache_events.get(event, 0) + 1))

    p0 = phase_device_gate(rehearse)
    if rehearse:
        # wide enough that the lean (chunked) head engages as at lm1b
        cfg = LMConfig(vocab_size=32768, d_model=64, num_layers=2,
                       num_heads=4, mlp_dim=128, max_seq_len=64,
                       dtype=jnp.bfloat16)
        sizes = REHEARSAL
    else:
        cfg = LMConfig.lm1b(dtype=jnp.bfloat16)
        sizes = FULL

    failed = []

    def run(phase, fn, *a):
        """A phase that raises is reported and FAILS the run; later
        phases still run so one command shows everything that is broken."""
        t0 = time.perf_counter()
        try:
            return fn(*a)
        except Exception as e:  # noqa: BLE001 — reported, never swallowed:
            # the phase is recorded as failed and the exit code is non-zero
            traceback.print_exc()
            say(phase, False, error="%s: %s" % (type(e).__name__,
                                                str(e)[:400]),
                after_s=round(time.perf_counter() - t0, 1))
            failed.append(phase)
            return None

    trained = run("P1", phase_train, cfg, sizes, p0, rehearse)
    if trained is None:
        say("P3", False, error="needs P1's trained runner")
        failed.append("P3")
    else:
        run("P3", phase_decode, trained[0], trained[1], cfg, sizes)
    del trained
    adt.reset()
    gc.collect()
    run("P2", phase_host_ps, cfg, sizes)
    adt.reset()
    gc.collect()
    run("P4", phase_kernels, cfg, sizes, rehearse)
    adt.reset()

    hits = cache_events.get("/jax/compilation_cache/cache_hits", 0)
    misses = cache_events.get("/jax/compilation_cache/cache_misses", 0)
    print("CHIP_SMOKE summary %s" % json.dumps(
        {"compile_cache": {"dir": p0["compile_cache_dir"], "hits": hits,
                           "misses": misses, "entries_at_start":
                               p0["compile_cache_entries_at_start"]},
         "failed": failed,
         "total_s": round(time.perf_counter() - T_START, 1),
         "claim": None}), flush=True)
    print("CHIP_SMOKE FAIL %s" % ",".join(failed) if failed
          else "CHIP_SMOKE PASS", flush=True)
    if not rehearse:
        print(result_line(p0, failed), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
