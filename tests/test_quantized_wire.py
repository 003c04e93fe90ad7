"""Quantized wire collectives (blockwise int8 AR + PS push/pull).

Pins the PR's contracts end to end: the blockwise codec (round-trip
bound, NaN poisoning, host/device bit-equality), the EQuARX two-phase
all-reduce (sum accuracy, SPMD bit-identity, all_to_all+all_gather
lowering), training parity of the quantized wire vs fp32 on both the
AllReduce and host-PS paths (per-step AND fused k=4), the ADT310/311
diagnostics and the search-space canon that never emits them, the
byte-accounting agreement between the telemetry counters, the cost
model, and the ADT5xx measured profile, degraded PS pulls dequantizing
the last-good snapshot, and the PR 6 searcher choosing
``wire_dtype=int8`` on its own when bandwidth-bound.
"""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.model_item import ModelItem
from autodist_tpu.parallel import collectives as C
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.telemetry import spans as tel


# ------------------------------------------------------------------ codec


def test_block_codec_roundtrip_bound():
    """Per-element error is bounded by its OWN block's absmax/127 —
    tighter than a per-tensor scale when magnitudes vary across blocks."""
    rng = np.random.RandomState(0)
    # block 0 small-magnitude, block 1 large: per-tensor scaling would
    # wipe out block 0's resolution
    x = np.concatenate([rng.randn(64).astype(np.float32) * 1e-3,
                        rng.randn(64).astype(np.float32) * 1e3])
    q, s = C.quant_i8_block(jnp.asarray(x), block=64)
    back = np.asarray(C.dequant_i8_block(q, s, 128))
    for b in range(2):
        sl = slice(64 * b, 64 * (b + 1))
        bound = np.abs(x[sl]).max() / 127.0 + 1e-12
        assert np.abs(back[sl] - x[sl]).max() <= bound * 1.0001
    # one scale for the whole tensor CANNOT hit block 0's bound (sanity of
    # "blockwise"): the same codec with the tensor as its one block
    qt, st = C.quant_i8_block(jnp.asarray(x), block=128)
    back_t = np.asarray(C.dequant_i8_block(qt, st, 128))
    assert (np.abs(back_t[:64] - x[:64]).max()
            > np.abs(back[:64] - x[:64]).max() * 10)


def test_block_codec_padding_and_nan_poisoning():
    x = np.arange(100, dtype=np.float32)  # not a block multiple
    q, s = C.quant_i8_block(jnp.asarray(x), block=32)
    assert q.shape == (4, 32) and s.shape == (4,)
    back = np.asarray(C.dequant_i8_block(q, s, 100))
    assert back.shape == (100,)
    # a NaN poisons ITS block's scale (divergence must propagate), the
    # other blocks stay finite
    x[5] = np.nan
    q, s = C.quant_i8_block(jnp.asarray(x), block=32)
    s = np.asarray(s)
    assert not np.isfinite(s[0]) and np.isfinite(s[1:]).all()


def test_host_and_device_codec_bitwise_equal():
    """quant_wire_np (the PS store's host side) and quant_wire (the
    in-graph side) must produce identical bytes — the fused engine's
    in-scan codec emulation depends on it."""
    rng = np.random.RandomState(1)
    arr = rng.randn(37, 11).astype(np.float32) * 3.7
    w_host = C.quant_wire_np(arr)
    w_dev = jax.tree_util.tree_map(np.asarray, C.quant_wire(arr))
    np.testing.assert_array_equal(w_host["q"], w_dev["q"])
    np.testing.assert_array_equal(w_host["s"], w_dev["s"])
    back = C.dequant_wire_np(w_host, (37, 11))
    np.testing.assert_array_equal(
        back, np.asarray(C.dequant_wire(w_dev, (37, 11))))
    # aval stand-ins match the real containers exactly
    av = C.wire_avals((37, 11))
    assert av["q"].shape == w_host["q"].shape
    assert av["s"].shape == w_host["s"].shape


def test_error_feedback_residual_is_wire_error():
    """residual + quantized image == the compensated gradient, exactly —
    the EF invariant that preserves the sum of updates."""
    rng = np.random.RandomState(2)
    g = rng.randn(300).astype(np.float32) * 1e-2
    q, s = C.quant_i8_block(jnp.asarray(g), block=64)
    image = np.asarray(C.dequant_i8_block(q, s, 300))
    residual = g - image
    np.testing.assert_allclose(residual + image, g, rtol=0, atol=1e-7)


def test_int8_wire_payload_bytes_formula():
    q, f = C.int8_wire_payload_bytes(1000, 4, block=256)
    assert f == 4000
    assert q == 4 * 256 + 4 * 4  # padded int8 body + f32 sidecar
    # sub-block payload: sidecar + padding exceed the saving (ADT311)
    q_small, f_small = C.int8_wire_payload_bytes(8, 4, block=256)
    assert q_small > f_small


# -------------------------------------------------- two-phase all-reduce


def test_int8_block_all_reduce_two_phase():
    """Sum accuracy, SPMD bit-identity, and the EQuARX lowering shape:
    ONE all_to_all (the int8 reduce-scatter) + all_gather — not the
    2(n-1)-hop ppermute ring."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("data",))
    rng = np.random.RandomState(0)
    L = 1000  # not divisible by 8 -> exercises chunk/block padding
    x = rng.randn(8, L).astype(np.float32)
    fn = jax.jit(jax.shard_map(
        lambda xs: C.int8_block_all_reduce(xs.reshape(-1), "data", 8),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    got = np.asarray(fn(x.reshape(8 * L))).reshape(8, L)
    exact = x.sum(axis=0)
    # every replica holds bit-identical reduced values
    assert np.max(np.abs(got - got[0])) == 0.0
    rel = np.abs(got[0] - exact) / (np.abs(exact) + 1e-6)
    assert np.median(rel) < 0.03, np.median(rel)
    hlo = fn.lower(x.reshape(8 * L)).as_text()
    assert "all_to_all" in hlo and "all_gather" in hlo
    assert "collective_permute" not in hlo


# --------------------------------------------------------- training parity


def _mlp_setup(seed=0, din=64, dout=8, batch=32):
    rng = np.random.RandomState(seed)
    params = {"w": jnp.asarray(rng.randn(din, dout) * 0.1, jnp.float32),
              "v": jnp.asarray(rng.randn(dout, dout) * 0.1, jnp.float32)}
    batch_np = {"x": rng.randn(batch, din).astype(np.float32),
                "y": rng.randn(batch, dout).astype(np.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w"])
        return jnp.mean((h @ p["v"] - b["y"]) ** 2)

    return loss_fn, params, batch_np


def _train(builder, loss_fn, params, batch, steps=12, fuse=0):
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=builder)
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch)
    runner.init(params)
    if fuse:
        hist = runner.fit([batch] * steps, fuse_steps=fuse)
    else:
        hist = runner.fit([batch] * steps)
    return ([float(m["loss"]) for m in hist], runner)


def test_quantized_ar_parity_and_counters():
    """AllReduce wire_dtype=int8: loss curve stays on the fp32
    trajectory (error feedback), the wire counters report the saving,
    and the dispatch count is unchanged (the codec lives in-graph)."""
    loss_fn, params, batch = _mlp_setup()
    fp, r_fp = _train(S.AllReduce(), loss_fn, params, batch)
    q, r_q = _train(S.AllReduce(wire_dtype="int8"), loss_fn, params, batch)
    counters = tel.counters()
    assert counters["wire.bytes_saved"] > 0
    assert counters["wire.bytes_quantized"] > 0
    assert r_q.distributed_step.dispatches == r_fp.distributed_step.dispatches
    np.testing.assert_allclose(q, fp, rtol=0.25, atol=1e-3)
    assert abs(q[-1] - fp[-1]) < 0.1 * max(abs(fp[-1]), 1e-3) + 1e-3
    # the lowering carries the two-phase quantized collective
    sharded = r_q.remapper.remap_feed(batch)
    hlo = r_q.distributed_step.lowered_text(r_q.state, sharded)
    assert "all_to_all" in hlo and "i8" in hlo


def test_quantized_ar_fused_matches_per_step():
    """Fused k=4 with the quantized AR wire is allclose to the per-step
    quantized loop with k x fewer dispatches (the codec composes with
    the lax.scan engine)."""
    loss_fn, params, batch = _mlp_setup(seed=3)
    per, r_per = _train(S.AllReduce(wire_dtype="int8"), loss_fn, params,
                        batch, steps=8)
    fused, r_fused = _train(S.AllReduce(wire_dtype="int8"), loss_fn,
                            params, batch, steps=8, fuse=4)
    np.testing.assert_allclose(per, fused, rtol=1e-5, atol=1e-6)
    assert r_fused.distributed_step.dispatches == \
        r_per.distributed_step.dispatches // 4


def test_quantized_ps_parity_per_step_and_fused():
    """Host-PS wire_dtype=int8: values pull as int8+scales (dequant
    in-graph), grads push the same way (dequant at the store boundary);
    the fused engine's in-scan codec emulation matches the per-step
    quantized loop."""
    loss_fn, params, batch = _mlp_setup(seed=5)
    fp, _ = _train(S.PS(), loss_fn, params, batch)
    q, r_q = _train(S.PS(wire_dtype="int8"), loss_fn, params, batch)
    # w (64x8 = 512 el) rides the quantized wire; v (8x8 = 64 el) is
    # sub-block and stays fp32 (the builder's ADT311 gate)
    assert r_q.distributed_step.ps_store.wire_quant == ["w"]
    np.testing.assert_allclose(q, fp, rtol=0.25, atol=1e-3)
    assert abs(q[-1] - fp[-1]) < 0.1 * max(abs(fp[-1]), 1e-3) + 1e-3
    counters = tel.counters()
    assert counters["wire.bytes_quantized"] > 0
    assert counters["wire.bytes_saved"] > 0
    # fused k=4 vs per-step, both quantized
    per, _ = _train(S.PS(wire_dtype="int8"), loss_fn, params, batch,
                    steps=8)
    fused, _ = _train(S.PS(wire_dtype="int8"), loss_fn, params, batch,
                      steps=8, fuse=4)
    np.testing.assert_allclose(per, fused, rtol=1e-4, atol=1e-5)


def test_quantized_ps_eval_and_checkpoint_stay_exact(tmp_path):
    """The store holds exact fp32 (only the wire is lossy): checkpoints
    round-trip bit-exactly and evaluate runs through the wire-form
    snapshot."""
    from autodist_tpu.checkpoint import Saver
    loss_fn, params, batch = _mlp_setup(seed=7)
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(
        strategy_builder=S.PS(wire_dtype="int8"))
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch)
    runner.init(params)
    for _ in range(3):
        runner.run(batch)
    ev = runner.evaluate([batch])
    assert np.isfinite(float(ev["loss"]))
    saver = Saver(directory=str(tmp_path))
    saver.save(runner)
    for _ in range(2):
        runner.run(batch)
    a = runner.gather_params()
    saver.restore(runner)
    for _ in range(2):
        runner.run(batch)
    b = runner.gather_params()
    np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))


# ------------------------------------------------------- degraded pulls


def test_degraded_pull_dequantizes_last_good_snapshot(monkeypatch):
    """Fault leg: with the owner unreachable, a quantized pull serves the
    LAST fetched values through the same wire codec — the device-side
    dequant of a degraded pull equals the last-good snapshot within the
    codec bound, and past the window the pull still fails loudly."""
    from autodist_tpu.model_item import VarInfo
    from autodist_tpu.parallel.ps import PSStore, PSVarPlan
    from test_faults import _FlakyService

    monkeypatch.setenv("ADT_PS_MAX_LAG", "2")
    infos = {"w": VarInfo(name="w", shape=(32, 16), dtype="float32")}
    plans = {"w": PSVarPlan(var_name="w", destinations=("hostA:CPU:0",),
                            sync=False, wire_dtype="int8")}
    rng = np.random.RandomState(0)
    init = {"w": rng.randn(32, 16).astype(np.float32)}
    owner_svc = _FlakyService()
    owner = PSStore(dict(plans), infos, optax.sgd(0.1))
    owner.init_params(init)
    owner.enable_serving(lambda host: owner_svc, my_host="hostA")
    try:
        worker = PSStore(dict(plans), infos, optax.sgd(0.1))
        worker.init_params(init)
        worker.enable_serving(lambda host: owner_svc, my_host="hostB")
        good = worker.pull()  # healthy fetch primes the cache; wire form
        assert set(good["w"]) == {"q", "s"}
        good_vals = C.dequant_wire_np(good["w"], (32, 16))
        np.testing.assert_allclose(good_vals, init["w"],
                                   atol=np.abs(init["w"]).max() / 127 + 1e-6)
        owner_svc.down = True
        for _ in range(2):  # inside the window: last-good, still wire-form
            vals = worker.pull()
            assert set(vals["w"]) == {"q", "s"}
            np.testing.assert_array_equal(vals["w"]["q"], good["w"]["q"])
            np.testing.assert_array_equal(vals["w"]["s"], good["w"]["s"])
        assert worker.stats["degraded_pulls"] == 2
        with pytest.raises(RuntimeError, match="degraded-serve window"):
            worker.pull()
    finally:
        owner_svc.down = False
        owner.close()


# ------------------------------------------------------------ diagnostics


def _lint(strategy, item, spec):
    from autodist_tpu.analysis import verify
    return list(verify(strategy, item, spec))


def _spec_2x2():
    return ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True, "tpus": 4}]})


def _emb_item():
    params = {"emb": jnp.zeros((4096, 64)),
              "w": jnp.zeros((64, 512)),
              "tiny": jnp.zeros((8,))}

    def loss_fn(p, batch):
        e = jnp.take(p["emb"], batch["ids"], axis=0)
        return jnp.mean((e @ p["w"]).sum(-1) + p["tiny"].sum())

    batch = {"ids": np.zeros((32,), np.int32)}
    return ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare()


def test_adt310_errors_and_warnings():
    from autodist_tpu.strategy.base import (AllReduceSynchronizer,
                                            GraphConfig, PSSynchronizer,
                                            Strategy, VarConfig)
    item, spec = _emb_item(), _spec_2x2()
    replicas = [d.name_string() for d in spec.devices]

    def plan(**node_kw):
        nodes = [VarConfig(var_name="emb",
                           synchronizer=AllReduceSynchronizer()),
                 VarConfig(var_name="tiny",
                           synchronizer=AllReduceSynchronizer()),
                 VarConfig(var_name="w", **node_kw)]
        return Strategy(node_config=nodes,
                        graph_config=GraphConfig(replicas=replicas))

    # sparse var on the quantized wire: error
    sp = plan(synchronizer=AllReduceSynchronizer())
    sp.find("emb").synchronizer = AllReduceSynchronizer(wire_dtype="int8")
    diags = _lint(sp, item, spec)
    assert any(d.code == "ADT310" and d.severity.name == "ERROR"
               and d.var == "emb" for d in diags), diags
    # compressor + wire codec: error
    both = plan(synchronizer=AllReduceSynchronizer(
        compressor="HorovodCompressor", wire_dtype="int8"))
    diags = _lint(both, item, spec)
    assert any(d.code == "ADT310" and d.severity.name == "ERROR"
               and d.var == "w" for d in diags), diags
    # unknown wire dtype: error
    bad = plan(synchronizer=AllReduceSynchronizer(wire_dtype="int4"))
    assert any(d.code == "ADT310" and d.severity.name == "ERROR"
               for d in _lint(bad, item, spec))
    # partitioned AR: warning (ignored)
    part = plan(partitioner="2,1", part_configs=[
        VarConfig(var_name="w/part_%d" % i,
                  synchronizer=AllReduceSynchronizer(wire_dtype="int8"))
        for i in range(2)])
    diags = _lint(part, item, spec)
    assert any(d.code == "ADT310" and d.severity.name == "WARNING"
               for d in diags), diags
    # proxied PS: warning (no host wire)
    proxy = plan(synchronizer=PSSynchronizer(
        reduction_destination="127.0.0.1:CPU:0", local_replication=True,
        wire_dtype="int8"))
    diags = _lint(proxy, item, spec)
    assert any(d.code == "ADT310" and d.severity.name == "WARNING"
               for d in diags), diags
    # sub-block var: ADT311 warning
    small = plan(synchronizer=AllReduceSynchronizer())
    small.find("tiny").synchronizer = AllReduceSynchronizer(
        wire_dtype="int8")
    diags = _lint(small, item, spec)
    assert any(d.code == "ADT311" and d.var == "tiny" for d in diags), diags
    # clean quantized plan lints with NO errors
    ok = plan(synchronizer=AllReduceSynchronizer(wire_dtype="int8"))
    errs = [d for d in _lint(ok, item, spec)
            if d.severity.name == "ERROR"]
    assert not errs, errs


def test_builder_quantized_plans_lint_clean():
    """The wire_dtype builders gate sparse/integer vars themselves, so
    their plans carry no ADT310 errors (CI lints the same combos)."""
    item, spec = _emb_item(), _spec_2x2()
    for builder in (S.AllReduce(wire_dtype="int8"),
                    S.PS(wire_dtype="int8")):
        strat = builder.build(item, spec)
        errs = [d for d in _lint(strat, item, spec)
                if d.severity.name == "ERROR"]
        assert not errs, (builder, errs)
        # serialization round-trips the wire axis
        from autodist_tpu.strategy.base import Strategy
        clone = Strategy.from_dict(strat.to_dict())
        assert clone.to_dict() == strat.to_dict()
        assert any(
            (getattr(n.synchronizer, "wire_dtype", "fp32") == "int8")
            for n in clone.node_config if n.synchronizer is not None)


def test_search_canon_never_emits_wire_diagnostics():
    """120 random mutations (wire operator included): every materialized
    plan verifies with zero ADT310/311 diagnostics of ANY severity —
    canon keeps the searcher out of the warning space entirely."""
    from autodist_tpu.search.space import PlanSpace
    item, spec = _emb_item(), _spec_2x2()
    space = PlanSpace(item, spec)
    assert space.wire_options["w"] == ("fp32", "int8")
    assert space.wire_options["emb"] == ("fp32",)     # sparse
    assert space.wire_options["tiny"] == ("fp32",)    # sub-block
    rng = random.Random(0)
    plan = space.seeds()[0][1]
    seen_wire_mutation = False
    for _ in range(120):
        out = space.mutate(plan, rng)
        if out is None:
            continue
        plan, desc = out
        seen_wire_mutation |= desc.startswith("wire[")
        strat = space.build(plan)
        assert not [d for d in _lint(strat, item, spec)
                    if d.code in ("ADT310", "ADT311")], (desc, plan)
    assert seen_wire_mutation, "wire operator never fired in 120 draws"


# ------------------------------------------------------- byte accounting


def test_wire_byte_accounting_agrees_across_layers():
    """Satellite: the telemetry counters, the lowering's static
    accounting, the cost model's priced payload, and the ADT5xx measured
    profile agree on the quantized payload within tolerance — scale
    sidecar included everywhere."""
    # large enough that chunk/block padding is negligible next to the
    # payload (w: 512x64, v: 64x64 -> 36864 elements, whole blocks)
    loss_fn, params, batch = _mlp_setup(seed=9, din=512, dout=64, batch=16)
    steps = 6
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(
        strategy_builder=S.AllReduce(wire_dtype="int8"))
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch)
    runner.init(params)
    for _ in range(steps):
        runner.run(batch)
    counters = tel.counters()
    meta = runner.distributed_step.metadata
    per_step_meta = meta["wire_quant_bytes_per_step"]
    assert per_step_meta > 0
    # counters == static accounting, exactly (same formula, same source)
    assert counters["wire.bytes_quantized"] == pytest.approx(
        per_step_meta * steps)
    saved_meta = (meta["wire_fp32_bytes_per_step"] - per_step_meta)
    assert counters["wire.bytes_saved"] == pytest.approx(saved_meta * steps)
    # cost model's priced payload within 30% (per-var sidecars vs the
    # bucket's concatenated payload differ only by block padding)
    from autodist_tpu.simulator.cost_model import CostModel
    item = runner.distributed_step.model_item
    cm = CostModel(item, _spec_2x2())
    priced = sum(cm._int8_payload(item.var_infos[n].num_elements)
                 for n in ("w", "v"))
    assert priced == pytest.approx(per_step_meta, rel=0.3)
    # drift report surfaces the wire section with the reduction factor
    # (read BEFORE the reset below wipes the recorder)
    from autodist_tpu.telemetry import drift as drift_lib
    report = drift_lib.build_report(cm, runner.distributed_step.strategy)
    assert report.wire is not None
    assert report.wire["reduction_x"] > 2.0
    assert "quantized wire" in report.format_table()
    # ADT5xx measured profile prices the int8 payload at true byte width:
    # the quantized program's total collective payload must be far below
    # the fp32 program's (which moves the same gradients at 4 bytes/elem)
    sharded = runner.remapper.remap_feed(batch)
    from autodist_tpu.analysis import hlo as hlo_lib
    sched_q = hlo_lib.collective_schedule(
        runner.distributed_step.lowered_text(runner.state, sharded))
    payload_q = sum(c.payload_bytes for c in sched_q)
    autodist_tpu.reset()
    ad_fp = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    r_fp = ad_fp.build(loss_fn, optax.adam(0.05), params, batch)
    r_fp.init(params)
    sched_fp = hlo_lib.collective_schedule(
        r_fp.distributed_step.lowered_text(r_fp.state,
                                           r_fp.remapper.remap_feed(batch)))
    payload_fp = sum(c.payload_bytes for c in sched_fp)
    assert payload_q < payload_fp / 2.0, (payload_q, payload_fp)


# ------------------------------------------------------------- searcher


def _search_fixture(width=256, batch=16, depth=3):
    """Large FLAT (rank-1) weights, reshaped inside the loss: rank-1
    tensors pass through PowerSGD (ADT308), so the wire contest the
    searcher faces is fp32 vs bf16 vs the blockwise int8 codec — the
    axis under test — rather than low-rank factorization winning
    outright on matrices."""
    params = {"w%d" % i: jnp.zeros((width * width,)) for i in range(depth)}

    def loss_fn(p, b):
        h = b["x"]
        for i in range(depth):
            h = jnp.tanh(h @ p["w%d" % i].reshape(width, width))
        return jnp.mean(h ** 2)

    batch_np = {"x": np.zeros((batch, width), np.float32)}
    item = ModelItem(loss_fn=loss_fn, optimizer=optax.sgd(0.1),
                     params=params, example_batch=batch_np).prepare()
    return loss_fn, params, batch_np, item


def test_search_picks_int8_wire_when_bandwidth_bound():
    """Acceptance: under a bandwidth-constrained ResourceSpec the
    searcher selects wire_dtype=int8 for at least one variable with NO
    hand-pinning; on a compute-bound spec it refuses to pay the accuracy
    premium."""
    from autodist_tpu.search.drivers import SearchConfig, run_search
    _loss_fn, _params, _batch, item = _search_fixture()
    # 4 v5e nodes behind 1 Gbps everywhere: strong compute, starved wire
    # -> the 1.15x lossy premium is decisively repaid by the ~3.9x cut
    nodes = [{"address": "10.0.0.%d" % (i + 1), "tpus": 4,
              "chief": i == 0, "network_bandwidth": 1}
             for i in range(4)]
    starved = ResourceSpec.from_dict(
        {"nodes": nodes, "slice": {"type": "v5e", "ici_bandwidth": 1}})
    r = run_search(item, starved, config=SearchConfig(budget=48, seed=0))
    assert r.ok
    wired = [n for n, c in r.plan.choices if c.wire_dtype == "int8"]
    assert wired, "bandwidth-bound search never chose the int8 wire: %s" \
        % r.plan.describe()
    # compute-bound (local CPU devices, default fat-enough wire): the
    # quantized wire's premium is never repaid
    fat = ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True,
                    "cpus": list(range(8))}]})
    r_fat = run_search(item, fat, config=SearchConfig(budget=48, seed=0))
    assert r_fat.ok
    assert not [n for n, c in r_fat.plan.choices
                if c.wire_dtype == "int8"], r_fat.plan.describe()


def test_searched_quantized_plan_trains_end_to_end(monkeypatch):
    """Satellite: a bandwidth-starved search over the test env's OWN
    devices (ICI and the host-PS PCIe wire both constrained) chooses a
    quantized plan, which then compiles and trains through the full
    stack."""
    from autodist_tpu.search.drivers import SearchConfig, run_search
    from autodist_tpu.simulator import cost_model as cm_lib
    width, batch = 256, 16
    loss_fn, params, batch_np, item = _search_fixture(width, batch)
    monkeypatch.setattr(cm_lib, "PCIE_BANDWIDTH_BYTES_S", 1e8)
    local = ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True,
                    "cpus": list(range(8))}],
         "slice": {"ici_bandwidth": 1}})
    r = run_search(item, local, config=SearchConfig(budget=48, seed=0))
    assert r.ok
    wired = [n for n, c in r.plan.choices if c.wire_dtype == "int8"]
    assert wired, r.plan.describe()

    class Pin(S.StrategyBuilder):
        def build(self, model_item, resource_spec):
            return r.strategy

    autodist_tpu.reset()
    rng = np.random.RandomState(0)
    live_params = {k: jnp.asarray(rng.randn(width * width) * 0.05,
                                  jnp.float32) for k in params}
    live_batch = {"x": rng.randn(batch, width).astype(np.float32)}
    ad = autodist_tpu.AutoDist(strategy_builder=Pin())
    runner = ad.build(loss_fn, optax.sgd(0.1), live_params, live_batch)
    runner.init(live_params)
    losses = [float(runner.run(live_batch)["loss"]) for _ in range(5)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_from_strategy_converts_int8_compressor_to_wire_axis():
    """A zoo strategy built with the (still-supported) Int8CompressorEF
    converts into a wire_dtype=int8 seed instead of silently losing its
    ~4x compression (the compressor axis no longer offers int8)."""
    from autodist_tpu.search.space import PlanSpace
    item, spec = _emb_item(), _spec_2x2()
    space = PlanSpace(item, spec)
    strat = S.AllReduce(compressor="Int8CompressorEF").build(item, spec)
    plan = space.from_strategy(strat)
    assert plan is not None
    cm = plan.choice_map()
    assert cm["w"].wire_dtype == "int8"
    assert cm["w"].compressor == "NoneCompressor"


def test_cost_model_does_not_discount_ignored_wire_paths():
    """wire_dtype=int8 on a proxied PS var (no host wire exists — the
    runtime psums full-width) must NOT be priced at quantized width:
    identical estimate to the fp32 spelling."""
    from autodist_tpu.simulator.cost_model import CostModel
    from autodist_tpu.strategy.base import (GraphConfig, PSSynchronizer,
                                            Strategy, VarConfig)
    item, spec = _emb_item(), _spec_2x2()
    replicas = [d.name_string() for d in spec.devices]

    def proxy_plan(wire):
        return Strategy(node_config=[
            VarConfig(var_name=n, synchronizer=PSSynchronizer(
                reduction_destination="127.0.0.1:CPU:0",
                local_replication=True, wire_dtype=wire))
            for n in ("emb", "w", "tiny")],
            graph_config=GraphConfig(replicas=replicas))

    cm = CostModel(item, spec)
    est_q = cm.estimate(proxy_plan("int8"))
    est_fp = cm.estimate(proxy_plan("fp32"))
    assert est_q.allreduce_s == pytest.approx(est_fp.allreduce_s)
    assert est_q.step_time_s == pytest.approx(est_fp.step_time_s)


def test_from_strategy_roundtrips_wire_axis():
    from autodist_tpu.search.space import PlanSpace
    item, spec = _emb_item(), _spec_2x2()
    space = PlanSpace(item, spec)
    strat = S.AllReduce(wire_dtype="int8").build(item, spec)
    plan = space.from_strategy(strat)
    assert plan is not None
    cm = plan.choice_map()
    assert cm["w"].wire_dtype == "int8"
    assert cm["emb"].wire_dtype == "fp32"   # sparse: canon strips it
    assert cm["tiny"].wire_dtype == "fp32"  # sub-block: canon strips it
    assert "int8w=" in plan.describe()
