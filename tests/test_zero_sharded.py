"""ZeRO-style sharded weight update (the ZeroSharded synchronizer kind).

Pins the PR's contracts end to end: strategy IR round-trip, training
parity with the AllReduce baseline (per-step AND fused k=4, fp32 and
int8 wire), dispatch parity, the zero.rs_bytes/ag_bytes counters and the
zero.hbm_saved_bytes gauge, the synchronizer-aware plan-level memory
gate (projection within the 20% tolerance of XLA's own buffer
assignment, and a previously-ADT501-gated plan passing and training
under ZeroSharded), the ADT312/313 diagnostics and the search-space
canon that never emits them, the searcher choosing ZeroSharded under a
memory-constrained ResourceSpec, original-layout optimizer-state
reconstruction for checkpoints, and the sharded saver's 4->2
replica-count restore re-laying-out the optimizer shards.
"""
import random
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.analysis import memory as memory_lib
from autodist_tpu.analysis import verify
from autodist_tpu.analysis.diagnostics import Severity
from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.telemetry import spans as tel


def _spec(n_cpus):
    return ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True,
                    "cpus": list(range(n_cpus))}]})


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


def _train(builder, loss_fn, params, batch, steps=10, fuse=0, spec=None):
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=builder, resource_spec=spec)
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch)
    runner.init(params)
    if fuse:
        hist = runner.fit([batch] * steps, fuse_steps=fuse)
    else:
        hist = runner.fit([batch] * steps)
    return [float(m["loss"]) for m in hist], runner


# ------------------------------------------------------------ strategy IR


def test_ir_roundtrip_and_unknown_kind():
    loss_fn, params, batch = _mlp_setup()
    item = ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare()
    spec = _spec(4)
    for builder in (S.ZeroSharded(), S.ZeroSharded(wire_dtype="int8")):
        strat = builder.build(item, spec)
        clone = S.Strategy.from_dict(strat.to_dict())
        assert clone.to_dict() == strat.to_dict()
        assert any(getattr(n.synchronizer, "kind", "") == "ZeroSharded"
                   for n in clone.node_config)
        errs = [d for d in verify(strat, item, spec)
                if d.severity >= Severity.ERROR]
        assert not errs, (builder, errs)
    # the kind is registered in the deserializer's error surface
    from autodist_tpu.analysis.diagnostics import DiagnosticError
    from autodist_tpu.strategy.base import synchronizer_from_dict
    with pytest.raises(DiagnosticError, match="ZeroSharded"):
        synchronizer_from_dict({"kind": "Nope"}, "w")


# --------------------------------------------------------- training parity


def test_zero_parity_per_step_and_fused():
    """Acceptance: ZeroSharded is allclose to the AllReduce baseline
    (params + opt + metrics) per-step, and fused k=4 matches the
    per-step zero loop with the k x dispatch reduction — the sharded
    opt state rides the lax.scan carry."""
    loss_fn, params, batch = _mlp_setup()
    fp, r_fp = _train(S.AllReduce(), loss_fn, params, batch)
    z, r_z = _train(S.ZeroSharded(), loss_fn, params, batch)
    np.testing.assert_allclose(z, fp, rtol=1e-4, atol=1e-6)
    assert (r_z.distributed_step.dispatches
            == r_fp.distributed_step.dispatches)
    # params and reconstructed full optimizer state match the baseline
    pz, pf = r_z.gather_params(), r_fp.gather_params()
    for a, b in zip(jax.tree_util.tree_leaves(pz),
                    jax.tree_util.tree_leaves(pf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    oz = r_z.distributed_step.gather_opt_state(r_z.state)
    of = r_fp.distributed_step.gather_opt_state(r_fp.state)
    za, fa = jax.tree_util.tree_leaves(oz), jax.tree_util.tree_leaves(of)
    assert [np.shape(a) for a in za] == [np.shape(a) for a in fa]
    for a, b in zip(za, fa):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    zf, r_zf = _train(S.ZeroSharded(), loss_fn, params, batch, fuse=5)
    np.testing.assert_allclose(zf, z, rtol=1e-5, atol=1e-6)
    assert (r_zf.distributed_step.dispatches
            == r_z.distributed_step.dispatches // 5)


def test_zero_int8_wire_parity_and_counters():
    """The int8 wire (quantized reduce-scatter + quantized update
    all-gather) stays on the fp32 trajectory; the zero.* counters
    report the payloads; dispatch count is unchanged. Vars sized above
    the per-shard-block int8 floor (>= 8 replicas x 256-element
    blocks)."""
    loss_fn, params, batch = _mlp_setup(seed=3, din=512, dout=64,
                                        batch=16)
    fp, r_fp = _train(S.AllReduce(), loss_fn, params, batch)
    q, r_q = _train(S.ZeroSharded(wire_dtype="int8"), loss_fn, params,
                    batch)
    np.testing.assert_allclose(q, fp, rtol=0.25, atol=1e-3)
    assert abs(q[-1] - fp[-1]) < 0.1 * max(abs(fp[-1]), 1e-3) + 1e-3
    counters = tel.counters()
    assert counters["zero.rs_bytes"] > 0
    assert counters["zero.ag_bytes"] > 0
    assert (r_q.distributed_step.dispatches
            == r_fp.distributed_step.dispatches)
    meta = r_q.distributed_step.metadata
    assert meta["zero_wire_int8"], meta
    # counters == static accounting, exactly (same formula, same source)
    steps = r_q.distributed_step.dispatches
    assert counters["zero.rs_bytes"] == pytest.approx(
        meta["zero_rs_bytes_per_step"] * steps)
    # the quantized payload is far below the fp32 one
    fp32_rs = sum(zs.padded_elems * 4.0
                  for zs in r_q.distributed_step.zero_syncs.values())
    assert meta["zero_rs_bytes_per_step"] < fp32_rs / 2.0
    # fused k=5 matches the per-step quantized loop
    per, _ = _train(S.ZeroSharded(wire_dtype="int8"), loss_fn, params,
                    batch)
    fused, _ = _train(S.ZeroSharded(wire_dtype="int8"), loss_fn, params,
                      batch, fuse=5)
    np.testing.assert_allclose(fused, per, rtol=1e-5, atol=1e-6)


def test_zero_int8_gate_requires_one_block_per_shard():
    """A var above one block TOTAL but below one block PER SHARD must
    stay fp32 (the kernel rounds each shard to whole blocks, so int8
    would ship MORE bytes than fp32 there) — and the cost model's
    padded pricing agrees with the kernel's accounting exactly."""
    from autodist_tpu.kernel.synchronization.zero_synchronizer import (
        zero_wire_payload_bytes)
    from autodist_tpu.parallel.collectives import wire_block_size
    from autodist_tpu.strategy.zero_sharded_strategy import (
        zero_wire_quantizable)
    block = wire_block_size()
    n = 8

    class Info:
        sparse = False
        dtype = "float32"
        num_elements = block + 50  # one block total, sub-block per shard

    assert not zero_wire_quantizable(Info(), n)
    Info.num_elements = n * block
    assert zero_wire_quantizable(Info(), n)
    # below the gate, the padded int8 payload really is worse than fp32
    worse = zero_wire_payload_bytes(block + 50, n, "int8")
    assert worse > zero_wire_payload_bytes(block + 50, n, "fp32")
    # the builder applies the gate: small-var int8 plans self-gate
    loss_fn, params, batch = _mlp_setup()  # 512- and 64-element vars
    item = ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare()
    strat = S.ZeroSharded(wire_dtype="int8").build(item, _spec(8))
    assert all(n_.synchronizer.wire_dtype == "fp32"
               for n_ in strat.node_config
               if getattr(n_.synchronizer, "kind", "") == "ZeroSharded")


def test_zero_hbm_saved_gauge_and_metadata():
    loss_fn, params, batch = _mlp_setup()
    _, r = _train(S.ZeroSharded(), loss_fn, params, batch, steps=2)
    meta = r.distributed_step.metadata
    assert set(meta["zero_sharded"]) == {"w", "v"}
    assert meta["zero_hbm_saved_bytes"] > 0
    from autodist_tpu.telemetry.spans import get_recorder
    assert get_recorder().gauges().get("zero.hbm_saved_bytes", 0) > 0
    # the fp32 wire credits its reduce-scatter and all-gather too
    assert tel.counters()["zero.rs_bytes"] > 0
    assert tel.counters()["zero.ag_bytes"] > 0


def test_zero_single_replica_degrades_to_allreduce():
    loss_fn, params, batch = _mlp_setup(seed=5)
    spec1 = _spec(1)
    fp, _ = _train(S.AllReduce(), loss_fn, params, batch, steps=6,
                   spec=spec1)
    z, r_z = _train(S.ZeroSharded(), loss_fn, params, batch, steps=6,
                    spec=spec1)
    np.testing.assert_allclose(z, fp, rtol=1e-6, atol=1e-7)
    assert not r_z.distributed_step.metadata["zero_sharded"]


# -------------------------------------------------------------- memory gate


@pytest.fixture(scope="module")
def _mem_artifacts():
    """One AllReduce and one ZeroSharded build on a 4-replica CPU mesh,
    sized so optimizer state dominates: plan-level projections and XLA's
    compiled memory stats for both (donated variant — the steady state
    the plan-level heuristic models)."""
    rng = np.random.RandomState(0)
    params = {"w1": np.asarray(rng.randn(256, 512) * 0.05, np.float32),
              "w2": np.asarray(rng.randn(512, 64) * 0.05, np.float32)}
    batch = {"x": rng.randn(16, 256).astype(np.float32),
             "y": rng.randn(16, 64).astype(np.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    spec4 = _spec(4)
    out = {"spec": spec4, "loss_fn": loss_fn, "params": params,
           "batch": batch}
    for name, builder in (("ar", S.AllReduce()), ("zero", S.ZeroSharded())):
        autodist_tpu.reset()
        ad = autodist_tpu.AutoDist(strategy_builder=builder,
                                   resource_spec=spec4)
        runner = ad.build(loss_fn, optax.adam(1e-2), params, batch)
        runner.init(params)
        dstep = runner.distributed_step
        ps_avals, _ = dstep._ps_avals()
        placed = runner.remapper.remap_feed(batch)
        ma = dstep._step_fn.lower(
            runner.state, ps_avals, placed).compile().memory_analysis()
        out[name] = {
            "strategy": dstep.strategy,
            "item": dstep.model_item,
            "xla_peak": (ma.argument_size_in_bytes
                         + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes
                         - ma.alias_size_in_bytes),
            "metadata": dict(dstep.metadata),
        }
    autodist_tpu.reset()
    return out


def test_plan_gate_projects_zero_drop_within_20pct(_mem_artifacts):
    """Satellite: the synchronizer-aware plan-level gate projects the
    ZeroSharded footprint within the existing 20% tolerance of XLA's own
    buffer assignment, and the projected drop vs AllReduce equals the
    (P-1)/P opt-state fraction the lowering reports."""
    art = _mem_artifacts
    spec, item = art["spec"], art["zero"]["item"]
    p_ar = memory_lib.plan_peak_hbm(art["ar"]["strategy"], item, spec)
    p_z = memory_lib.plan_peak_hbm(art["zero"]["strategy"], item, spec)
    assert p_z < p_ar
    x_z = art["zero"]["xla_peak"]
    assert x_z > 0
    assert abs(p_z - x_z) / x_z < 0.20, (p_z, x_z)
    # the projection's drop IS the lowering's reported opt-state saving
    saved = art["zero"]["metadata"]["zero_hbm_saved_bytes"]
    assert saved > 0
    assert p_ar - p_z == pytest.approx(saved, rel=1e-6)
    # and the measured (XLA) drop confirms the saving is real
    x_ar = art["ar"]["xla_peak"]
    assert x_ar - x_z > 0.5 * saved


def test_adt501_gated_plan_unlocks_and_trains(_mem_artifacts):
    """Acceptance: a budget between the two footprints fails AllReduce
    with ADT501 at plan-lint time, passes ZeroSharded clean — and the
    ZeroSharded plan actually trains under that spec."""
    art = _mem_artifacts
    loss_fn, params, batch = (art["loss_fn"], art["params"], art["batch"])
    tight = ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True,
                    "cpus": [0, 1, 2, 3]}],
         "slice": {"hbm_gib": 2.2 / 1024.0}})
    item = art["zero"]["item"]
    rep_ar = memory_lib.plan_memory_report(
        S.AllReduce().build(item, tight), item, tight)
    rep_z = memory_lib.plan_memory_report(
        S.ZeroSharded().build(item, tight), item, tight)
    assert "ADT501" in [d.code for d in rep_ar["diagnostics"]]
    assert not [d for d in rep_z["diagnostics"]
                if d.severity >= Severity.ERROR]
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.ZeroSharded(),
                               resource_spec=tight)
    runner = ad.build(loss_fn, optax.adam(1e-2), params, batch)
    runner.init(params)
    losses = [float(runner.run(batch)["loss"]) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# ------------------------------------------------------------- diagnostics


def _emb_item():
    params = {"emb": jnp.zeros((4096, 64)),
              "w": jnp.zeros((64, 512)),
              "tiny": jnp.zeros((2,))}

    def loss_fn(p, batch):
        e = jnp.take(p["emb"], batch["ids"], axis=0)
        return jnp.mean((e @ p["w"]).sum(-1) + p["tiny"].sum())

    batch = {"ids": np.zeros((32,), np.int32)}
    return ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare()


def _tpu_spec():
    return ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True, "tpus": 4}]})


def test_adt312_and_adt313():
    from autodist_tpu.strategy.base import (GraphConfig, PSSynchronizer,
                                            Strategy, VarConfig,
                                            ZeroShardedSynchronizer)
    item, spec = _emb_item(), _tpu_spec()
    replicas = [d.name_string() for d in spec.devices]

    def plan(nodes):
        return Strategy(node_config=nodes,
                        graph_config=GraphConfig(replicas=replicas))

    def base():
        return [VarConfig(var_name="w",
                          synchronizer=ZeroShardedSynchronizer()),
                VarConfig(var_name="tiny",
                          synchronizer=S.AllReduceSynchronizer()),
                VarConfig(var_name="emb", synchronizer=PSSynchronizer(
                    reduction_destination="127.0.0.1:CPU:0"))]

    # sparse var on the sharded update: error
    n = base()
    n[2] = VarConfig(var_name="emb",
                     synchronizer=ZeroShardedSynchronizer())
    d = verify(plan(n), item, spec)
    assert any(x.code == "ADT312" and x.severity.name == "ERROR"
               and x.var == "emb" for x in d), d
    # sub-shard var: ADT313 warning
    n = base()
    n[1] = VarConfig(var_name="tiny",
                     synchronizer=ZeroShardedSynchronizer())
    d = verify(plan(n), item, spec)
    assert any(x.code == "ADT313" and x.var == "tiny" for x in d), d
    # mp_axes conflict: error
    n = base()
    n[0] = VarConfig(var_name="w", synchronizer=ZeroShardedSynchronizer(),
                     mp_axes={0: "model"})
    d = verify(plan(n), item, spec)
    assert any(x.code == "ADT312" and x.severity.name == "ERROR"
               for x in d), d
    # partitioner conflict: error
    n = base()
    n[0] = VarConfig(var_name="w", synchronizer=ZeroShardedSynchronizer(),
                     partitioner="2,1")
    d = verify(plan(n), item, spec)
    assert any(x.code == "ADT312" and x.severity.name == "ERROR"
               for x in d), d
    # staleness>0 PS beside a zero var: error
    n = base()
    n[2] = VarConfig(var_name="emb", synchronizer=PSSynchronizer(
        reduction_destination="127.0.0.1:CPU:0", staleness=2))
    d = verify(plan(n), item, spec)
    assert any(x.code == "ADT312" and x.severity.name == "ERROR"
               for x in d), d
    # async PS beside a zero var: ADT307 (all-or-nothing) + ADT312
    n = base()
    n[2] = VarConfig(var_name="emb", synchronizer=PSSynchronizer(
        reduction_destination="127.0.0.1:CPU:0", sync=False))
    codes = {x.code for x in verify(plan(n), item, spec)}
    assert "ADT312" in codes and "ADT307" in codes
    # a clean zero plan carries neither
    d = verify(plan(base()), item, spec)
    assert not [x for x in d if x.code in ("ADT312", "ADT313")], d


def test_lowering_raises_what_lint_lists():
    """The compile path refuses the same ADT312 combinations the linter
    reports (sparse var on the sharded update)."""
    loss_fn_params = _emb_item()
    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.strategy.base import (GraphConfig, Strategy,
                                            VarConfig,
                                            ZeroShardedSynchronizer)
    from jax.sharding import Mesh
    item = loss_fn_params
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("data",))
    strat = Strategy(
        node_config=[
            VarConfig(var_name="emb",
                      synchronizer=ZeroShardedSynchronizer()),
            VarConfig(var_name="w", synchronizer=S.AllReduceSynchronizer()),
            VarConfig(var_name="tiny",
                      synchronizer=S.AllReduceSynchronizer())],
        graph_config=GraphConfig(replicas=["127.0.0.1:CPU:%d" % i
                                           for i in range(4)]))
    with pytest.raises(ValueError, match="ADT312"):
        GraphTransformer(strat, mesh, item).transform()


# ------------------------------------------------------------------ search


def test_search_space_zero_axis_canon_sweep():
    """120 random mutations (zero operator included): every materialized
    plan verifies with zero ADT312/313 diagnostics of ANY severity."""
    from autodist_tpu.search.space import PlanSpace
    item, spec = _emb_item(), _tpu_spec()
    space = PlanSpace(item, spec)
    assert space.zero_ok["w"]
    assert not space.zero_ok["emb"]    # sparse
    assert not space.zero_ok["tiny"]   # sub-replica-sized
    seeds = dict(space.seeds())
    assert "seed:zero" in seeds and "seed:zero-int8w" in seeds
    cm = seeds["seed:zero"].choice_map()
    assert cm["w"].zero and not cm["emb"].zero and not cm["tiny"].zero
    cmq = seeds["seed:zero-int8w"].choice_map()
    assert cmq["w"].zero and cmq["w"].wire_dtype == "int8"
    rng = random.Random(0)
    plan = seeds["seed:zero"]
    seen = False
    for _ in range(120):
        out = space.mutate(plan, rng)
        if out is None:
            continue
        plan, desc = out
        seen |= desc.startswith("zero[")
        strat = space.build(plan)
        bad = [d for d in verify(strat, item, spec)
               if d.code in ("ADT312", "ADT313")]
        assert not bad, (desc, plan, bad)
    assert seen, "zero operator never fired in 120 draws"


def test_from_strategy_roundtrips_zero_axis():
    from autodist_tpu.search.space import PlanSpace
    item, spec = _emb_item(), _tpu_spec()
    space = PlanSpace(item, spec)
    plan = space.from_strategy(
        S.ZeroSharded(wire_dtype="int8").build(item, spec))
    assert plan is not None
    cm = plan.choice_map()
    assert cm["w"].zero and cm["w"].wire_dtype == "int8"
    assert not cm["emb"].zero and not cm["tiny"].zero
    assert "zero=" in plan.describe()


def test_search_picks_zero_when_memory_constrained(monkeypatch):
    """Satellite: a memory-constrained ResourceSpec (small
    slice.hbm_gib) makes the searcher pick ZeroSharded for the large
    vars (prime dims keep divisor-based partitioning out of the space —
    the flat ZeRO shard is the only sharding that applies); a
    headroom-rich spec refuses the extra collective launches."""
    from autodist_tpu.search.drivers import SearchConfig, run_search
    from autodist_tpu.simulator import cost_model as cm_lib
    monkeypatch.setattr(cm_lib, "PCIE_BANDWIDTH_BYTES_S", 1e8)
    width = 257  # prime: no divisor-based partitioning exists
    params = {"w%d" % i: jnp.zeros((width, width)) for i in range(3)}

    def loss_fn(p, b):
        h = b["x"]
        for i in range(3):
            h = jnp.tanh(h @ p["w%d" % i])
        return jnp.mean(h ** 2)

    batch = {"x": np.zeros((16, width), np.float32)}
    item = ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare()
    tight = ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True, "tpus": 4}],
         "slice": {"hbm_gib": 2.83 / 1024.0}})
    r = run_search(item, tight, config=SearchConfig(budget=48, seed=0))
    assert r.ok
    zeroed = [n for n, c in r.plan.choices if c.zero]
    assert zeroed, ("memory-constrained search never chose ZeroSharded: "
                    "%s" % r.plan.describe())
    rich = _tpu_spec()
    r2 = run_search(item, rich, config=SearchConfig(budget=48, seed=0))
    assert r2.ok
    assert not [n for n, c in r2.plan.choices if c.zero], \
        r2.plan.describe()


def test_cost_model_prices_zero_like_allreduce_wire():
    """rs + ag move the same ring bytes as the all-reduce: identical
    allreduce_s, strictly lower HBM, and the int8 wire prices at the
    quantized payload."""
    from autodist_tpu.simulator.cost_model import CostModel
    item, spec = _emb_item(), _tpu_spec()
    cm = CostModel(item, spec)
    ar = cm.estimate(S.AllReduce().build(item, spec))
    z = cm.estimate(S.ZeroSharded().build(item, spec))
    assert z.allreduce_s == pytest.approx(ar.allreduce_s)
    assert z.hbm_bytes < ar.hbm_bytes
    # the int8 wire prices the eligible var at the quantized payload
    # (the sparse emb's dense-priced wire dominates this model, so the
    # total shrinks by w's 3/4 saving only)
    zq = cm.estimate(S.ZeroSharded(wire_dtype="int8").build(item, spec))
    assert zq.allreduce_s < z.allreduce_s
    w_bytes = item.var_infos["w"].num_elements * 4
    saved = (z.allreduce_s - zq.allreduce_s)
    assert saved > 0.5 * (2.0 * 3 / 4) * w_bytes * 0.75 / (
        spec.ici_bandwidth_gbps() * 1e9 / 8)


# -------------------------------------------------------------- checkpoints


def test_plain_saver_roundtrip_and_full_opt_layout(tmp_path):
    """Original-layout checkpoints: gather_opt_state reconstructs the
    full optimizer tree from the sync_state shards, and a save/restore
    round trip replays deterministically."""
    from autodist_tpu.checkpoint import Saver
    loss_fn, params, batch = _mlp_setup(seed=7)
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.ZeroSharded())
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch)
    runner.init(params)
    for _ in range(3):
        runner.run(batch)
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


def test_elastic_snapshot_adopt_relays_zero_shards():
    """In-run elastic shrink path: `elastic.snapshot_runner_state` on a
    4-replica ZeroSharded runner adopts onto a 2-replica rebuild with
    the optimizer shards re-laid-out (the live-handoff analog of the
    sharded checkpoint's cross-topology restore) — adam moments
    preserved, training continues."""
    from autodist_tpu.runtime import elastic
    loss_fn, params, batch = _mlp_setup(seed=11, din=128, dout=16)
    _, r4 = _train(S.ZeroSharded(), loss_fn, params, batch, steps=3,
                   spec=_spec(4))
    snap = elastic.snapshot_runner_state(r4)
    assert snap is not None and snap.get("mesh")
    opt4 = r4.distributed_step.gather_opt_state(r4.state)
    p4 = r4.gather_params()
    autodist_tpu.reset()
    ad2 = autodist_tpu.AutoDist(strategy_builder=S.ZeroSharded(),
                                resource_spec=_spec(2))
    r2 = ad2.build(loss_fn, optax.adam(0.05), params, batch)
    r2.init(params)
    elastic.adopt_snapshot(r2, snap)
    for a, b in zip(jax.tree_util.tree_leaves(p4),
                    jax.tree_util.tree_leaves(r2.gather_params())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    opt2 = r2.distributed_step.gather_opt_state(r2.state)
    for a, b in zip(jax.tree_util.tree_leaves(opt4),
                    jax.tree_util.tree_leaves(opt2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert np.isfinite(float(r2.run(batch)["loss"]))


def test_sharded_restore_across_replica_count_change():
    """Satellite: the sharded saver stores only locally-owned opt-state
    shards (they ride the sync_state tree's per-device slices), and a
    4 -> 2 replica-count restore re-lays the optimizer shards out
    exactly — adam moments survive the topology change — falling back
    through the existing integrity scan when the newest checkpoint is
    damaged."""
    from autodist_tpu.checkpoint.sharded import ShardedSaver
    loss_fn, params, batch = _mlp_setup(seed=9, din=128, dout=16)
    d = tempfile.mkdtemp()
    _, r4 = _train(S.ZeroSharded(), loss_fn, params, batch, steps=3,
                   spec=_spec(4))
    saver = ShardedSaver(directory=d)
    saver.save(r4)  # the good checkpoint (step 3)
    full_opt_4 = r4.distributed_step.gather_opt_state(r4.state)
    full_params_4 = r4.gather_params()
    r4.run(batch)
    base = saver.save(r4)  # newest (step 4) — about to be damaged
    import glob
    import os
    shard = glob.glob(base + ".shard-p*.npz")[0]
    with open(shard, "r+b") as f:
        f.seek(0)
        f.write(b"\0" * 64)

    autodist_tpu.reset()
    ad2 = autodist_tpu.AutoDist(strategy_builder=S.ZeroSharded(),
                                resource_spec=_spec(2))
    r2 = ad2.build(loss_fn, optax.adam(0.05), params, batch)
    r2.init(params)
    state, step = ShardedSaver(directory=d).restore(r2)
    assert step == 3  # integrity scan skipped the damaged newest save
    full_opt_2 = r2.distributed_step.gather_opt_state(r2.state)
    full_params_2 = r2.gather_params()
    for a, b in zip(jax.tree_util.tree_leaves(full_params_4),
                    jax.tree_util.tree_leaves(full_params_2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(full_opt_4),
                    jax.tree_util.tree_leaves(full_opt_2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    m = r2.run(batch)
    assert np.isfinite(float(m["loss"]))
    assert os.path.isdir(d)
