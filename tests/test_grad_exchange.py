"""The gradient exchange of the DEFAULT lowering on more than one replica.

What PR 26 changed and what must hold: plain mean-psums are issued in the
order the backward pass completes their gradients (read from the loss's
gradient jaxpr), the training programs compile with per-program options on
a TPU with more than one replica and with none anywhere else, and step
metadata says which collectives travel. The order places collectives, it
computes nothing: on the CPU mesh the lowering trains bit-identically to
the same per-variable psums issued in the order they had before.
(The device-less compile for a described v5e:2x2 lives in
tests/test_olmoe.py, beside the one topology fixture the suite has.)
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.kernel import graph_transformer as gt
from autodist_tpu.ops import embedding
from autodist_tpu.parallel import collectives
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy.base import (AllReduceSynchronizer, GraphConfig,
                                        PSSynchronizer, Strategy, VarConfig)
from autodist_tpu.strategy.ps_strategy import (reduction_devices,
                                               replica_devices)
from autodist_tpu.telemetry import spans as tel


def _mlp(seed=0, wide=False):
    """Four variables; ``wide`` spreads their sizes 400:1."""
    rng = np.random.RandomState(seed)
    h = 200 if wide else 16
    params = {"w1": jnp.asarray(rng.randn(8, h).astype(np.float32) * 0.1),
              "b1": jnp.zeros((h,), jnp.float32),
              "w2": jnp.asarray(rng.randn(h, 4).astype(np.float32) * 0.1),
              "b2": jnp.zeros((4,), jnp.float32)}

    def loss_fn(p, b):
        hid = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((hid @ p["w2"] + p["b2"] - b["y"]) ** 2)

    def batch():
        return {"x": rng.randn(16, 8).astype(np.float32),
                "y": rng.randn(16, 4).astype(np.float32)}
    return params, loss_fn, batch


def _embedding_model(seed=0):
    """A sparse embedding table (its gradient travels as gathered pairs)
    in front of a dense layer."""
    rng = np.random.RandomState(seed)
    params = {"table": jnp.asarray(rng.randn(512, 8).astype(np.float32) * 0.1),
              "w": jnp.asarray(rng.randn(8, 4).astype(np.float32) * 0.1),
              "b": jnp.zeros((4,), jnp.float32)}

    def loss_fn(p, b):
        e = embedding.embedding_lookup(p["table"], b["ids"], name="table")
        return jnp.mean((e @ p["w"] + p["b"] - b["y"]) ** 2)

    def batch():
        return {"ids": rng.randint(0, 512, (16,)).astype(np.int32),
                "y": rng.randn(16, 4).astype(np.float32)}
    return params, loss_fn, batch


MODELS = {"dense": _mlp, "sizes_400_to_1": lambda: _mlp(wide=True),
          "sparse_embedding": _embedding_model}


class _HostPSAllReduceMix:
    """Every other trainable variable on the host-PS store, the rest
    all-reduced, each in a group of its own."""

    def build(self, item, spec):
        dest = reduction_devices(spec)[0]
        nodes = [VarConfig(var_name=n, synchronizer=(
            AllReduceSynchronizer(group=i) if i % 2 == 0 else
            PSSynchronizer(reduction_destination=dest, sync=True)))
            for i, n in enumerate(item.trainable_var_names)]
        return Strategy(node_config=nodes, graph_config=GraphConfig(
            replicas=list(replica_devices(spec))))


# plan -> (builder, the kind each device-synced variable of the MLP
# travels under in metadata["grad_sync_groups"]; None: not pinned here)
PLANS = {
    "AllReduce": (S.AllReduce, None),
    # every variable a group of its own: four plain sums, launch-bound
    "chunk1": (lambda: S.AllReduce(chunk_size=1),
               dict(w1="pack", b1="pack", w2="pack", b2="pack")),
    # two variables per concat bucket, compressed wire, bucket state
    "compressed": (lambda: S.AllReduce(compressor="HorovodCompressor",
                                       chunk_size=2),
                   dict(w1="bucket", b1="bucket", w2="bucket", b2="bucket")),
    # reduce-scatter + sharded apply; the sub-shard bias falls back
    "ZeroSharded": (S.ZeroSharded,
                    dict(w1="zero", b1="zero", w2="zero", b2="var")),
    # b2 and w2 leave through the host-PS store, outside the exchange
    "host_ps_mix": (_HostPSAllReduceMix, dict(w1="pack", b1="pack")),
    # int8 wire under the bf16 compute tier: the matrices quantize (a
    # bucket each), the sub-block biases stay plain sums
    "int8_wire_bf16": (lambda: S.AllReduce(wire_dtype="int8", chunk_size=1,
                                           compute_dtype="bf16"),
                       dict(w1="bucket", b1="pack", w2="bucket", b2="pack")),
}
# the int8 plan runs on the wide MLP: on the 16-wide one every variable
# is under one scale block and the wire self-gates to float32
LOWERINGS = ([(m, "AllReduce") for m in sorted(MODELS)]
             + [("dense", p) for p in ("chunk1", "compressed", "ZeroSharded",
                                       "host_ps_mix")]
             + [("sizes_400_to_1", "int8_wire_bf16")])


def _train(model, fuse, sentinel, steps=6, plan="AllReduce"):
    params, loss_fn, batch = MODELS[model]()
    batches = [batch() for _ in range(steps if not fuse else 8)]
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=PLANS[plan][0]())
    runner = ad.build(loss_fn, optax.adam(0.05), params, batches[0],
                      sentinel=sentinel)
    runner.init(params)
    hist = (runner.fit(iter(batches), fuse_steps=fuse, metrics_every=2)
            if fuse else runner.fit(iter(batches)))
    dstep = runner.distributed_step
    out = {"metrics": jax.tree_util.tree_map(np.asarray, list(hist)),
           "params": jax.tree_util.tree_map(np.asarray,
                                            runner.gather_params()),
           "opt": jax.tree_util.tree_map(
               np.asarray, dstep.gather_opt_state(runner.state)),
           "meta": dstep.metadata, "dispatches": dstep.dispatches,
           "trainable": sorted(params),
           "sentinel": runner.step_stats().get("sentinel")}
    autodist_tpu.reset()
    return out


def _assert_same_run(got, want):
    for key in ("metrics", "params", "opt"):
        a, b = (jax.tree_util.tree_leaves(t[key]) for t in (got, want))
        assert len(a) == len(b) and a
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y, err_msg=key)


@pytest.mark.parametrize("sentinel", [None, True], ids=["plain", "sentinel"])
@pytest.mark.parametrize("fuse", [0, 4], ids=["per_step", "fused_k4"])
@pytest.mark.parametrize("model,plan", LOWERINGS,
                         ids=["%s/%s" % mp for mp in LOWERINGS])
def test_default_lowering_is_the_per_variable_psums_bit_for_bit(
        monkeypatch, model, plan, fuse, sentinel):
    """Eight replicas, six steps (eight under fuse_steps=4): parameters,
    optimizer state, every metric and every sentinel verdict equal, bit
    for bit, those of the same lowering with its per-variable psums in
    the order they had before (no readiness read: all ties, tree order).
    Whatever the plan, step metadata names every device-synced variable
    once, under the kind it travels as, and fusing four steps into one
    program divides the dispatches by four."""
    got = _train(model, fuse, sentinel, plan=plan)
    monkeypatch.setattr(gt.GraphTransformer, "_grad_ready_order",
                        lambda self, grad_jaxpr=None: {})
    want = _train(model, fuse, sentinel, plan=plan)
    assert (len(got["meta"]["grad_sync_groups"])
            == len(want["meta"]["grad_sync_groups"]) > 0)
    _assert_same_run(got, want)
    if sentinel:
        assert all("sentinel" in m for m in got["metrics"])
    kinds = PLANS[plan][1]
    if kinds is not None:
        named = [(n, grp["kind"]) for grp in got["meta"]["grad_sync_groups"]
                 for n in grp["vars"]]
        assert sorted(named) == sorted(kinds.items())
        assert sorted(kinds) == sorted(
            set(got["trainable"]) - set(got["meta"]["ps_host_resident"]))
    if fuse:
        assert got["dispatches"] == want["dispatches"] == 8 // fuse


def test_a_nan_gradient_is_skipped_once_whatever_the_order(monkeypatch):
    """The sentinel judges the COMPLETE synced gradient: a NaN planted in
    one variable's gradient at step 3 is skipped, once, and the run ends
    in the same finite state with and without the readiness order."""
    monkeypatch.setenv("ADT_GRAD_FAULT_PLAN", json.dumps(
        {"faults": [{"var": "w1", "mode": "nan", "step": 3}]}))
    got = _train("dense", 0, True, steps=8, plan="chunk1")
    monkeypatch.setattr(gt.GraphTransformer, "_grad_ready_order",
                        lambda self, grad_jaxpr=None: {})
    want = _train("dense", 0, True, steps=8, plan="chunk1")
    assert got["sentinel"]["skips"] == want["sentinel"]["skips"] == 1
    assert all(np.isfinite(m["loss"]) for m in got["metrics"])
    _assert_same_run(got, want)


def test_the_order_is_the_backward_pass_own():
    """The readiness the lowering reads: the last layer's gradients come
    first, whatever the names sort like."""
    params, loss_fn, batch = _mlp()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.sgd(0.1), params, batch())
    groups = runner.distributed_step.metadata["grad_sync_groups"]
    autodist_tpu.reset()
    assert [g["kind"] for g in groups] == ["pack"]  # 4 launch-bound vars
    assert groups[0]["bytes"] == sum(
        int(np.prod(v.shape)) * 4 for v in params.values())
    order = groups[0]["vars"]
    assert order.index("b2") < order.index("b1")
    assert order.index("w2") < order.index("w1")


def _entry(name, ready, nbytes, kind="f32"):
    return (name, ready, nbytes, kind)


GROUPING = {
    # an oversized variable stands alone, and does not close the pack
    # that was filling around it
    "oversized_alone": (
        [_entry("a", 0, 10), _entry("big", 1, 4 << 20), _entry("b", 2, 10)],
        [("big",), ("a", "b")]),
    # exactly the bound stands alone
    "at_the_bound": (
        [_entry("x", 0, 1 << 20), _entry("y", 1, 8)], [("x",), ("y",)]),
    # a pack stays within the bound: the third member opens the next
    "packs_stay_within": (
        [_entry("a", 0, 400 << 10), _entry("b", 1, 400 << 10),
         _entry("c", 2, 400 << 10), _entry("d", 3, 8)],
        [("a", "b"), ("c", "d")]),
    # only one kind (dtype, axes) shares a pack
    "kinds_apart": (
        [_entry("a", 0, 8), _entry("h", 1, 8, "bf16"), _entry("b", 2, 8)],
        [("h",), ("a", "b")]),
    # readiness decides, not the name
    "by_readiness": (
        [_entry("layer_10", 1, 2 << 20), _entry("layer_2", 9, 2 << 20),
         _entry("layer_9", 2, 2 << 20)],
        [("layer_10",), ("layer_9",), ("layer_2",)]),
    "nothing": ([], []),
}


@pytest.mark.parametrize("case", sorted(GROUPING))
def test_grouping_rule(case):
    entries, want = GROUPING[case]
    groups = collectives.plan_grad_sync_groups(entries)
    assert [g.var_names for g in groups] == want
    # every variable in exactly one group, none split
    names = [n for g in groups for n in g.var_names]
    assert sorted(names) == sorted(e[0] for e in entries)
    size = {e[0]: e[2] for e in entries}
    ready = {e[0]: e[1] for e in entries}
    for g in groups:
        assert g.nbytes == sum(size[n] for n in g.var_names)
        assert g.ready_at == max(ready[n] for n in g.var_names)
        assert len(g.var_names) == 1 or g.nbytes <= collectives.PACK_BYTES
    # in the order they become complete
    assert [g.ready_at for g in groups] == sorted(g.ready_at for g in groups)


def test_readiness_is_read_off_the_gradient_jaxpr():
    def loss(p, x):
        h = jnp.tanh(x @ p["first"])
        return jnp.sum(h @ p["second"]) + 0.0 * jnp.sum(p["unused"])
    p = {"first": jnp.ones((4, 4)), "second": jnp.ones((4, 2)),
         "unused": jnp.ones((3,))}
    closed = jax.make_jaxpr(jax.grad(loss))(p, jnp.ones((2, 4)))
    names = sorted(p)  # the flatten order of a dict
    ready = collectives.grad_readiness(closed.jaxpr, names)
    assert ready["second"] < ready["first"]
    assert set(ready) == set(names)


def _one_device_spec():
    return ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True, "cpus": [0]}]})


def test_one_replica_lowers_as_before(monkeypatch):
    """N == 1: no readiness is read, no group, no option, no gradient
    collective in the program, nothing credited to ``overlap.buckets``."""
    def never(self, grad_jaxpr=None):
        raise AssertionError("one replica read the gradient's readiness")
    monkeypatch.setattr(gt.GraphTransformer, "_grad_ready_order", never)
    params, loss_fn, batch = _mlp()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                               resource_spec=_one_device_spec())
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch())
    runner.init(params)
    meta = runner.distributed_step.metadata
    assert meta["async_collectives"] == [] and meta["grad_sync_groups"] == []
    assert tel.counters()["overlap.buckets"] == 0.0
    # the loss's pmean over the size-1 axes is the program's one collective
    assert runner.lowered_text(batch()).count("stablehlo.all_reduce") == 1
    autodist_tpu.reset()


def test_options_only_for_a_tpu_with_more_than_one_replica():
    assert collectives.async_collective_options("cpu", 8) == {}
    assert collectives.async_collective_options("tpu", 1) == {}
    assert collectives.async_collective_options("gpu", 8) == {}
    on = collectives.async_collective_options("tpu", 4)
    assert on == collectives.ASYNC_COLLECTIVE_OPTIONS and on is not \
        collectives.ASYNC_COLLECTIVE_OPTIONS
    assert on["xla_enable_async_all_reduce"] is True
    assert on["xla_jf_crs_combiner_threshold_in_bytes"] == \
        collectives.PACK_BYTES


class _JitSpy:
    """``jax.jit`` as the lowering calls it, with what it was given."""

    def __init__(self):
        self.calls = []
        self._jit = jax.jit

    def __call__(self, fn, **kwargs):
        self.calls.append((getattr(fn, "__name__", str(fn)), kwargs))
        return self._jit(fn, **kwargs)

    def options_of(self, *names):
        return [kw.get("compiler_options") for n, kw in self.calls
                if any(part in n for part in names)]


def _drive_every_program(monkeypatch, spy):
    """Build on the eight-device CPU mesh and make every kind of program:
    step, fused step, eval, predict, decode."""
    monkeypatch.setattr(gt.jax, "jit", spy)
    params, loss_fn, batch = _mlp()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch())
    runner.init(params)
    runner.fit(iter([batch() for _ in range(4)]), fuse_steps=2)
    runner.run(batch())
    runner.evaluate(iter([batch()]))
    runner.predict({"x": batch()["x"]},
                   lambda p, b: {"h": b["x"] @ p["w1"]})
    dstep = runner.distributed_step
    dstate = {"x": np.zeros((8, 8), np.float32)}
    dstep.decode_program(lambda p, d: ({"y": d["x"] @ p["w1"]}, d), dstate)
    meta = dstep.metadata
    autodist_tpu.reset()
    return meta


def test_off_the_tpu_no_option_reaches_jit(monkeypatch):
    spy = _JitSpy()
    meta = _drive_every_program(monkeypatch, spy)
    assert meta["async_collectives"] == []
    assert len(spy.calls) >= 5
    assert all("compiler_options" not in kw for _, kw in spy.calls)


def test_only_training_programs_get_the_options(monkeypatch):
    """With options to give (a TPU's stand-in: one the CPU compiler takes),
    the per-step and the fused step programs compile with them; eval,
    predict and decode programs with none."""
    opts = {"xla_embed_ir_in_executable": False}
    monkeypatch.setattr(collectives, "async_collective_options",
                        lambda platform, replicas: dict(opts))
    spy = _JitSpy()
    meta = _drive_every_program(monkeypatch, spy)
    assert meta["async_collectives"] == sorted(opts)
    train = spy.options_of("local_step", "local_multi")
    assert len(train) >= 2 and all(o == opts for o in train)
    other = spy.options_of("local_eval", "local_predict", "local_decode")
    assert len(other) >= 3 and all(o is None for o in other)


def test_overlap_buckets_counts_the_groups_of_an_async_program(monkeypatch):
    monkeypatch.setattr(collectives, "async_collective_options",
                        lambda platform, replicas:
                        {"xla_embed_ir_in_executable": False})
    params, loss_fn, batch = _mlp(wide=True)
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(0.05), params, batch())
    groups = runner.distributed_step.metadata["grad_sync_groups"]
    assert tel.counters()["overlap.buckets"] == len(groups) > 0
    assert all(set(g) == {"kind", "vars", "bytes"} for g in groups)
    autodist_tpu.reset()
