"""Serving subsystem: strategy-compiled batched inference.

The serving tentpole's correctness contracts:

- **bitwise identity**: an engine dispatch on padded requests returns,
  row for row, exactly what the same compiled forward program
  (``DistributedStep.predict_program``) returns on the same padded
  inputs — for a PS-backed AND an AllReduce strategy — with the padded
  rows masked out of the fetches;
- **zero recompiles after warmup**: every bucket compiles once in
  :meth:`InferenceEngine.warmup`; steady-state traffic across mixed
  group sizes never grows the jit cache;
- **shed, never hang**: queue overflow, a closed batcher, and an
  exhausted PS-degradation window all fail with the typed
  :class:`ServingUnavailable` in bounded time, while the worker loop
  survives per-group errors and keeps serving;
- **pad-to-bucket** in ``stack_batches`` (repeat-last padding, caller
  masks) and its multi-process global-array refusal.
"""
import threading
import time
from unittest import mock

import jax
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.data.prefetch import stack_batches
from autodist_tpu.serving import (InferenceEngine, MicroBatcher,
                                  ServingConfig, ServingUnavailable)
from autodist_tpu.telemetry import spans as tel


# ---------------------------------------------------------------- fixture


def _make_problem(seed=0, n=16):
    """Tiny embedding scorer — the recommendation-shaped toy: a request
    is one {"ids": scalar} row of the training batch (labels dropped)."""
    rng = np.random.RandomState(seed)
    params = {"w": rng.randn(4, 2).astype(np.float32),
              "b": np.zeros((2,), np.float32),
              "emb": rng.randn(16, 4).astype(np.float32)}

    def loss_fn(p, batch):
        import jax.numpy as jnp
        feat = jnp.take(p["emb"], batch["ids"], axis=0)
        pred = feat @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def serve_fn(p, batch):
        import jax.numpy as jnp
        feat = jnp.take(p["emb"], batch["ids"], axis=0)
        return {"score": feat @ p["w"] + p["b"]}

    batch = {"ids": rng.randint(0, 16, size=(n,)).astype(np.int32),
             "y": rng.randn(n, 2).astype(np.float32)}
    requests = [{"ids": batch["ids"][i]} for i in range(n)]
    return params, loss_fn, serve_fn, batch, requests


def _build_runner(make_builder, train_steps=1):
    params, loss_fn, serve_fn, batch, requests = _make_problem()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=make_builder())
    runner = ad.build(loss_fn, optax.adam(0.1), params, batch)
    runner.init(params)
    for _ in range(train_steps):
        runner.run(batch)  # serve values that actually moved
    return runner, serve_fn, batch, requests


def _expected_scores(runner, ids):
    """Host-side reference: the CURRENT (trained) full params applied to
    ``ids`` — value-level (allclose) check; bitwise identity is asserted
    program-call-vs-program-call below."""
    full = {k: np.asarray(v) for k, v in runner.gather_params().items()}
    full.update({k: np.asarray(v)
                 for k, v in runner.distributed_step.pull_ps().items()})
    return np.take(full["emb"], np.asarray(ids), axis=0) @ full["w"] \
        + full["b"]


BUILDERS = [("PS", lambda: S.PS()), ("AllReduce", lambda: S.AllReduce())]


# ---------------------------------------------------------- stack_batches


def test_stack_batches_pads_by_repeating_last():
    group = [{"x": np.full((2,), i, np.float32)} for i in range(3)]
    out = stack_batches(group, pad_to=8)
    assert out["x"].shape == (8, 2)
    np.testing.assert_array_equal(out["x"][:3, 0], [0.0, 1.0, 2.0])
    # padded rows repeat the LAST real element — real data, no NaN risk
    np.testing.assert_array_equal(out["x"][3:, 0], [2.0] * 5)
    # pad_to == len is a plain stack
    np.testing.assert_array_equal(stack_batches(group, pad_to=3)["x"],
                                  out["x"][:3])


def test_stack_batches_pad_to_smaller_than_group_raises():
    group = [{"x": np.zeros((2,))} for _ in range(4)]
    with pytest.raises(ValueError, match="pad_to must be >="):
        stack_batches(group, pad_to=2)


def test_stack_batches_refuses_multiprocess_global_arrays():
    """A non-fully-addressable jax.Array cannot be re-stacked process-
    locally; the error must say what to do, not bubble jnp.stack's."""
    leaf = mock.MagicMock(spec=jax.Array)
    leaf.is_fully_addressable = False
    with pytest.raises(ValueError,
                       match="multi-process global arrays"):
        stack_batches([{"x": leaf}, {"x": leaf}])


# ----------------------------------------------------------------- engine


@pytest.mark.parametrize("name,make_builder", BUILDERS,
                         ids=[b[0] for b in BUILDERS])
def test_engine_bitwise_identity_and_zero_recompiles(name, make_builder):
    """The two acceptance criteria in one build: (a) after warming both
    buckets, mixed-size traffic performs ZERO recompiles; (b) every
    served row is bitwise identical to the same compiled program called
    directly on the same padded inputs, padding masked out."""
    runner, serve_fn, batch, requests = _build_runner(make_builder)
    engine = InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=(8, 16), snapshot_max_age_s=0.0)).warmup()
    dstep = runner.distributed_step
    # predict_program caches per (serve_fn, donate, structure): the
    # engine's own program comes back — identical executable, not merely
    # an equivalent one
    program = dstep.predict_program(
        serve_fn, donate_batch=True,
        example_batch=stack_batches([requests[0]], pad_to=8))
    for n in (3, 8, 11, 16):
        got, n_out = engine.run_batch(requests[:n])
        assert n_out == n
        assert got["score"].shape == (n, 2)
        bucket = engine.bucket_for(n)
        host = stack_batches(requests[:n], pad_to=bucket)
        placed = runner.remapper.remap_feed(host)
        direct = runner.remapper.remap_fetch(
            program(runner.state, dstep.pull_ps(), placed))
        # bitwise, not allclose: same executable, same inputs
        np.testing.assert_array_equal(got["score"],
                                      np.asarray(direct["score"])[:n])
        np.testing.assert_allclose(
            got["score"], _expected_scores(runner, host["ids"][:n]),
            rtol=1e-5, atol=1e-6)
    assert engine.recompiles_after_warmup() == 0
    assert engine.stats["padded_rows"] == (8 - 3) + (16 - 11)
    # per-request convenience fans out one tree per request
    rows = engine.predict(requests[:3])
    assert len(rows) == 3
    np.testing.assert_array_equal(
        np.stack([r["score"] for r in rows]),
        engine.run_batch(requests[:3])[0]["score"])


def test_bucket_validation_and_selection():
    runner, serve_fn, _, requests = _build_runner(lambda: S.AllReduce())
    replicas = runner.remapper.num_replicas
    # defaults round up to replica multiples
    engine = InferenceEngine(runner, serve_fn, requests[0])
    assert all(b % replicas == 0 for b in engine.buckets)
    assert engine.buckets == tuple(sorted(engine.buckets))
    eng = InferenceEngine(runner, serve_fn, requests[0],
                          ServingConfig(buckets=(8, 16)))
    assert eng.bucket_for(1) == 8 and eng.bucket_for(9) == 16
    with pytest.raises(ServingUnavailable, match="largest bucket"):
        eng.bucket_for(17)
    with pytest.raises(ValueError, match="not multiples"):
        InferenceEngine(runner, serve_fn, requests[0],
                        ServingConfig(buckets=(replicas + 1,)))
    with pytest.raises(ValueError, match="duplicate"):
        InferenceEngine(runner, serve_fn, requests[0],
                        ServingConfig(buckets=(8, 8)))
    with pytest.raises(ValueError):
        ServingConfig(max_delay_ms=-1)
    with pytest.raises(ValueError):
        ServingConfig(max_queue=0)


def test_engine_degraded_window_then_shed_then_recovery(monkeypatch):
    """The PR 1 staleness-window contract on the serving side: snapshot
    refresh failures serve the LAST good snapshot for ``degraded_batches``
    batches (counted), then shed with the typed error; a successful
    refresh resets the window."""
    runner, serve_fn, _, requests = _build_runner(lambda: S.PS())
    engine = InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=(8,), snapshot_max_age_s=0.0,
                      degraded_batches=2)).warmup()
    good, _ = engine.run_batch(requests[:4])
    dstep = runner.distributed_step
    real_pull = dstep.pull_ps

    def failing_pull():
        raise OSError("coordination service unreachable")

    c0 = tel.counters()["serve.degraded"]
    monkeypatch.setattr(dstep, "pull_ps", failing_pull)
    for i in (1, 2):  # inside the window: serve the last snapshot
        degraded, _ = engine.run_batch(requests[:4])
        np.testing.assert_array_equal(degraded["score"], good["score"])
        assert engine.stats["degraded"] == i
    assert tel.counters()["serve.degraded"] == c0 + 2
    with pytest.raises(ServingUnavailable, match="degraded window"):
        engine.run_batch(requests[:4])
    # the engine object survives the shed: recovery resets the window
    monkeypatch.setattr(dstep, "pull_ps", real_pull)
    recovered, _ = engine.run_batch(requests[:4])
    np.testing.assert_array_equal(recovered["score"], good["score"])
    assert engine._degraded_used == 0


def test_engine_requires_initialized_runner():
    params, loss_fn, serve_fn, batch, requests = _make_problem()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(0.1), params, batch)
    engine = InferenceEngine(runner, serve_fn, requests[0],
                             ServingConfig(buckets=(8,)))
    with pytest.raises(RuntimeError, match="uninitialized"):
        engine.run_batch(requests[:2])


# ------------------------------------------------------------ microbatcher


def test_microbatcher_fans_out_per_request():
    """Concurrent submits group into padded buckets and fan back out:
    every caller gets ITS row, latency histogram + counters account every
    request."""
    runner, serve_fn, batch, requests = _build_runner(lambda: S.PS())
    engine = InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=(8, 16), max_delay_ms=20.0)).warmup()
    with MicroBatcher(engine) as mb:
        futures = [(r, mb.submit(r)) for r in requests[:12]]
        for r, f in futures:
            row = f.result(timeout=30)
            assert row["score"].shape == (2,)
            np.testing.assert_allclose(
                row["score"], _expected_scores(runner, r["ids"]),
                rtol=1e-5, atol=1e-6)
        one = mb.predict_one(requests[0], timeout=30)
        np.testing.assert_allclose(
            one["score"], _expected_scores(runner, requests[0]["ids"]),
            rtol=1e-5, atol=1e-6)
        stats = mb.stats()
    assert stats["requests"] == 13 and stats["fan_out"] == 13
    assert stats["errors"] == 0 and stats["shed"] == 0
    assert stats["recompiles_after_warmup"] == 0
    # grouped dispatches, not 13 size-1 batches (20ms deadline, 12
    # requests enqueued before the worker wakes)
    assert stats["batches"] < 13
    assert stats["p50_ms"] is not None and stats["p99_ms"] is not None
    assert stats["p99_ms"] >= stats["p50_ms"]


def test_microbatcher_sheds_on_queue_full_and_close(monkeypatch):
    runner, serve_fn, _, requests = _build_runner(lambda: S.PS())
    engine = InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=(8,), max_queue=2)).warmup()
    release = threading.Event()
    real_run = engine.run_batch

    def slow_run(reqs):
        release.wait(timeout=30)
        return real_run(reqs)

    monkeypatch.setattr(engine, "run_batch", slow_run)
    mb = MicroBatcher(engine)
    try:
        first = mb.submit(requests[0])  # consumed by the (blocked) worker
        time.sleep(0.1)
        queued = [mb.submit(r) for r in requests[1:3]]  # fills the queue
        with pytest.raises(ServingUnavailable, match="queue full"):
            mb.submit(requests[3])
        assert mb.stats()["shed"] == 1
    finally:
        release.set()
    first.result(timeout=30)
    for f in queued:
        f.result(timeout=30)
    mb.close()
    with pytest.raises(ServingUnavailable, match="closed"):
        mb.submit(requests[0])


def test_microbatcher_close_fails_still_queued_futures(monkeypatch):
    runner, serve_fn, _, requests = _build_runner(lambda: S.PS())
    engine = InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=(8,))).warmup()
    hold = threading.Event()
    real_run = engine.run_batch
    monkeypatch.setattr(
        engine, "run_batch",
        lambda reqs: (hold.wait(timeout=30), real_run(reqs))[1])
    mb = MicroBatcher(engine)
    mb.submit(requests[0])
    time.sleep(0.1)
    straggler = mb.submit(requests[1])

    def unblock():
        time.sleep(0.3)
        hold.set()
    threading.Thread(target=unblock, daemon=True).start()
    mb.close()
    # whatever close could not drain carries the typed shed, not a hang
    if not straggler.done():
        straggler.result(timeout=1)
    else:
        exc = straggler.exception(timeout=1)
        assert exc is None or isinstance(exc, ServingUnavailable)


def test_microbatcher_survives_group_errors_and_typed_sheds(monkeypatch):
    """A malformed request fails ITS group's futures with the real error;
    a ServingUnavailable from the engine (degradation exhausted) sheds
    the group; the worker keeps serving afterwards in both cases."""
    runner, serve_fn, _, requests = _build_runner(lambda: S.PS())
    engine = InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=(8,), max_delay_ms=1.0)).warmup()
    with MicroBatcher(engine) as mb:
        bad = mb.submit({"ids": np.zeros((3, 3), np.float32)})  # bad tree
        with pytest.raises(Exception) as ei:
            bad.result(timeout=30)
        assert not isinstance(ei.value, ServingUnavailable)
        real_run = engine.run_batch
        monkeypatch.setattr(
            engine, "run_batch",
            mock.MagicMock(side_effect=ServingUnavailable("window out")))
        shed = mb.submit(requests[0])
        with pytest.raises(ServingUnavailable):
            shed.result(timeout=30)
        monkeypatch.setattr(engine, "run_batch", real_run)
        good = mb.submit(requests[1])  # the worker thread is still alive
        np.testing.assert_allclose(
            good.result(timeout=30)["score"],
            _expected_scores(runner, requests[1]["ids"]),
            rtol=1e-5, atol=1e-6)
        stats = mb.stats()
        assert stats["errors"] == 1 and stats["shed"] >= 1


# -------------------------------------------------- runner predict / eval


def test_runner_predict_named_fetches_match_reference():
    runner, serve_fn, batch, requests = _build_runner(lambda: S.PS())
    feats = {"ids": batch["ids"]}
    out = runner.predict(feats, serve_fn)
    assert set(out) == {"score"}
    assert out["score"].shape == (16, 2)
    np.testing.assert_allclose(out["score"],
                               _expected_scores(runner, batch["ids"]),
                               rtol=1e-5, atol=1e-6)
    # snapshot reuse path (the caller-loop contract evaluate also uses)
    snap = runner.distributed_step.pull_ps()
    again = runner.predict(feats, serve_fn, ps_vals=snap)
    np.testing.assert_array_equal(np.asarray(out["score"]),
                                  np.asarray(again["score"]))


def test_evaluate_weights_scalars_by_example_count():
    """The mean-of-means fix: a ragged final batch contributes by its
    example count, not as a full batch's worth of mean."""
    runner, serve_fn, batch, _ = _build_runner(lambda: S.PS(),
                                               train_steps=0)
    rng = np.random.RandomState(7)
    big = {"ids": rng.randint(0, 16, size=(16,)).astype(np.int32),
           "y": rng.randn(16, 2).astype(np.float32)}
    small = {"ids": rng.randint(0, 16, size=(8,)).astype(np.int32),
             "y": 10.0 + rng.randn(8, 2).astype(np.float32)}
    loss_big = runner.evaluate([big])["loss"]
    loss_small = runner.evaluate([small])["loss"]
    combined = runner.evaluate([big, small])["loss"]
    weighted = (16 * loss_big + 8 * loss_small) / 24
    naive = (loss_big + loss_small) / 2
    np.testing.assert_allclose(combined, weighted, rtol=1e-6)
    assert abs(combined - naive) > 1e-3  # the bias the fix removes


# ----------------------------------------------- batcher degradation paths
# (pure-python fake engine: these contracts are the BATCHER's — queue
# accounting, Retry-After population, deadlines, brownout — and must be
# testable without compiling a program)


class _FakeEngine:
    """Minimal engine surface the MicroBatcher consumes. ``block`` (a
    threading.Event) parks the FIRST dispatch until set, so tests can
    pile up a queue behind a busy worker deterministically."""

    def __init__(self, config=None, block=None):
        self.config = config or ServingConfig(buckets=(8,),
                                              max_delay_ms=0.0)
        self.max_batch = 8
        self.buckets = (8,)
        self.stats = {"padded_rows": 0}
        self._block = block

    def run_batch(self, requests):
        if self._block is not None:
            self._block.wait(timeout=30)
        return list(requests), len(requests)

    def fan_out(self, fetched, n):
        return fetched

    def recompiles_after_warmup(self):
        return 0


def _gauge():
    return tel.gauges().get("serve.queue_depth")


def test_queue_depth_gauge_fresh_after_traffic_stops():
    """Regression: the gauge was only written on submit(), so it read
    stale-high forever once traffic stopped. The worker loop now writes
    it after EVERY wakeup, so an idle tier reads 0."""
    mb = MicroBatcher(_FakeEngine())
    futs = [mb.submit({"x": i}) for i in range(6)]
    for f in futs:
        f.result(timeout=5)
    deadline = time.perf_counter() + 5
    while _gauge() != 0 and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert _gauge() == 0
    mb.close()


def test_queue_depth_gauge_zero_after_drain():
    block = threading.Event()
    mb = MicroBatcher(_FakeEngine(block=block))
    mb.submit({"x": 0})              # in-flight, parked
    time.sleep(0.05)
    for i in range(4):
        mb.submit({"x": i})          # queued behind the parked worker
    assert _gauge() >= 1
    threading.Timer(0.1, block.set).start()
    mb.drain(timeout=10)
    assert _gauge() == 0


def test_queue_full_shed_carries_computed_clamped_retry_after():
    """Regression: queue-full sheds raised with retry_after_s=None.
    Every shed now carries a populated hint — the drain knob before any
    measurement exists, the measured drain-rate estimate after."""
    block = threading.Event()
    mb = MicroBatcher(_FakeEngine(block=block), max_queue=2)
    mb.submit({"x": 0})              # in-flight, parked
    time.sleep(0.05)
    mb.submit({"x": 1})
    mb.submit({"x": 2})              # queue now at max_queue
    with pytest.raises(ServingUnavailable) as ei:
        mb.submit({"x": 3})
    # no group has completed yet: the knob is the honest fallback
    assert ei.value.retry_after_s == pytest.approx(5.0)
    block.set()
    deadline = time.perf_counter() + 5
    while mb._drain_rate is None and time.perf_counter() < deadline:
        time.sleep(0.005)
    # measured now: still populated, and clamped to the sane band
    retry = mb._computed_retry_after(depth=4)
    assert retry is not None and 0.05 <= retry <= 60.0
    mb.close()


def test_closed_batcher_shed_carries_retry_after():
    """Regression: a submit against a plainly closed (not draining)
    batcher shed with retry_after_s=None."""
    mb = MicroBatcher(_FakeEngine())
    mb.close()
    with pytest.raises(ServingUnavailable) as ei:
        mb.submit({"x": 0})
    assert ei.value.retry_after_s == pytest.approx(5.0)


def test_close_while_queued_sheds_with_retry_after():
    """Close with the worker wedged mid-dispatch: whatever is still
    queued when the join times out sheds typed WITH a Retry-After (the
    regression: it shed with None)."""
    block = threading.Event()
    mb = MicroBatcher(_FakeEngine(block=block))
    f0 = mb.submit({"x": 0})         # in-flight, parked for the whole close
    time.sleep(0.05)
    queued = [mb.submit({"x": i}) for i in range(2)]
    mb.close(timeout=0.2)            # join times out; queue must shed
    for f in queued:
        with pytest.raises(ServingUnavailable) as ei:
            f.result(timeout=5)
        assert ei.value.retry_after_s == pytest.approx(5.0)
    block.set()                      # release the worker; in-flight lands
    f0.result(timeout=5)


def test_expired_deadline_sheds_before_dispatch():
    """A request whose deadline passed while it queued is shed at group
    time — typed, with a populated Retry-After — instead of consuming a
    dispatch slot."""
    block = threading.Event()
    mb = MicroBatcher(_FakeEngine(block=block))
    before = tel.counters().get("serve.deadline_shed", 0.0)
    mb.submit({"x": 0})              # in-flight, parked
    time.sleep(0.05)
    doomed = mb.submit({"x": 1}, deadline_s=0.01)
    alive = mb.submit({"x": 2})
    time.sleep(0.05)                 # the deadline lapses in queue
    block.set()
    assert alive.result(timeout=5) == {"x": 2}
    with pytest.raises(ServingUnavailable) as ei:
        doomed.result(timeout=5)
    assert ei.value.retry_after_s is not None
    assert mb.stats_local["deadline_shed"] == 1
    assert tel.counters()["serve.deadline_shed"] == before + 1
    mb.close()


def test_brownout_widens_group_deadline_under_sustained_overload():
    block = threading.Event()
    cfg = ServingConfig(buckets=(8,), max_delay_ms=1.0, max_queue=8,
                        brownout_queue_frac=0.5, brownout_sustain_s=0.0,
                        brownout_delay_factor=3.0)
    mb = MicroBatcher(_FakeEngine(config=cfg, block=block))
    mb.submit({"x": 0})              # in-flight, parked
    time.sleep(0.05)
    for i in range(6):               # queue past frac*max_queue, twice
        mb.submit({"x": i})          # observed (arm, then enter)
    assert mb.stats()["brownout"] == {"active": True, "entries": 1}
    assert mb._effective_delay_s == pytest.approx(3.0 * mb.max_delay_s)
    tel_entries = tel.counters().get("serve.brownouts", 0.0)
    assert tel_entries >= 1
    block.set()
    # backlog recedes: the worker loop exits brownout at half the entry
    # threshold and restores the configured deadline
    deadline = time.perf_counter() + 5
    while (mb.stats()["brownout"]["active"]
           and time.perf_counter() < deadline):
        time.sleep(0.005)
    assert mb.stats()["brownout"]["active"] is False
    assert mb._effective_delay_s == pytest.approx(mb.max_delay_s)
    mb.close()


def test_stats_autoscale_subdict_stable_keys():
    """The autoscale sub-dict rides stats() with stable keys whether or
    not a controller runs in this process (pre-registered counters)."""
    mb = MicroBatcher(_FakeEngine())
    sub = mb.stats()["autoscale"]
    assert set(sub) == {"grows", "shrinks", "holds", "refusals"}
    mb.close()


def test_brownout_config_validation():
    with pytest.raises(ValueError, match="brownout_queue_frac"):
        ServingConfig(brownout_queue_frac=0.0)
    with pytest.raises(ValueError, match="brownout_delay_factor"):
        ServingConfig(brownout_delay_factor=0.5)


# ------------------ the recommendation flagships behind the micro-batcher


def _flagship(label):
    """(runner, serve_fn, requests) of a tiny DLRM under Parallax (tables
    on load-balanced PS, dense MLPs on AllReduce) or a tiny NCF on host
    PS, one train step in."""
    if label == "dlrm":
        from autodist_tpu.models.dlrm import DLRMConfig, make_train_setup
        loss_fn, params, batch, apply_fn = make_train_setup(
            DLRMConfig.tiny(), batch_size=64)
        keys, builder = ("dense", "sparse"), S.Parallax()
    else:
        from autodist_tpu.models.ncf import NCFConfig, make_train_setup
        loss_fn, params, batch, apply_fn = make_train_setup(
            NCFConfig.tiny(), batch_size=64)
        keys, builder = ("user", "item"), S.PS()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=builder)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    runner.run(batch)
    requests = [{k: np.asarray(batch[k])[i] for k in keys} for i in range(64)]

    def serve_fn(p, b):
        return {"score": apply_fn(p, *(b[k] for k in keys))}
    return runner, serve_fn, requests


def _closed_loop(mb, requests, clients=8, each=25):
    """``clients`` threads, each submitting ``each`` requests one at a
    time and waiting for the answer. Returns (answered, shed, errored)."""
    counts = [[0, 0, 0] for _ in range(clients)]

    def client(i):
        rng = np.random.RandomState(i)
        for _ in range(each):
            try:
                mb.submit(requests[rng.randint(len(requests))]).result(
                    timeout=60)
                counts[i][0] += 1
            except ServingUnavailable:
                counts[i][1] += 1
            except Exception:  # noqa: BLE001 - counted, the caller asserts
                counts[i][2] += 1
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    return tuple(sum(c[j] for c in counts) for j in range(3))


def _flagship_engine(runner, serve_fn, requests):
    replicas = runner.remapper.num_replicas
    return InferenceEngine(
        runner, serve_fn, requests[0],
        ServingConfig(buckets=(4 * replicas, 8 * replicas),
                      max_delay_ms=1.0)).warmup()


@pytest.mark.parametrize("label", ["dlrm", "ncf"])
def test_a_flagship_under_closed_loop_traffic_answers_all_and_recompiles_nothing(
        label):
    """Eight closed-loop clients against a tiny DLRM (Parallax) and a tiny
    NCF (host PS), two-feature requests: every one answered, none shed or
    errored, no bucket compiled again; and, traced, a request's whole
    path is in a trace that validates: enqueue, the group's batch, its
    dispatch and its readback."""
    from autodist_tpu.telemetry import export
    tel.configure("1")
    try:
        runner, serve_fn, requests = _flagship(label)
        with MicroBatcher(_flagship_engine(runner, serve_fn, requests)) as mb:
            assert _closed_loop(mb, requests) == (200, 0, 0)
            stats = mb.stats()
        names = {e.name for e in tel.get_recorder().events()}
        trace = export.chrome_trace()
    finally:
        tel.configure(None)
    assert stats["errors"] == 0 and stats["shed"] == 0
    assert stats["recompiles_after_warmup"] == 0
    assert stats["fan_out"] == 200 and stats["batches"] <= 200
    assert {"serve.enqueue", "serve.batch", "serve.dispatch",
            "serve.readback"} <= names
    assert export.validate_chrome_trace(trace) == []


def test_a_replica_behind_a_faulted_wire_answers_or_sheds_typed_and_counts_it(
        monkeypatch, tmp_path):
    """Degraded but alive, on the real wire: NCF's PS store re-wired as a
    serving replica that owns nothing and fetches every value over the
    coordination service through a ``FaultyProxy`` that resets every bulk
    read. The first batches are answered from the last good snapshot
    (counted as degraded), the rest shed typed once the window is spent;
    no request errors and none hangs."""
    import socket

    from autodist_tpu.parallel.ps import PSStore
    from autodist_tpu.runtime import ps_service
    from autodist_tpu.runtime.coordination import CoordinationServer
    from autodist_tpu.runtime.faultinject import FaultPlan, FaultyProxy
    from autodist_tpu.runtime.resilience import ResilientCoordinationClient

    monkeypatch.setenv("ADT_BLACKBOX_DIR", str(tmp_path))  # the breaker dumps
    runner, serve_fn, requests = _flagship("ncf")
    engine = _flagship_engine(runner, serve_fn, requests)
    store = runner.distributed_step.ps_store
    owner_host, = {d.split(":")[0] for p in store.plans.values()
                   for d in p.destinations if d}
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = CoordinationServer(port=port)
    server.start()
    plan = FaultPlan({"seed": 3, "faults": [
        {"op": "reset", "match": "BGETB", "nth": 1, "repeat": True}]})
    proxy = FaultyProxy("127.0.0.1", port, plan=plan).start()
    owner = PSStore(dict(store.plans), store._var_infos, store._optimizer)

    def service(host):
        return ps_service.CoordPSService(
            lambda: ResilientCoordinationClient(
                "127.0.0.1", proxy.port, rpc_timeout=2.0, max_retries=2,
                seed=0), prefix="ps:" + host)
    try:
        owner.init_params(store.full_values())
        owner.enable_serving(service, my_host=owner_host)
        store.enable_serving(service, my_host="serving-replica")
        engine.config.snapshot_max_age_s = 0.0  # a refresh every batch
        before = tel.counters()
        with MicroBatcher(engine) as mb:
            answered, shed, errored = _closed_loop(mb, requests, each=10)
            stats = mb.stats()
        after = tel.counters()
    finally:
        proxy.stop()
        owner.close()
        server.stop()
    assert errored == 0 and answered + shed == 80
    assert answered > 0 and shed > 0, (answered, shed)
    assert stats["recompiles_after_warmup"] == 0
    assert len(plan.injected) > 0
    assert after["serve.degraded"] - before.get("serve.degraded", 0) >= 1
    assert after["serve.shed"] - before.get("serve.shed", 0) == shed
