"""Preemption plane (runtime/preemption.py): advance-notice departure.

Tier-1 legs: loud knob validation, the notice/plan/left KV protocol and
the operator drain CLI against a REAL coordination service, the
watchdog's departure-mark consultation (an announced leaver is never
escalated as dead), the chief's planned shrink published while the
leaver is ALIVE, deterministic SIGTERM chaining with the blackbox dump
hook (both orders, dump LAST), the deadline-budgeted rescue checkpoint
(taken and the skip branch), serving drain under concurrent submit
(in-flight completes, queued sheds typed with Retry-After), the
``faultinject`` preempt delivery (real SIGTERM, deadline SIGKILL), the
ADT432 build-time warning, and a REAL solo graceful departure plus a
REAL planned peer-departure reconfigure, end to end in subprocesses.
The randomized five-plane chaos campaign is the slow/chaos leg
(``tests/chaos_campaign.py``; 3 seeds nightly).
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from autodist_tpu.runtime import elastic, preemption
from autodist_tpu.runtime.coordination import (CoordinationClient,
                                               CoordinationServer)
from autodist_tpu.telemetry import spans as tel

HERE = os.path.dirname(os.path.abspath(__file__))
PORT = 15917


@pytest.fixture(scope="module")
def server():
    srv = CoordinationServer(port=PORT)
    srv.start()
    yield srv
    srv.stop()


@pytest.fixture(autouse=True)
def _clean_plane():
    yield
    elastic.clear()
    preemption.reset()


def _client(**kw):
    return CoordinationClient("127.0.0.1", PORT, **kw)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _counter(name):
    return tel.counters().get(name, 0.0)


# ----------------------------------------------------------- knob validation


def test_preempt_knobs_validated_loudly(monkeypatch):
    """Garbage/negative preemption knobs raise the typed config error
    NAMING the knob (the ElasticConfigError pattern) at bring-up."""
    monkeypatch.setenv("ADT_PREEMPT_DEADLINE_S", "soon")
    with pytest.raises(elastic.ElasticConfigError) as e:
        preemption.validate_preempt_knobs()
    assert e.value.knob == "ADT_PREEMPT_DEADLINE_S"

    monkeypatch.setenv("ADT_PREEMPT_DEADLINE_S", "-5")
    with pytest.raises(elastic.ElasticConfigError,
                       match="ADT_PREEMPT_DEADLINE_S"):
        preemption.validate_preempt_knobs()

    monkeypatch.setenv("ADT_PREEMPT_DEADLINE_S", "45")
    monkeypatch.setenv("ADT_PREEMPT_POLL_S", "-1")
    with pytest.raises(elastic.ElasticConfigError,
                       match="ADT_PREEMPT_POLL_S"):
        preemption.validate_preempt_knobs()

    monkeypatch.setenv("ADT_PREEMPT_POLL_S", "0")
    monkeypatch.setenv("ADT_DRAIN_RETRY_AFTER_S", "later")
    with pytest.raises(elastic.ElasticConfigError,
                       match="ADT_DRAIN_RETRY_AFTER_S"):
        preemption.validate_preempt_knobs()

    monkeypatch.setenv("ADT_DRAIN_RETRY_AFTER_S", "2.5")
    assert preemption.validate_preempt_knobs() == (45.0, 0.0, 2.5)


# ----------------------------------------------------------- notice protocol


def test_notice_protocol_roundtrip(server):
    """publish/read/plan/left/clear over a real service; the seq cursor
    advances on every publish so pollers re-scan only on change."""
    c = _client()
    seq0 = c.get(preemption.SEQ_KEY)
    before = _counter("preempt.notices")
    notice = preemption.publish_notice(c, "w7", deadline_s=30,
                                       reason="maintenance")
    assert _counter("preempt.notices") == before + 1
    assert c.get(preemption.SEQ_KEY) != seq0
    got = preemption.read_notice(c, "w7")
    assert got is not None and got.reason == "maintenance"
    assert got.worker == "w7"
    # the wire rounds timestamps to the microsecond
    assert abs(got.deadline - notice.deadline) < 1e-3
    assert 0 < got.remaining_s() <= 30

    preemption.publish_plan(c, "w7", 12, notice)
    plan = preemption.read_plan(c, "w7")
    assert plan["rescue_step"] == 12 and plan["reason"] == "maintenance"

    assert preemption.has_left(c, "w7") is False
    preemption.mark_left(c, "w7")
    assert preemption.has_left(c, "w7") is True

    preemption.clear_notice(c, "w7")
    assert preemption.read_notice(c, "w7") is None
    assert preemption.read_plan(c, "w7") is None
    assert preemption.has_left(c, "w7") is False

    # an expired notice reads as None (GC-stale: cancelled eviction)
    c.put(preemption.NOTICE_PREFIX + "w8", preemption.PreemptionNotice(
        "w8", time.time() - preemption.NOTICE_STALE_AFTER_S - 1,
        "drain").to_json())
    assert preemption.read_notice(c, "w8") is None
    c.close()


def test_drain_cli_publishes_and_reports(server, capsys):
    """The operator ``drain`` verb publishes the mark; ``status`` reads
    it back as JSON."""
    rc = preemption.main(["drain", "w-cli", "--deadline", "42",
                          "--reason", "kernel-upgrade",
                          "--port", str(PORT)])
    assert rc == 0
    assert "w-cli" in capsys.readouterr().out
    c = _client()
    notice = preemption.read_notice(c, "w-cli")
    assert notice is not None and notice.reason == "kernel-upgrade"
    assert 0 < notice.remaining_s() <= 42
    c.close()

    rc = preemption.main(["status", "w-cli", "--port", str(PORT)])
    assert rc == 0
    status = json.loads(capsys.readouterr().out)
    assert status["notice"]["reason"] == "kernel-upgrade"
    assert status["left"] is False


def test_maintenance_poller_one_shot(tmp_path):
    """The cloud maintenance hook: file existence signals the eviction,
    its JSON body carries deadline/reason, and the event is one-shot."""
    path = tmp_path / "maintenance.json"
    poller = preemption.MaintenancePoller(str(path))
    assert poller.check() is None
    path.write_text(json.dumps({"deadline_s": 90, "reason": "tpu-maint"}))
    notice = poller.check()
    assert notice is not None and notice.reason == "tpu-maint"
    assert 80 < notice.remaining_s() <= 90
    assert poller.check() is None  # consumed

    # a bare touch file uses the env-default deadline
    bare = tmp_path / "bare"
    bare.write_text("")
    notice = preemption.MaintenancePoller(str(bare)).check()
    assert notice is not None and notice.reason == "maintenance"

    # a body carrying ONLY a reason keeps it (deadline defaults)
    partial = tmp_path / "partial.json"
    partial.write_text(json.dumps({"reason": "kernel-upgrade"}))
    notice = preemption.MaintenancePoller(str(partial)).check()
    assert notice is not None and notice.reason == "kernel-upgrade"
    assert notice.remaining_s() > 0


# -------------------------------------------- watchdog × announced departure


def _mini_coordinator(tmp_path, monkeypatch, inrun=False):
    monkeypatch.setenv("ADT_COORDSVC_PORT", str(PORT))
    if inrun:
        monkeypatch.setenv("ADT_ELASTIC", "1")
        monkeypatch.setenv("ADT_ELASTIC_SYNC", "1")
        monkeypatch.setenv("ADT_ELASTIC_INRUN", "1")
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.runtime.cluster import Cluster
    from autodist_tpu.runtime.coordinator import Coordinator
    spec = tmp_path / "spec.yml"
    spec.write_text(
        "nodes:\n  - address: 127.0.0.1\n    chief: true\n    cpus: [0]\n"
        "  - address: localhost\n    cpus: [0]\n")
    return Coordinator("sid-preempt", Cluster(ResourceSpec(str(spec))),
                       heartbeat_timeout=5.0,
                       max_restarts=1 if inrun else 0)


def test_watchdog_consults_departure_mark(server, tmp_path, monkeypatch):
    """Satellite: an announced leaver whose heartbeat stops mid-handoff
    must NOT be declared dead (no unplanned-death escalation, no mark
    GC) — the departure mark wins over heartbeat silence until a grace
    past the deadline."""
    coord = _mini_coordinator(tmp_path, monkeypatch)
    c = _client()
    assert coord._is_departing(c, "wdep") is False
    preemption.publish_notice(c, "wdep", deadline_s=30, reason="drain")
    assert coord._is_departing(c, "wdep") is True
    assert "wdep" in coord._planned_departures
    # aged-out notice: a NEXT incarnation must be supervisable again
    coord._planned_departures["wdep"] = (
        time.time() - 2 * coord._heartbeat_timeout - 1)
    assert coord._is_departing(c, "wdep") is False
    assert "wdep" not in coord._planned_departures
    preemption.clear_notice(c, "wdep")
    coord.stop_watchdog()
    c.close()


def test_planned_shrink_published_while_leaver_alive(server, tmp_path,
                                                     monkeypatch):
    """The chief's watchdog answers an announced departure by publishing
    the survivor roster at epoch+1 BEFORE the leaver dies — no reap, no
    relaunch, no restart-budget spend — and a planned leaver's process
    exit is shutdown, never an abort."""
    coord = _mini_coordinator(tmp_path, monkeypatch, inrun=True)
    # the shrink-soundness gate has its own tests (test_elastic_epoch);
    # here it must not veto the published plan over an unreadable
    # test-strategy id
    monkeypatch.setattr(coord, "_shrink_unsound_reason", lambda a: None)
    c = _client()
    base = 300
    elastic.publish_epoch(c, base, ["127.0.0.1", "localhost"])
    before = _counter("preempt.planned_shrinks")
    coord._maybe_plan_departures(c)  # no notice: nothing happens
    assert elastic.read_epoch(c)[0] == base

    preemption.publish_notice(c, "localhost", deadline_s=30,
                              reason="maintenance")
    coord._maybe_plan_departures(c)
    epoch, roster = elastic.read_epoch(c)
    assert epoch == base + 1 and roster == ["127.0.0.1"]
    assert _counter("preempt.planned_shrinks") == before + 1
    assert coord._restarts == {}  # planned: no budget spent
    # only an actually-SHRUNK departure lets the process watcher treat
    # a nonzero exit as shutdown (unsound/chief departures fall through
    # to the whole-job restart their log promises)
    assert "localhost" in coord._departures_shrunk
    # idempotent: the handled departure is not re-planned next tick
    coord._maybe_plan_departures(c)
    assert elastic.read_epoch(c)[0] == base + 1
    preemption.clear_notice(c, "localhost")
    coord.stop_watchdog()
    coord.join()
    c.close()


# ----------------------------------------------- SIGTERM chaining (dump-last)


def _fire_sigterm_handler():
    handler = signal.getsignal(signal.SIGTERM)
    assert callable(handler), "no SIGTERM handler installed"
    handler(signal.SIGTERM, None)


@pytest.mark.parametrize("order", ["blackbox-first", "preempt-first"])
def test_sigterm_chain_both_fire_dump_last(tmp_path, monkeypatch, order):
    """Satellite: the preemption SIGTERM handler and the blackbox dump
    hook chain deterministically in BOTH install orders — both fire, the
    dump runs LAST (its event tail contains the notice), and the
    process survives (grace window, no default-disposition re-raise)."""
    from autodist_tpu.telemetry import blackbox
    monkeypatch.setenv("ADT_BLACKBOX_DIR", str(tmp_path))
    monkeypatch.setenv("ADT_PREEMPT_DEADLINE_S", "30")
    original = signal.getsignal(signal.SIGTERM)
    try:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        monkeypatch.setattr(blackbox, "_signal_hook_installed", False)
        monkeypatch.setattr(preemption, "_sigterm_installed", False)
        monkeypatch.setattr(preemption, "_signal_notice", None)
        # a guard is armed (grace active): the chain must not re-raise
        preemption._armed_guards.append(object())
        fr = blackbox.get_flight_recorder()
        fr.clear()
        if order == "blackbox-first":
            blackbox._install_hooks()
            assert preemption.install_sigterm_notice() is True
        else:
            assert preemption.install_sigterm_notice() is True
            blackbox._install_hooks()
        dumps_before = fr.dumps
        _fire_sigterm_handler()
        # both fired: the notice is live AND a dump landed
        assert preemption.signal_notice() is not None
        assert fr.dumps == dumps_before + 1
        dump = blackbox.load_dump(fr.last_dump_path)
        kinds = [e["kind"] for e in dump["events"]]
        # dump-last: the dump's own event tail already CONTAINS the
        # notice — the notice handler ran before the snapshot was taken
        assert "preempt.notice" in kinds
        assert "signal" in kinds
    finally:
        signal.signal(signal.SIGTERM, original)
        preemption.reset()


# --------------------------------------------------- serving drain satellite


def test_serving_drain_under_concurrent_submit(monkeypatch):
    """Satellite: drain with traffic in flight — the in-flight group's
    futures COMPLETE, queued futures shed typed with the Retry-After,
    post-drain submits shed immediately, and the serve.shed /
    serve.drained counters account all of it."""
    import optax

    import autodist_tpu
    from autodist_tpu import strategy as S
    from autodist_tpu.serving import (InferenceEngine, MicroBatcher,
                                      ServingConfig, ServingUnavailable)
    rng = np.random.RandomState(0)
    params = {"emb": rng.randn(16, 4).astype(np.float32),
              "w": rng.randn(4, 2).astype(np.float32)}

    def loss_fn(p, batch):
        import jax.numpy as jnp
        feat = jnp.take(p["emb"], batch["ids"], axis=0)
        return jnp.mean((feat @ p["w"] - batch["y"]) ** 2)

    def serve_fn(p, batch):
        import jax.numpy as jnp
        feat = jnp.take(p["emb"], batch["ids"], axis=0)
        return {"score": feat @ p["w"]}

    batch = {"ids": rng.randint(0, 16, size=(8,)).astype(np.int32),
             "y": rng.randn(8, 2).astype(np.float32)}
    requests = [{"ids": batch["ids"][i]} for i in range(8)]
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(0.1), params, batch)
    runner.init(params)
    engine = InferenceEngine(runner, serve_fn, requests[0],
                             ServingConfig(buckets=(8,),
                                           max_delay_ms=1.0)).warmup()
    from autodist_tpu.serving import active_batchers
    hold = threading.Event()
    real_run = engine.run_batch
    monkeypatch.setattr(
        engine, "run_batch",
        lambda reqs: (hold.wait(timeout=30), real_run(reqs))[1])
    mb = MicroBatcher(engine)
    assert mb in active_batchers()
    in_flight = mb.submit(requests[0])
    time.sleep(0.15)  # the worker took it and is blocked in run_batch
    queued = [mb.submit(r) for r in requests[1:3]]
    shed_before = _counter("serve.shed")
    drained_before = _counter("serve.drained")

    def release_soon():
        time.sleep(0.3)
        hold.set()
    threading.Thread(target=release_soon, daemon=True).start()
    shed = mb.drain(retry_after_s=7.5)
    assert shed == 2
    # in-flight COMPLETED during the drain — a real result, not a shed
    assert in_flight.result(timeout=5)["score"].shape == (2,)
    # queued futures carry the typed Retry-After shed
    for f in queued:
        with pytest.raises(ServingUnavailable) as e:
            f.result(timeout=1)
        assert e.value.retry_after_s == 7.5
    # post-drain submits shed synchronously, typed, with the Retry-After
    with pytest.raises(ServingUnavailable, match="draining") as e:
        mb.submit(requests[3])
    assert e.value.retry_after_s == 7.5
    stats = mb.stats()
    assert stats["drained"] == 1 and stats["shed"] >= 2
    assert _counter("serve.shed") == shed_before + 2
    assert _counter("serve.drained") == drained_before + 1
    mb.drain()  # idempotent
    autodist_tpu.reset()


# ------------------------------------------------ rescue deadline budgeting


def _build_tiny_runner(port, ckpt_dir, monkeypatch, preempt_poll="0.01"):
    import optax

    import autodist_tpu as adt
    from autodist_tpu import strategy
    monkeypatch.setenv("ADT_COORDSVC_PORT", str(port))
    monkeypatch.setenv("ADT_ELASTIC", "1")
    monkeypatch.setenv("ADT_ELASTIC_SYNC", "1")
    monkeypatch.setenv("ADT_ELASTIC_INRUN", "1")
    monkeypatch.setenv("ADT_ELASTIC_POLL_S", "0.01")
    monkeypatch.setenv("ADT_PREEMPT_POLL_S", preempt_poll)
    monkeypatch.setenv("ADT_CKPT_DIR", str(ckpt_dir))
    adt.reset()
    rng = np.random.RandomState(0)
    import jax
    params = {"w": jax.numpy.asarray(rng.randn(8, 4) * 0.3,
                                     jax.numpy.float32)}

    def loss_fn(p, batch):
        import jax.numpy as jnp
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    batch = {"x": rng.randn(8, 8).astype(np.float32),
             "y": rng.randn(8, 4).astype(np.float32)}
    ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
    runner = ad.build(loss_fn, optax.sgd(0.05), params, batch)
    runner.init(params)
    return runner, batch


def test_rescue_checkpoint_deadline_skip_branch(server, tmp_path,
                                                monkeypatch):
    """Satellite: when the remaining grace cannot cover the measured
    ckpt.save_ms p99 (× safety), the rescue save is SKIPPED — counted,
    no file written — and the departure goes straight to the handoff."""
    ckpt_dir = tmp_path / "ckpt"
    runner, batch = _build_tiny_runner(PORT, ckpt_dir, monkeypatch)
    runner.run(batch)
    # measured saves are catastrophically slow vs a 0.8s grace window
    tel.hist_observe("ckpt.save_ms", 60000.0)
    c = _client()
    preemption.publish_notice(c, runner._preempt.worker, deadline_s=0.8,
                              reason="spot")
    time.sleep(0.05)
    skips_before = _counter("preempt.rescue_skips")
    with pytest.raises(preemption.PlannedDeparture):
        for _ in range(5):
            runner.run(batch)
    assert _counter("preempt.rescue_skips") == skips_before + 1
    stats = runner.step_stats()["preempt"]
    assert stats["rescue_saves"] == 0.0 or not os.path.exists(ckpt_dir) \
        or not any(f.endswith(".meta.json") for f in os.listdir(ckpt_dir))
    assert stats["handoffs"] >= 1.0
    preemption.clear_notice(c, runner._preempt.worker)
    c.close()


def test_solo_graceful_departure_e2e(server, tmp_path, monkeypatch):
    """A REAL drain end to end (single worker, no survivors): operator
    notice → cluster-agreed rescue plan → committed rescue checkpoint →
    serving drained → PlannedDeparture with exit code 0 and the left
    stamp published; fit()'s unwind does not mask the departure."""
    ckpt_dir = tmp_path / "ckpt"
    runner, batch = _build_tiny_runner(PORT, ckpt_dir, monkeypatch)
    c = _client()
    worker = runner._preempt.worker
    runner.run(batch)

    def drain_soon():
        time.sleep(0.3)
        preemption.publish_notice(c, worker, deadline_s=30, reason="drain")
    threading.Thread(target=drain_soon, daemon=True).start()
    import itertools
    with pytest.raises(preemption.PlannedDeparture) as e:
        runner.fit(itertools.repeat(batch), steps=10_000)
    assert e.value.code == 0
    stats = runner.step_stats()["preempt"]
    assert stats["rescue_saves"] == 1.0
    assert stats["handoffs"] == 1.0 and stats["last_handoff_s"] > 0
    # the rescue checkpoint COMMITTED at the agreed step
    from autodist_tpu.checkpoint import integrity
    committed = [s for s in integrity.scan(str(ckpt_dir))
                 if s.state == "committed"]
    plan = preemption.read_plan(c, worker)
    assert committed and plan is not None
    assert max(s.step for s in committed) >= plan["rescue_step"]
    assert preemption.has_left(c, worker) is True
    # planned path: zero checkpoint-fallback restores
    assert _counter("ckpt.fallback") == 0.0
    preemption.clear_notice(c, worker)
    c.close()


def test_exclusion_epoch_outrunning_notice_poll_still_departs(
        server, tmp_path, monkeypatch):
    """Race: the chief publishes the shrink epoch right after the drain
    notice, and the leaver's epoch poll (fast) sees the exclusion before
    its throttled notice poll (here: 60 s) ever adopted the mark — the
    reconfigure path must consult the KV notice UNTHROTTLED and depart
    gracefully, never crash with the zombie FencedOut."""
    ckpt_dir = tmp_path / "ckpt"
    runner, batch = _build_tiny_runner(PORT, ckpt_dir, monkeypatch,
                                       preempt_poll="60")
    c = _client()
    worker = runner._preempt.worker
    runner.run(batch)
    m = elastic.current()
    # notice + exclusion land back to back, before any notice poll
    preemption.publish_notice(c, worker, deadline_s=30, reason="drain")
    elastic.publish_epoch(c, m.epoch + 1, ["the-survivor"])
    time.sleep(0.05)
    with pytest.raises(preemption.PlannedDeparture) as e:
        for _ in range(5):
            runner.run(batch)
    assert e.value.code == 0 and e.value.reason == "drain"
    assert runner.step_stats()["preempt"]["handoffs"] == 1.0
    preemption.clear_notice(c, worker)
    c.close()


def test_fence_yields_to_announced_departure_until_deadline(server):
    """The planned-shrink epoch may land BEFORE the leaver's final
    boundary: an ANNOUNCED leaver's writes (rescue checkpoint, flush,
    left stamp) must pass the epoch fence until its deadline — and be
    fenced as a zombie again after it (the SIGKILL has fired; a late
    incarnation must not write)."""
    c = _client()
    base = 400
    elastic.publish_epoch(c, base, ["chief", "wleave"])
    leaver = elastic.Membership("wleave", base, ["chief", "wleave"],
                                client_factory=_client)
    elastic.publish_epoch(c, base + 1, ["chief"])  # announced shrink
    with pytest.raises(elastic.FencedOut):
        leaver.fence("ckpt.save")  # un-announced: zombie semantics
    leaver.expect_departure(time.time() + 30)
    leaver.fence("ckpt.save")  # announced: final boundary proceeds
    leaver.fence("ps.push")
    leaver.expect_departure(time.time() - 1)  # deadline passed...
    leaver.expect_departure(time.time() + 30)  # ...never shrinks back
    leaver.fence("ckpt.save")
    leaver._departure_until = time.time() - 1  # force-expire
    with pytest.raises(elastic.FencedOut):
        leaver.fence("ckpt.save")  # past the deadline: fenced again
    leaver.close()
    c.close()


# --------------------------------------------------- faultinject preempt op


STUBBORN = ("import signal, sys, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: None)\n"
            "print('up', flush=True)\n"
            "time.sleep(60)\n")

GRACEFUL = ("import signal, sys, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))\n"
            "print('up', flush=True)\n"
            "time.sleep(60)\n")


def _spawn_target(code):
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True)
    assert proc.stdout.readline().strip() == "up"
    return proc


def test_deliver_preemption_sigterm_then_deadline_sigkill():
    """The preempt fault delivery: a stubborn target (ignores SIGTERM)
    is SIGKILLed at the deadline; a graceful one departs inside the
    window and is never touched by the killer."""
    from autodist_tpu.runtime import faultinject
    stubborn = _spawn_target(STUBBORN)
    killer = faultinject.deliver_preemption(stubborn.pid, deadline_s=0.5)
    assert stubborn.wait(timeout=10) == -signal.SIGKILL
    killer.join(timeout=5)

    graceful = _spawn_target(GRACEFUL)
    killer = faultinject.deliver_preemption(graceful.pid, deadline_s=2.0)
    assert graceful.wait(timeout=10) == 0  # exited inside the window
    killer.join(timeout=5)


@pytest.mark.chaos
def test_preempt_wire_op_fires_through_proxy(server):
    """The declarative ``{"op": "preempt"}`` wire rule delivers the real
    SIGTERM+deadline-SIGKILL when its nth matching RPC crosses the
    proxy."""
    from autodist_tpu.runtime.faultinject import FaultPlan, FaultyProxy
    stubborn = _spawn_target(STUBBORN)
    plan = FaultPlan({"faults": [
        {"op": "preempt", "match": "PUT", "nth": 2, "deadline_s": 0.5}]})
    with FaultyProxy("127.0.0.1", PORT, plan=plan,
                     preempt_pid=stubborn.pid) as proxy:
        c = CoordinationClient("127.0.0.1", proxy.port)
        c.put("preop/one", "1")     # nth=1: no fire
        assert stubborn.poll() is None
        c.put("preop/two", "2")     # nth=2: SIGTERM + deadline SIGKILL
        assert stubborn.wait(timeout=10) == -signal.SIGKILL
        assert "preempt:PUT" in plan.injected
        c.close()


# ------------------------------------------------------------------- ADT432


def test_adt432_warns_on_model_parallel_handoff():
    """Preemption handoff armed on a fail-fast (model-parallel) family
    warns at build time; data-parallel stays clean."""
    from autodist_tpu.analysis import rules
    mp = types.SimpleNamespace(
        graph_config=types.SimpleNamespace(
            mesh_shape={"data": 2, "model": 4}),
        node_config=[])
    diags = rules.verify_preemption(mp)
    assert [d.code for d in diags] == ["ADT432"]
    assert "model" in diags[0].message

    dp = types.SimpleNamespace(
        graph_config=types.SimpleNamespace(mesh_shape={"data": 8}),
        node_config=[])
    assert rules.verify_preemption(dp) == []
    degenerate = types.SimpleNamespace(
        graph_config=types.SimpleNamespace(
            mesh_shape={"data": 4, "model": 1}),
        node_config=[])
    assert rules.verify_preemption(degenerate) == []


# --------------------------------- planned peer departure: reconfigure e2e


PEER_DRIVER = """
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import optax
import autodist_tpu as adt
from autodist_tpu import strategy
from autodist_tpu.runtime import elastic, preemption
from autodist_tpu.runtime.coordination import (CoordinationClient,
                                               CoordinationServer)
from autodist_tpu.telemetry import spans as tel

outdir, announced = sys.argv[1], sys.argv[2] == "announced"
port = int(os.environ["ADT_COORDSVC_PORT"])
srv = CoordinationServer(port)
srv.start()

rng = np.random.RandomState(0)
params = {"w": jax.numpy.asarray(rng.randn(8, 4) * 0.3, jax.numpy.float32)}

def loss_fn(p, batch):
    return jax.numpy.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

batch = {"x": rng.randn(8, 8).astype(np.float32),
         "y": rng.randn(8, 4).astype(np.float32)}

# uninterrupted reference first (no elastic knobs read at build)
ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
step = ad.function(loss_fn, optimizer=optax.sgd(0.05), params=params)
ref = [float(step(batch)["loss"]) for _ in range(10)]
adt.reset()

os.environ["ADT_ELASTIC"] = "1"
os.environ["ADT_ELASTIC_SYNC"] = "1"
os.environ["ADT_ELASTIC_INRUN"] = "1"
os.environ["ADT_ELASTIC_POLL_S"] = "0.01"
os.environ["ADT_PREEMPT_POLL_S"] = "0.01"

# pre-publish a TWO-member roster (this process + a phantom peer about
# to be evicted) so the build adopts it: the survivor's view of a real
# 2-worker job whose peer announces departure
client = CoordinationClient("127.0.0.1", port)
me = "127.0.0.1"
elastic.publish_epoch(client, 1, [me, "peer-leaving"])

ad = adt.AutoDist(strategy_builder=strategy.AllReduce())
runner = ad.build(loss_fn, optax.sgd(0.05), params, batch)
runner.init(params)
m = elastic.current()
assert m is not None and m.roster == [me, "peer-leaving"], m.roster

losses = []
for i in range(10):
    losses.append(float(runner.run(batch)["loss"]))
    if i == 3 and announced:
        # the peer announces its departure: every process (this
        # survivor included) joins the rescue checkpoint and pre-stages
        # its snapshot for the announced shrink
        preemption.publish_notice(client, "peer-leaving", deadline_s=30,
                                  reason="maintenance")
        time.sleep(0.05)
    if i == 5:
        # the chief's planned shrink: survivor-only roster, published
        # while the leaver is still alive (here: the phantom peer)
        elastic.publish_epoch(client, 2, [me])
        time.sleep(0.05)

stats = runner.step_stats()
rec = tel.get_recorder()
reconf = [e for e in rec.events() if e.name == "elastic.reconfigure"]
out = {
    "ref": ref, "losses": losses,
    "reconfigs": stats["elastic"]["reconfigs"],
    "epoch": elastic.current().epoch,
    "preempt": stats["preempt"],
    "ckpt_fallback": tel.counters().get("ckpt.fallback", 0.0),
    "planned_flags": [bool(e.args.get("planned")) for e in reconf],
    "reconfigure_s": rec.durations_s("elastic.reconfigure"),
}
with open(os.path.join(outdir, "out.json"), "w") as f:
    json.dump(out, f)
print("DRIVER_DONE", flush=True)
srv.stop()
"""


@pytest.mark.parametrize("announced", [True, False],
                         ids=["announced", "unannounced"])
def test_peer_departure_reconfigures_without_fallback(tmp_path,
                                                      announced):
    """Acceptance core: a planned eviction of a sync peer completes the
    handoff from LIVE state — the surviving process rescue-checkpoints
    at the agreed step, pre-stages its snapshot, reconfigures under the
    announced shrink epoch with the ``planned`` flag on the downtime
    span, and ``ckpt.fallback`` stays at ZERO while the loss trajectory
    matches the uninterrupted run exactly. The same shrink with no
    notice before it: one reconfigure all the same, its snapshot taken
    inside the span (no ``planned`` flag, no rescue save), the same
    losses."""
    script = tmp_path / "driver.py"
    script.write_text(PEER_DRIVER)
    env = dict(os.environ)
    for k in ("ADT_WORKER", "ADT_ELASTIC", "ADT_ELASTIC_SYNC",
              "ADT_ELASTIC_INRUN", "ADT_AUTO_RESUME"):
        env.pop(k, None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "ADT_COORDSVC_PORT": str(_free_port()),
        "ADT_CKPT_DIR": str(tmp_path / "ckpt"),
        "ADT_TRACE": "1",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE)] +
            ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH")
             else [])),
    })
    proc = subprocess.run(
        [sys.executable, str(script), str(tmp_path),
         "announced" if announced else "unannounced"],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["reconfigs"] == 1 and out["epoch"] == 2, out
    # the survivor joined the cluster-agreed rescue checkpoint
    assert out["preempt"]["rescue_saves"] == float(announced), out["preempt"]
    # the handoff used LIVE state: the reconfigure ran with the
    # pre-staged snapshot (planned flag) and NEVER touched the
    # last-good-checkpoint fallback
    assert out["planned_flags"] == [announced], out
    assert out["ckpt_fallback"] == 0.0, out
    assert out["reconfigure_s"][0] > 0
    np.testing.assert_allclose(out["losses"], out["ref"],
                               rtol=1e-6, atol=1e-7)


# ------------------------------------------------------------ chaos campaign


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_campaign_one_seed(tmp_path):
    """One seeded five-plane campaign (wire + partition + ckpt + grad +
    preempt): SIGKILL lands ``deadline_s`` after the SIGTERM, a
    committed rescue checkpoint exists, and the restarted job's loss
    trajectory matches the uncrashed reference. The nightly workflow
    runs 3 seeds and uploads the transcripts."""
    sys.path.insert(0, HERE)
    try:
        from chaos_campaign import run_campaign
        transcript = run_campaign(4242, str(tmp_path))
    finally:
        sys.path.remove(HERE)
    inv = transcript["invariants"]
    assert inv["always_resumable"] and inv["zero_corrupt_committed"]
    assert inv["loss_continuity_max_rel_err"] < 1e-4
    assert os.path.exists(transcript["path"])
