"""Load-adaptive serving fleet (serving/autoscale.py).

Tier-1 legs: the AutoscalePolicy unit matrix — hysteresis (no flap
across the band edge), sustain windows, per-direction cooldown
enforcement (stamped by the ACTUATOR, not the decision), min/max
clamps, stale-telemetry holds — plus the FleetAutoscaler against a REAL
coordination service: grow-on-join admission, refusal onto a worker
with a pending preemption notice, planned drain-then-shrink through
``retire_worker``, and the epoch fence (a decision computed against a
stale epoch is dropped as ``FencedOut``, never double-applied). The
ADT440/441 lints run at controller construction. The end-to-end load
ramp (2→4→2 with live traffic behind the real engine and micro-batcher)
closes the file; its oscillating-load twin is ``slow`` (the nightly chaos
workflow runs it).
"""
import socket
import time
import types

import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S

from autodist_tpu.analysis import rules
from autodist_tpu.analysis.diagnostics import DiagnosticError
from autodist_tpu.runtime import elastic, preemption
from autodist_tpu.runtime.coordination import (CoordinationClient,
                                               CoordinationServer)
from autodist_tpu.serving import (InferenceEngine, MicroBatcher,
                                  ServingConfig, ServingUnavailable)
from autodist_tpu.serving.autoscale import (AutoscalePolicy,
                                            AutoscaleSignals,
                                            FleetAutoscaler, lint_policy)
from autodist_tpu.telemetry import spans as tel


def _policy(**kw):
    base = dict(min_replicas=1, max_replicas=4, queue_high=10.0,
                queue_low=2.0, sustain_s=1.0, grow_cooldown_s=5.0,
                shrink_cooldown_s=5.0)
    base.update(kw)
    return AutoscalePolicy(**base)


def _sig(depth, **kw):
    return AutoscaleSignals(queue_depth=depth, **kw)


# --------------------------------------------------------- config validation


def test_policy_rejects_bad_bounds():
    with pytest.raises(ValueError, match="min_replicas"):
        _policy(min_replicas=0)
    with pytest.raises(ValueError, match="clamp is empty"):
        _policy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="hysteresis band is empty"):
        _policy(queue_high=5.0, queue_low=5.0)
    with pytest.raises(ValueError, match=">= 0"):
        _policy(sustain_s=-1.0)


# ------------------------------------------------------------- decision core


def test_sustained_overload_grows():
    p = _policy()
    assert p.decide(_sig(50), replicas=2, now=0.0).direction == "hold"
    d = p.decide(_sig(50), replicas=2, now=1.5)
    assert d.direction == "grow" and d.target == 3


def test_sustained_idle_shrinks():
    p = _policy()
    p.decide(_sig(0), replicas=3, now=0.0)
    d = p.decide(_sig(0), replicas=3, now=1.5)
    assert d.direction == "shrink" and d.target == 2


def test_hysteresis_in_band_resets_sustain():
    """A signal dipping back INTO the band must re-earn its full
    sustain window — the excursion timer does not accumulate across
    band re-entries, which is what prevents edge flap."""
    p = _policy()
    p.decide(_sig(50), replicas=2, now=0.0)      # above: arms
    p.decide(_sig(5), replicas=2, now=0.6)       # in-band: resets
    d = p.decide(_sig(50), replicas=2, now=1.2)  # above again
    assert d.direction == "hold"                 # 1.2s total, 0s sustained
    assert p.decide(_sig(50), replicas=2, now=2.5).direction == "grow"


def test_hysteresis_falling_below_high_does_not_arm_shrink():
    """Between the bands NOTHING happens: dropping out of overload to a
    mid-band depth must not start the idle timer."""
    p = _policy()
    p.decide(_sig(50), replicas=3, now=0.0)
    p.decide(_sig(5), replicas=3, now=1.0)       # mid-band, NOT idle
    d = p.decide(_sig(5), replicas=3, now=10.0)  # still mid-band
    assert d.direction == "hold" and d.reason == "in-band"


def test_cooldown_stamped_by_actuator_not_decision():
    """decide() returning "grow" must NOT start the grow cooldown — a
    refused/fenced actuation would otherwise burn it with no scale
    event. Only note_scaled (the actuator's confirmation) stamps it."""
    p = _policy()
    p.decide(_sig(50), replicas=2, now=0.0)
    assert p.decide(_sig(50), replicas=2, now=1.5).direction == "grow"
    # not actuated: the same sustained state still commands a grow
    assert p.decide(_sig(50), replicas=2, now=1.6).direction == "grow"
    p.note_scaled("grow", now=1.6)
    # actuated: cooldown holds, and the sustain timer was reset
    p.decide(_sig(50), replicas=3, now=1.7)
    d = p.decide(_sig(50), replicas=3, now=3.0)
    assert d.direction == "hold" and "cooldown" in d.reason
    assert p.decide(_sig(50), replicas=3, now=7.0).direction == "grow"


def test_shrink_cooldown_enforced():
    p = _policy()
    p.decide(_sig(0), replicas=4, now=0.0)
    assert p.decide(_sig(0), replicas=4, now=1.5).direction == "shrink"
    p.note_scaled("shrink", now=1.5)
    p.decide(_sig(0), replicas=3, now=1.6)
    d = p.decide(_sig(0), replicas=3, now=3.5)
    assert d.direction == "hold" and "cooldown" in d.reason
    assert p.decide(_sig(0), replicas=3, now=8.0).direction == "shrink"


def test_min_max_clamps():
    p = _policy(min_replicas=2, max_replicas=3)
    p.decide(_sig(50), replicas=3, now=0.0)
    d = p.decide(_sig(50), replicas=3, now=2.0)
    assert d.direction == "hold" and "max_replicas" in d.reason
    p2 = _policy(min_replicas=2, max_replicas=3)
    p2.decide(_sig(0), replicas=2, now=0.0)
    d = p2.decide(_sig(0), replicas=2, now=2.0)
    assert d.direction == "hold" and "min_replicas" in d.reason


def test_p99_alone_triggers_overload():
    p = _policy(p99_high_ms=100.0)
    p.decide(_sig(0, p99_ms=500.0), replicas=2, now=0.0)
    d = p.decide(_sig(0, p99_ms=500.0), replicas=2, now=1.5)
    assert d.direction == "grow"


def test_stale_telemetry_holds():
    """A controller that cannot currently SEE the fleet must refuse to
    scale it — and reset its sustain timers (the window must be
    measured, not assumed)."""
    p = _policy(stale_signal_s=5.0)
    stale = _sig(50, scrape_ages={"w1": 30.0})
    d = p.decide(stale, replicas=2, now=0.0)
    assert d.direction == "hold" and "stale" in d.reason
    # fresh again: sustain restarts from scratch
    p.decide(_sig(50, scrape_ages={"w1": 0.1}), replicas=2, now=1.0)
    assert p.decide(_sig(50, scrape_ages={"w1": 0.1}),
                    replicas=2, now=2.5).direction == "grow"


# ------------------------------------------------------------------- lints


def _ps_strategy(*hosts):
    nodes = [types.SimpleNamespace(
        var_name="v%d" % i, part_configs=None,
        synchronizer=types.SimpleNamespace(reduction_destination=h))
        for i, h in enumerate(hosts)]
    return types.SimpleNamespace(
        graph_config=types.SimpleNamespace(mesh_shape={"data": 2}),
        node_config=nodes)


def _model_parallel_strategy():
    return types.SimpleNamespace(
        graph_config=types.SimpleNamespace(
            mesh_shape={"data": 2, "model": 2}),
        node_config=[])


def test_adt440_min_below_ps_owner_floor():
    diags = rules.verify_autoscale(
        _policy(min_replicas=1),
        strategy=_ps_strategy("10.0.0.1:7070", "10.0.0.2:7070"))
    assert [d.code for d in diags] == ["ADT440"]
    assert diags[0].severity.name == "ERROR"
    with pytest.raises(DiagnosticError, match="ADT440"):
        lint_policy(_policy(min_replicas=1),
                    strategy=_ps_strategy("10.0.0.1:7070",
                                          "10.0.0.2:7070"))
    # at the floor: sound
    assert lint_policy(_policy(min_replicas=2),
                       strategy=_ps_strategy("10.0.0.1:7070",
                                             "10.0.0.2:7070")) == []


def test_adt440_fail_fast_family_cannot_scale():
    diags = rules.verify_autoscale(_policy(min_replicas=1,
                                           max_replicas=4),
                                   strategy=_model_parallel_strategy())
    assert "ADT440" in [d.code for d in diags]
    # pinned bounds: no replica-count change armed, no error
    assert rules.verify_autoscale(
        _policy(min_replicas=2, max_replicas=2),
        strategy=_model_parallel_strategy()) == []


def test_adt441_threshold_warnings():
    diags = rules.verify_autoscale(_policy(queue_high=100.0,
                                           queue_low=2.0),
                                   max_queue=64)
    assert [d.code for d in diags] == ["ADT441"]
    assert diags[0].severity.name == "WARNING"
    # warnings do not raise at construction
    lint_policy(_policy(queue_high=100.0, queue_low=2.0), max_queue=64)
    diags = rules.verify_autoscale(
        _policy(sustain_s=0.0, grow_cooldown_s=0.0, shrink_cooldown_s=0.0))
    assert [d.code for d in diags] == ["ADT441"]


# ----------------------------------------------------- actuation (real wire)


@pytest.fixture()
def server():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = CoordinationServer(port=port)
    srv.start()
    yield port
    srv.stop()


@pytest.fixture(autouse=True)
def _clean_plane():
    yield
    elastic.clear()
    preemption.reset()


CHIEF = "10.0.0.1:9000"
W2 = "10.0.0.2:9000"
W3 = "10.0.0.3:9000"


def _scaler(client, signals, **kw):
    base = dict(min_replicas=1, max_replicas=4, queue_high=10.0,
                queue_low=2.0, sustain_s=0.0, grow_cooldown_s=60.0,
                shrink_cooldown_s=60.0)
    base.update(kw.pop("policy_kw", {}))
    return FleetAutoscaler(client, AutoscalePolicy(**base), CHIEF,
                           signals_fn=lambda: signals, **kw)


def test_grow_admits_pool_worker(server):
    client = CoordinationClient("127.0.0.1", server)
    elastic.publish_epoch(client, 1, [CHIEF])
    sc = _scaler(client, _sig(50), pool=[W2, W3])
    d = sc.step()
    assert d.direction == "grow"
    assert elastic.read_epoch(client) == (2, [CHIEF, W2])
    assert sc.stats()["grows"] == 1
    assert tel.counters()["autoscale.grows"] >= 1


def test_grow_prefers_announced_joiner(server):
    client = CoordinationClient("127.0.0.1", server)
    elastic.publish_epoch(client, 1, [CHIEF])
    elastic.announce_join(client, W3)
    sc = _scaler(client, _sig(50), pool=[W2, W3])
    sc.step()
    # W3 asked for admission, so it outranks the cold spare W2 — and
    # its join announcement is consumed by the admission
    assert elastic.read_epoch(client) == (2, [CHIEF, W3])
    assert not elastic.pending_join(client, W3)


def test_grow_refused_onto_pending_notice(server):
    """The platform is about to take W2 — growing onto it would be a
    scale event that immediately unwinds. Refused (counted), and the
    next admissible candidate is used instead."""
    client = CoordinationClient("127.0.0.1", server)
    elastic.publish_epoch(client, 1, [CHIEF])
    preemption.publish_notice(client, W2, deadline_s=60, reason="spot")
    sc = _scaler(client, _sig(50), pool=[W2, W3])
    d = sc.step()
    assert d.direction == "grow"
    assert elastic.read_epoch(client) == (2, [CHIEF, W3])
    assert sc.stats()["refusals"] == 1
    # every candidate under notice: the grow degrades to a hold
    preemption.publish_notice(client, W3, deadline_s=60, reason="spot")
    elastic.publish_epoch(client, 3, [CHIEF])
    sc2 = _scaler(client, _sig(50), pool=[W2, W3])
    d = sc2.step()
    assert d.direction == "hold" and "admissible" in d.reason
    assert elastic.read_epoch(client) == (3, [CHIEF])


def test_shrink_goes_through_planned_departure(server):
    client = CoordinationClient("127.0.0.1", server)
    elastic.publish_epoch(client, 1, [CHIEF, W2])
    before = tel.counters().get("preempt.notices", 0.0)
    sc = _scaler(client, _sig(0), notice_deadline_s=45.0)
    d = sc.step()
    assert d.direction == "shrink"
    # the leaver got an ADVANCE notice (arming its graceful-departure
    # path) before the survivor epoch was published
    notice = preemption.read_notice(client, W2)
    assert notice is not None and notice.reason == "autoscale-idle"
    assert elastic.read_epoch(client) == (2, [CHIEF])
    assert tel.counters()["preempt.notices"] == before + 1
    assert sc.stats()["shrinks"] == 1


def test_shrink_never_retires_the_controller(server):
    client = CoordinationClient("127.0.0.1", server)
    elastic.publish_epoch(client, 1, [CHIEF])
    sc = _scaler(client, _sig(0))
    d = sc.step()
    # min_replicas=1 and the only member is the controller: hold
    assert d.direction == "hold"
    assert elastic.read_epoch(client) == (1, [CHIEF])


def test_stale_epoch_decision_is_fenced_and_dropped(server):
    """The race the fence exists for: between this controller's epoch
    read and its actuation, ANOTHER controller moves the fleet. The
    stale decision must die as FencedOut — dropped, counted, and
    absolutely not applied on top (no double-scale)."""
    client = CoordinationClient("127.0.0.1", server)
    elastic.publish_epoch(client, 1, [CHIEF])

    def racing_signals():
        # runs after step() read epoch 1, before the actuation: a rival
        # controller admits W3 first
        if elastic.read_epoch(client)[0] == 1:
            elastic.publish_epoch(client, 2, [CHIEF, W3])
        return _sig(50)

    sc = FleetAutoscaler(
        client, AutoscalePolicy(min_replicas=1, max_replicas=4,
                                queue_high=10.0, queue_low=2.0,
                                sustain_s=0.0, grow_cooldown_s=60.0,
                                shrink_cooldown_s=60.0),
        CHIEF, pool=[W2], signals_fn=racing_signals)
    d = sc.step()
    assert d.direction == "hold" and "fenced" in d.reason
    assert sc.stats()["fenced"] == 1
    # the rival's epoch stands untouched — W2 was NOT admitted on top
    assert elastic.read_epoch(client) == (2, [CHIEF, W3])
    # the cooldown was not burned: the next (fresh-epoch) step may grow
    d = sc.step()
    assert d.direction == "grow"
    assert elastic.read_epoch(client) == (3, [CHIEF, W3, W2])


def test_step_without_published_epoch_raises(server):
    client = CoordinationClient("127.0.0.1", server)
    sc = _scaler(client, _sig(50))
    with pytest.raises(RuntimeError, match="no membership epoch"):
        sc.step()


def test_construction_lints_against_strategy(server):
    client = CoordinationClient("127.0.0.1", server)
    with pytest.raises(DiagnosticError, match="ADT440"):
        FleetAutoscaler(client, _policy(min_replicas=1), CHIEF,
                        strategy=_ps_strategy("10.0.0.1:7070",
                                              "10.0.0.2:7070"))


def test_retire_worker_validates_membership(server):
    client = CoordinationClient("127.0.0.1", server)
    with pytest.raises(RuntimeError, match="no membership epoch"):
        preemption.retire_worker(client, W2)
    elastic.publish_epoch(client, 1, [CHIEF])
    with pytest.raises(RuntimeError, match="not in the current roster"):
        preemption.retire_worker(client, W2)


def test_admit_worker_is_idempotent(server):
    client = CoordinationClient("127.0.0.1", server)
    elastic.publish_epoch(client, 1, [CHIEF])
    assert elastic.admit_worker(client, W2) == 2
    assert elastic.admit_worker(client, W2) == 2  # already a member
    assert elastic.read_epoch(client) == (2, [CHIEF, W2])


# ------------------------- the closed loop under live traffic (real stack)


class _LoadedFleet:
    """The real serving stack (engine + micro-batcher over a tiny MLP's
    runner) under an in-run membership ``[me, replica-b]`` with a pool
    ``[replica-c, replica-d]`` of phantom peers, so a ramp exercises the
    real admission / retirement wire in one process. Each batch takes
    15 ms more than the MLP needs, so a burst SUSTAINS a backlog on a
    CPU."""

    ME = "127.0.0.1"

    def __init__(self, port, monkeypatch, policy):
        for k, v in {"ADT_COORDSVC_PORT": str(port), "ADT_ELASTIC": "1",
                     "ADT_ELASTIC_SYNC": "1", "ADT_ELASTIC_INRUN": "1",
                     "ADT_ELASTIC_POLL_S": "0.01",
                     "ADT_PREEMPT_POLL_S": "0.01"}.items():
            monkeypatch.setenv(k, v)
        autodist_tpu.reset()
        rng = np.random.RandomState(0)
        params = {"w1": rng.randn(16, 32).astype(np.float32) * 0.1,
                  "b1": np.zeros((32,), np.float32),
                  "w2": rng.randn(32, 4).astype(np.float32) * 0.1}
        batch = {"x": rng.randn(32, 16).astype(np.float32),
                 "y": rng.randn(32, 4).astype(np.float32)}
        self.rows = batch["x"]

        def hidden(p, b):
            import jax.numpy as jnp
            return jnp.tanh(b["x"] @ p["w1"] + p["b1"]) @ p["w2"]

        def loss_fn(p, b):
            return ((hidden(p, b) - b["y"]) ** 2).mean()

        self.client = CoordinationClient("127.0.0.1", port)
        elastic.publish_epoch(self.client, 1, [self.ME, "replica-b"])
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
        runner = ad.build(loss_fn, optax.adam(1e-2), params, batch)
        runner.init(params)
        replicas = runner.remapper.num_replicas
        engine = InferenceEngine(
            runner, lambda p, b: {"y": hidden(p, b)}, {"x": self.rows[0]},
            ServingConfig(buckets=(replicas, 8 * replicas), max_delay_ms=2.0,
                          max_queue=64, brownout_queue_frac=0.5,
                          brownout_sustain_s=0.02,
                          brownout_delay_factor=4.0)).warmup()
        real_run = engine.run_batch

        def slow_run(reqs):
            time.sleep(0.015)
            return real_run(reqs)

        engine.run_batch = slow_run
        self.mb = MicroBatcher(engine)
        self.scaler = FleetAutoscaler(
            self.client, policy, self.ME, pool=["replica-c", "replica-d"],
            notice_deadline_s=60.0)
        self.futures, self.hints = [], []

    def burst(self, n, deadline_every=0):
        for i in range(n):
            expired = deadline_every and i % deadline_every == 0
            try:
                self.futures.append(self.mb.submit(
                    {"x": self.rows[i % len(self.rows)]},
                    deadline_s=0.001 if expired else None))
            except ServingUnavailable as e:
                self.hints.append(e.retry_after_s)

    def settle(self):
        for f in self.futures:
            try:
                f.result(timeout=30)
            except ServingUnavailable as e:
                self.hints.append(e.retry_after_s)
        self.futures.clear()

    def close(self):
        self.mb.close()
        self.client.close()


def _ramp_policy(**kw):
    return AutoscalePolicy(min_replicas=2, max_replicas=4, queue_high=8,
                           queue_low=2, **kw)


def test_a_load_ramp_grows_the_fleet_and_idles_it_back_by_planned_departures(
        server, monkeypatch):
    """2 → 4 → 2 under live traffic: sustained queue depth grows the
    fleet, idleness shrinks it back through the planned-departure path
    (a notice and a survivor epoch, never the checkpoint fallback),
    nothing is shed outside the overload window, every shed carries a
    ``Retry-After``, and the overload window saw both degradation paths:
    a brownout entry and an expired-deadline shed."""
    fleet = _LoadedFleet(server, monkeypatch, _ramp_policy(
        sustain_s=0.05, grow_cooldown_s=0.02, shrink_cooldown_s=0.02))
    try:
        give_up = time.perf_counter() + 30.0
        while ((fleet.scaler.stats()["grows"] < 2
                or fleet.mb.stats()["brownout"]["entries"] < 1)
               and time.perf_counter() < give_up):
            fleet.burst(24, deadline_every=8)
            fleet.scaler.step()
            time.sleep(0.01)
        fleet.settle()
        shed_in_overload = tel.counters().get("serve.shed", 0.0)
        give_up = time.perf_counter() + 30.0
        while (fleet.scaler.stats()["shrinks"] < 2
               and time.perf_counter() < give_up):
            fleet.scaler.step()
            time.sleep(0.02)
        scaled = fleet.scaler.stats()
        served = fleet.mb.stats()
        counters = tel.counters()
        epoch = elastic.read_epoch(fleet.client)
    finally:
        fleet.close()
    assert scaled["grows"] >= 1 and scaled["shrinks"] >= 1, scaled
    assert epoch is not None and len(epoch[1]) == 2, epoch
    assert counters.get("preempt.notices", 0.0) >= 1
    assert counters.get("ckpt.fallback", 0.0) == 0
    assert counters.get("serve.shed", 0.0) == shed_in_overload
    assert fleet.hints and all(h is not None for h in fleet.hints)
    assert served["brownout"]["entries"] >= 1, served["brownout"]
    assert served["deadline_shed"] >= 1, served


@pytest.mark.slow
@pytest.mark.chaos
def test_load_that_oscillates_inside_the_sustain_window_does_not_flap_the_fleet(
        server, monkeypatch):
    """Bursts shorter than the policy's sustain window, drained between
    spikes: the hysteresis band and the window hold the fleet still (at
    most two scale events over forty decisions)."""
    fleet = _LoadedFleet(server, monkeypatch, _ramp_policy(
        sustain_s=0.5, grow_cooldown_s=30.0, shrink_cooldown_s=30.0))
    try:
        for _ in range(40):
            fleet.burst(12)
            fleet.scaler.step()
            time.sleep(0.05)
        fleet.settle()
        scaled = fleet.scaler.stats()
    finally:
        fleet.close()
    assert scaled["grows"] + scaled["shrinks"] <= 2, scaled
    assert scaled["holds"] >= 10, scaled
