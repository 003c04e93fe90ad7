"""Cluster observability plane (ISSUE 11): clock-offset handshake
(skew/jitter tolerance, min-RTT filtering), goodput attribution (buckets
sum to wall time), straggler flagging, flight-recorder dumps on injected
``TrainingDiverged`` / breaker-open, fleet-profiling windows, and the
scrape-age / workers-missing satellites."""
import json
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.telemetry import blackbox, cluster, export, goodput
from autodist_tpu.telemetry import spans as tel


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    yield
    tel.configure(None)
    tel.reset()
    blackbox.reset()


class FakeCoordClient:
    """In-proc stand-in for the coordination client's KV/queue/blob API
    — the cluster-plane plumbing without a socket. ``delay_s`` simulates
    wire latency on every call (the jitter knob the clock tests turn)."""

    def __init__(self, delay_s=0.0):
        self.kv = {}
        self.queues = {}
        self.blobs = {}
        self.counters = {}
        self.delay_s = delay_s
        self._lock = threading.Lock()

    def _wire(self):
        if self.delay_s:
            time.sleep(self.delay_s)

    def put(self, key, value):
        self._wire()
        with self._lock:
            self.kv[key] = value

    def get(self, key):
        self._wire()
        with self._lock:
            return self.kv.get(key)

    def incr(self, name):
        self._wire()
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + 1
            return self.counters[name]

    def qpush(self, queue, payload, token=None):
        self._wire()
        with self._lock:
            self.queues.setdefault(queue, []).append(payload)

    def qpop(self, queue):
        self._wire()
        with self._lock:
            q = self.queues.get(queue)
            return q.pop(0) if q else None

    def bput(self, key, version, payload, token=None):
        self._wire()
        with self._lock:
            self.blobs[key] = (version, payload)

    def bget(self, key):
        self._wire()
        with self._lock:
            return self.blobs.get(key)


# ------------------------------------------------------------- clock sync


def test_clock_offset_recovers_injected_skew():
    """A worker whose wall clock runs 3s ahead must estimate an offset
    that cancels the skew, within the estimator's own reported error."""
    client = FakeCoordClient()
    skew_ns = 3_000_000_000
    with cluster.ClockSyncResponder(client, poll_s=0.001):
        est = cluster.estimate_clock_offset(
            client, "w0", rounds=4,
            clock=lambda: time.time_ns() + skew_ns)
    assert est.rounds == 4
    assert abs(est.offset_ns + skew_ns) <= max(est.error_ns, 50_000_000)
    assert est.error_ns == est.rtt_ns // 2 + 1
    d = cluster.ClockOffset.from_dict(est.to_dict())
    assert d.offset_ns == est.offset_ns


def test_clock_offset_min_rtt_filters_jitter():
    """Per-round wire jitter inflates RTT symmetrically; the min-RTT
    round wins, so the estimate stays tight even when most rounds are
    slow. The responder answers instantly (its own clock is the
    reference) while the REQUEST path jitters."""
    client = FakeCoordClient()
    # jitter: every call sleeps a random-ish amount, varying per call
    delays = iter([0.05, 0.0, 0.05, 0.0, 0.002, 0.0, 0.03, 0.0] * 8)

    orig_qpush = client.qpush

    def jittered_qpush(queue, payload, token=None):
        time.sleep(next(delays, 0.0))
        orig_qpush(queue, payload, token=token)

    client.qpush = jittered_qpush
    with cluster.ClockSyncResponder(client, poll_s=0.001):
        est = cluster.estimate_clock_offset(client, "w0", rounds=4)
    # no injected skew: the estimate must be ~zero despite 50ms jitter
    # rounds — bounded by the WINNING round's error, not the worst's
    assert abs(est.offset_ns) <= max(est.error_ns, 20_000_000)
    assert est.error_ns < 25_000_000  # the 2ms-ish round won, not 50ms


def test_clock_offset_times_out_without_responder():
    client = FakeCoordClient()
    with pytest.raises(TimeoutError, match="ClockSyncResponder"):
        cluster.estimate_clock_offset(client, "w0", rounds=2,
                                      round_timeout_s=0.05)


@pytest.mark.slow
def test_clock_offset_over_real_service_with_fault_proxy(monkeypatch):
    """The satellite acceptance: injected skew + fault-proxy DELAY
    jitter on the real coordination-service wire; the min-RTT filter
    still aligns within tolerance."""
    from autodist_tpu.runtime.coordination import (CoordinationClient,
                                                   CoordinationServer)
    from autodist_tpu.runtime.faultinject import FaultPlan, FaultyProxy
    port = 15913
    srv = CoordinationServer(port=port)
    srv.start()
    proxy = FaultyProxy("127.0.0.1", port, plan=FaultPlan({
        # delay every 3rd QPUSH by 80ms: two rounds pay the jitter, the
        # clean rounds win the min-RTT race
        "faults": [{"op": "delay", "match": "QPUSHB", "nth": 3,
                    "repeat": True, "delay_s": 0.08}]}))
    proxy.start()
    responder_client = CoordinationClient("127.0.0.1", port)
    worker_client = CoordinationClient("127.0.0.1", proxy.port)
    skew_ns = 2_500_000_000
    try:
        with cluster.ClockSyncResponder(responder_client, poll_s=0.001):
            est = cluster.estimate_clock_offset(
                client=worker_client, worker="w0", rounds=6,
                clock=lambda: time.time_ns() + skew_ns)
        assert abs(est.offset_ns + skew_ns) <= max(est.error_ns,
                                                   50_000_000)
        assert est.rtt_ns < 80_000_000  # a non-delayed round won
    finally:
        worker_client.close()
        responder_client.close()
        proxy.stop()
        srv.stop()


def test_chrome_trace_applies_clock_offset():
    """The exported timeline is reference-clock corrected: two recorders
    with a simulated 2s wall-clock disagreement (one corrected by the
    handshake offset) land their simultaneous spans together."""
    r_ref = tel.TraceRecorder(capacity=8, sample=1, pid=1, host="ref")
    r_skew = tel.TraceRecorder(capacity=8, sample=1, pid=2, host="skew")
    skew_ns = 2_000_000_000
    r_skew.epoch_offset_ns += skew_ns      # this host's clock runs ahead
    r_skew.clock_offset_ns = -skew_ns      # ...and the handshake knows
    r_skew.clock_error_ns = 1_000_000
    with r_ref.span("s", "t"):
        pass
    with r_skew.span("s", "t"):
        pass
    t_ref = next(e["ts"] for e in export.chrome_trace(r_ref)["traceEvents"]
                 if e["ph"] == "X")
    skew_trace = export.chrome_trace(r_skew)
    t_skew = next(e["ts"] for e in skew_trace["traceEvents"]
                  if e["ph"] == "X")
    assert abs(t_ref - t_skew) < 1e6  # within 1s (was 2s apart)
    assert skew_trace["otherData"]["clock_offset_ns"] == -skew_ns
    assert skew_trace["otherData"]["clock_error_ns"] == 1_000_000


def test_step_alignment_reads_merged_step_args():
    r1 = tel.TraceRecorder(capacity=16, sample=1, pid=1, host="a")
    r2 = tel.TraceRecorder(capacity=16, sample=1, pid=2, host="b")
    for rec in (r1, r2):
        for step in range(3):
            with rec.span("runner.dispatch", "runner", step=step):
                pass
    merged = export.merge_traces([export.chrome_trace(r1),
                                  export.chrome_trace(r2)])
    align = cluster.step_alignment(merged)
    assert align["aligned_steps"] == 3
    assert set(align["steps"]) == {0, 1, 2}
    for row in align["steps"].values():
        assert len(row["starts_us"]) == 2
        assert row["spread_us"] >= 0.0


# ---------------------------------------------------------------- goodput


def test_goodput_buckets_sum_to_wall_time_synthetic():
    rec = tel.TraceRecorder(capacity=256, sample=1, pid=1, host="h")
    with rec.span("runner.fit", "runner"):
        for step in range(3):
            with rec.span("runner.dispatch", "runner", step=step):
                with rec.span("runner.feed", "runner"):
                    time.sleep(0.002)
                with rec.span("dstep.dispatch", "dstep"):
                    with rec.span("ps.pull", "ps"):
                        time.sleep(0.002)
                    time.sleep(0.004)
            with rec.span("runner.readback", "runner"):
                time.sleep(0.001)
        with rec.span("ckpt.write", "ckpt"):
            time.sleep(0.002)
    report = goodput.breakdown_from_events(
        goodput._normalize_recorder(rec))
    assert report.wall_s > 0
    assert abs(report.attributed_s - report.wall_s) < 0.02 * report.wall_s
    b = report.buckets
    assert b["ps_wire"] >= 3 * 0.002 * 0.9
    assert b["host_input"] >= 3 * 0.002 * 0.9
    assert b["readback"] >= 3 * 0.001 * 0.9
    assert b["checkpoint"] >= 0.002 * 0.9
    assert b["compute"] >= 3 * 0.004 * 0.9
    assert report.num_dispatches == 3
    assert report.first_dispatch_s is not None
    # serialization round trip + table
    back = goodput.GoodputReport.from_dict(report.to_dict())
    assert back.buckets == {k: round(v, 6) for k, v in b.items()}
    assert "compute" in report.format_table()


def test_goodput_ignores_background_threads():
    """Async writer-thread time overlaps the wall; only the training
    thread's spans decompose it."""
    rec = tel.TraceRecorder(capacity=64, sample=1, pid=1, host="h")
    t0 = time.perf_counter()
    with rec.span("runner.dispatch", "runner", step=0):
        time.sleep(0.002)
    dispatch_s = time.perf_counter() - t0

    def background():
        with rec.span("ckpt.write", "ckpt"):
            time.sleep(0.01)
    t = threading.Thread(target=background, name="adt-ckpt-writer")
    t.start()
    t.join()
    report = goodput.breakdown_from_events(
        goodput._normalize_recorder(rec))
    assert report.buckets["checkpoint"] == 0.0
    # the 10ms background write is excluded: the wall is the training
    # thread's span alone (held against its own bracket, not a constant:
    # on a loaded host a 2 ms sleep has taken 11)
    assert report.wall_s <= dispatch_s + 1e-4


def test_goodput_real_fit_coverage_within_two_percent(tmp_path):
    """The acceptance bound on a real traced fit: attributed buckets sum
    to the recorded wall time within 2%, and the same decomposition is
    reachable from the exported trace file (the CLI path)."""
    from autodist_tpu.telemetry import cli
    tel.configure("1")
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(4, 2).astype(np.float32)),
              "b": jnp.zeros((2,), jnp.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)

    batches = [{"x": rng.randn(16, 4).astype(np.float32),
                "y": rng.randn(16, 2).astype(np.float32)}
               for _ in range(8)]
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.PS())
    runner = ad.build(loss_fn, optax.adam(0.1), params, batches[0])
    runner.init(params)
    runner.fit(list(batches), fuse_steps=4, metrics_every=2)
    report = runner.goodput_report()
    assert report is not None
    assert abs(report.coverage - 1.0) < 0.02
    assert report.buckets["ps_wire"] > 0       # host-PS strategy
    assert report.buckets["compute"] > 0
    stats = runner.step_stats()
    assert stats["goodput_breakdown"] == {
        k: round(v, 6) for k, v in report.buckets.items()}
    assert stats["straggler"]["flags"] == 0
    # drift joins the attributed buckets
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.telemetry import drift
    spec = ResourceSpec.from_dict({
        "nodes": [{"address": "127.0.0.1", "cpus": 8, "chief": True,
                   "network_bandwidth": 25}],
        "slice": {"ici_bandwidth": 100}})
    dr = drift.report_for_runner(runner, resource_spec=spec)
    assert dr.goodput is not None
    terms = {t.term: t for t in dr.terms}
    assert terms["compute"].measured_s is not None
    # CLI: per-process goodput table from the exported trace
    path = str(tmp_path / "trace.json")
    export.write_trace(path)
    assert cli.main(["goodput", path]) == 0
    # and from a saved report
    rpath = report.save(str(tmp_path / "goodput.json"))
    assert cli.main(["goodput", rpath]) == 0
    autodist_tpu.reset()


def test_goodput_report_none_when_tracing_off():
    tel.configure("0")
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(4, 2).astype(np.float32)),
              "b": jnp.zeros((2,), jnp.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)

    batch = {"x": np.zeros((8, 4), np.float32),
             "y": np.zeros((8, 2), np.float32)}
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(0.1), params, batch)
    runner.init(params)
    runner.run(batch)
    assert runner.goodput_report() is None
    assert runner.step_stats()["goodput_breakdown"] is None
    autodist_tpu.reset()


def test_cluster_goodput_flags_stragglers():
    """A merged trace whose second worker's dispatches run 3x slower
    must show the skew ratio and flag the straggler pid."""
    recs = []
    for pid, base in ((1, 0.001), (2, 0.003)):
        rec = tel.TraceRecorder(capacity=64, sample=1, pid=pid,
                                host="n%d" % pid)
        for step in range(4):
            with rec.span("runner.dispatch", "runner", step=step):
                time.sleep(base)
        recs.append(rec)
    merged = export.merge_traces([export.chrome_trace(r) for r in recs])
    out = goodput.cluster_goodput(merged, flag_ratio=1.5)
    assert out["skew_ratio"] > 1.5
    assert [s["pid"] for s in out["stragglers"]] == [2]
    assert set(out["workers"]) == {1, 2}


def test_straggler_ewma_flags_and_clears():
    det = goodput.StragglerEwma(alpha=0.2, zscore=4.0, patience=2,
                                warmup=4)
    for _ in range(10):
        assert det.observe(0.010 + np.random.RandomState(0).rand() * 1e-4) \
            is None
    assert det.observe(0.100) is None       # patience 1/2
    assert det.observe(0.100) == "flag"     # sustained → flag
    assert det.flagged and det.flags == 1
    assert det.observe(0.100) is None       # still flagged, no re-fire
    assert det.observe(0.010) == "clear"    # recovery
    assert not det.flagged
    stats = det.stats()
    assert stats["flags"] == 1 and stats["ewma_s"] is not None


# --------------------------------------------------------------- blackbox


def _problem(seed=0):
    rng = np.random.RandomState(seed)
    params = {"w": jnp.asarray(rng.randn(4, 2).astype(np.float32)),
              "b": jnp.zeros((2,), jnp.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)

    batch = {"x": rng.randn(16, 4).astype(np.float32),
             "y": rng.randn(16, 2).astype(np.float32)}
    return params, loss_fn, batch


def test_blackbox_dump_on_injected_training_diverged(monkeypatch,
                                                     tmp_path, capsys):
    """The acceptance artifact: an injected unbounded grad fault drives
    rollback → ladder exhaustion → ``TrainingDiverged``, and the run
    leaves a parseable blackbox dump containing the fatal verdict AND
    the last rollback event/span."""
    from autodist_tpu.checkpoint.saver import Saver
    from autodist_tpu.runtime.sentinel import SentinelPolicy, TrainingDiverged
    from autodist_tpu.telemetry import cli
    bb_dir = str(tmp_path / "blackbox")
    monkeypatch.setenv("ADT_BLACKBOX_DIR", bb_dir)
    monkeypatch.setenv("ADT_GRAD_FAULT_PLAN", json.dumps(
        {"faults": [{"var": "w", "mode": "nan", "step": 4,
                     "until": 100000}]}))
    tel.configure("1")  # the span tail must carry sentinel.rollback
    params, loss_fn, batch = _problem()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(0.1), params, batch,
                      sentinel=SentinelPolicy(max_skips_per_window=1,
                                              window_steps=50,
                                              max_rollbacks_per_step=2))
    runner.init(params)
    saver = Saver(directory=str(tmp_path / "ckpt"), max_to_keep=10)
    import itertools
    with pytest.raises(TrainingDiverged):
        runner.fit(itertools.repeat(batch), steps=64, save_every=2,
                   saver=saver)
    dumps = sorted(os.listdir(bb_dir))
    assert dumps, "no blackbox dump written"
    latest = os.path.join(bb_dir, dumps[-1])
    d = blackbox.load_dump(latest)
    assert d["trigger"] == "training_diverged"
    kinds = [e["kind"] for e in d["events"]]
    assert "sentinel.diverged" in kinds          # the fatal verdict
    assert "sentinel.rollback" in kinds          # the rollback trail
    assert "sentinel.verdict" in kinds           # bad verdicts leading in
    assert any(s["name"] == "sentinel.rollback"  # the last rollback SPAN
               for s in d["spans"])
    assert d["counters"]["sentinel.rollbacks"] >= 1
    # rollbacks dumped their own black boxes along the way
    triggers = {blackbox.load_dump(os.path.join(bb_dir, f))["trigger"]
                for f in dumps}
    assert any(t.startswith("sentinel rollback") for t in triggers)
    # the CLI renders it
    assert cli.main(["blackbox", latest]) == 0
    out = capsys.readouterr().out
    assert "training_diverged" in out and "sentinel.rollback" in out
    autodist_tpu.reset()


def test_blackbox_dump_on_breaker_open(monkeypatch, tmp_path):
    """Breaker-open against an unreachable service dumps the box with
    the breaker event and the retry trail."""
    from autodist_tpu.runtime.resilience import (CoordinationUnavailable,
                                                 ResilientCoordinationClient)
    bb_dir = str(tmp_path / "bb")
    monkeypatch.setenv("ADT_BLACKBOX_DIR", bb_dir)
    client = ResilientCoordinationClient(
        "127.0.0.1", 1, rpc_timeout=0.2, max_retries=2,
        backoff_base_s=0.001, backoff_max_s=0.002,
        breaker_failures=2, breaker_cooldown_s=0.2,
        connect_timeout=0.1, seed=0)
    with pytest.raises(CoordinationUnavailable):
        client.ping()
    dumps = [f for f in os.listdir(bb_dir) if f.endswith(".json")]
    assert dumps
    d = blackbox.load_dump(os.path.join(bb_dir, sorted(dumps)[-1]))
    assert d["trigger"] == "breaker_open"
    assert any(e["kind"] == "coord.breaker_open" for e in d["events"])
    assert d["counters"]["coord.breaker_opens"] >= 1


def test_blackbox_bounded_retention_and_log_tail(monkeypatch, tmp_path):
    monkeypatch.setenv("ADT_BLACKBOX_KEEP", "2")
    from autodist_tpu.utils import logging as adt_logging
    fr = blackbox.get_flight_recorder()
    fr.clear()
    adt_logging.warning("blackbox tail marker %d", 42)
    for i in range(4):
        fr.record("test.event", i=i)
        fr.dump("retention-test", directory=str(tmp_path))
    kept = [f for f in os.listdir(str(tmp_path)) if f.endswith(".json")]
    assert len(kept) == 2  # pruned to ADT_BLACKBOX_KEEP
    d = blackbox.load_dump(os.path.join(str(tmp_path), sorted(kept)[-1]))
    assert any("blackbox tail marker 42" in rec["msg"]
               for rec in d["logs"])
    assert [e["data"]["i"] for e in d["events"]] == [0, 1, 2, 3]


def test_blackbox_retention_keeps_the_newest_across_the_tenth_dump(
        monkeypatch, tmp_path):
    """A process's ninth, tenth, eleventh and twelfth dumps within one
    second: the names sort in the order they were written, so the two kept
    are the last two."""
    monkeypatch.setenv("ADT_BLACKBOX_KEEP", "2")
    monkeypatch.setattr(blackbox.time, "strftime",
                        lambda fmt: "20260101-000000")
    fr = blackbox.get_flight_recorder()
    fr.clear()
    monkeypatch.setattr(fr, "dumps", 8)
    paths = []
    for i in range(4):
        fr.record("test.event", i=i)
        paths.append(fr.dump("retention-test", directory=str(tmp_path)))
    kept = sorted(os.path.join(str(tmp_path), f)
                  for f in os.listdir(str(tmp_path)))
    assert kept == paths[-2:]
    assert [e["data"]["i"] for e in blackbox.load_dump(kept[-1])["events"]] \
        == [0, 1, 2, 3]


def test_blackbox_disabled_writes_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("ADT_BLACKBOX", "0")
    blackbox.record("test.event")
    assert blackbox.dump("disabled-test", directory=str(tmp_path)) is None
    assert not os.listdir(str(tmp_path))


# -------------------------------------------------------- fleet profiling


def test_profile_flag_round_trip_and_clear():
    client = FakeCoordClient()
    assert cluster.read_profile_window(client) is None
    seq = cluster.request_profile(client, 3, 5)
    assert cluster.read_profile_window(client) == (seq, 3, 5)
    seq2 = cluster.request_profile(client, 10, 12)
    assert seq2 > seq
    assert cluster.read_profile_window(client) == (seq2, 10, 12)
    cluster.clear_profile(client)
    assert cluster.read_profile_window(client) is None
    with pytest.raises(ValueError):
        cluster.request_profile(client, 5, 3)


def test_parse_profile_env():
    assert cluster.parse_profile_env("") is None
    assert cluster.parse_profile_env("3:5") == (3, 5)
    assert cluster.parse_profile_env("4") == (4, 4)
    assert cluster.parse_profile_env("5:3") is None
    assert cluster.parse_profile_env("nope") is None


def test_runner_env_window_captures_jax_profile(monkeypatch, tmp_path):
    """ADT_PROFILE_STEPS=N:M arms the fleet-window machinery locally:
    the runner captures a jax.profiler trace for exactly that step
    window."""
    monkeypatch.setenv("ADT_WORKING_DIR", str(tmp_path))
    monkeypatch.setenv("ADT_PROFILE_STEPS", "2:3")
    # DEFAULT_TRACE_DIR is computed at const import; patch it directly
    from autodist_tpu import const as const_mod
    monkeypatch.setattr(const_mod, "DEFAULT_TRACE_DIR",
                        str(tmp_path / "traces"))
    params, loss_fn, batch = _problem()
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(0.1), params, batch)
    runner.init(params)
    for _ in range(5):
        runner.run(batch)
    assert not runner._profile_active
    out = str(tmp_path / "traces" / "fleet-0-chief")
    assert os.path.isdir(out)
    captured = [f for _, _, files in os.walk(out) for f in files]
    assert captured, "jax.profiler wrote nothing into the fleet window"
    assert tel.counters()["profiler.windows"] == 1
    autodist_tpu.reset()


# ------------------------------------------------- scrape-age satellites


def test_scrape_cluster_reports_ages_and_missing_gauge():
    client = FakeCoordClient()
    rec = tel.TraceRecorder(capacity=16, sample=1, pid=5, host="n0")
    with rec.span("s", "t"):
        pass
    export.publish_telemetry(client, "w0", rec)
    time.sleep(0.02)
    scraped = export.scrape_cluster(client, ["w0", "ghost1", "ghost2"])
    assert scraped["missing"] == ["ghost1", "ghost2"]
    assert scraped["scrape_age_s"]["w0"] >= 0.02
    assert tel.get_recorder().gauges()["cluster.workers_missing"] == 2.0
    text = scraped["metrics_text"]
    assert "adt_cluster_workers_missing 2" in text
    assert 'adt_cluster_scrape_age_seconds{worker="w0"}' in text
    assert "# HELP adt_cluster_workers_missing" in text
    # per-worker clock metadata rides the scrape
    assert scraped["clocks"]["w0"]["offset_ns"] == 0


def test_scrape_age_is_reference_clock_corrected():
    """A worker whose clock runs ahead publishes a corrected stamp: its
    age must read ~0, not negative/clamped garbage."""
    client = FakeCoordClient()
    rec = tel.TraceRecorder(capacity=4, sample=1, pid=5, host="n0")
    rec.clock_offset_ns = -3_000_000_000  # clock 3s ahead of reference
    rec.counter_add("runner.steps", 1)
    # publish stamps time.time() + offset -> ~3s in the "past" locally,
    # but correct on the reference timeline... the age is computed by a
    # coordinator whose clock IS the reference here, so simulate that by
    # checking the published stamp directly
    export.publish_telemetry(client, "w0", rec)
    payload = json.loads(client.blobs["telemetry/w0"][1].decode())
    assert payload["published_at"] == pytest.approx(time.time() - 3.0,
                                                    abs=0.5)
    assert payload["clock"]["offset_ns"] == -3_000_000_000
