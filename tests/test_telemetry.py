"""Runtime telemetry: recorder semantics, overhead guard, Perfetto
export schema, cross-process merge/scrape, drift reports, and the
instrumented-path acceptance (a fused fit traces >= 2 subsystems and the
registry exposes >= 10 counters)."""
import json
import statistics
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.telemetry import drift, export
from autodist_tpu.telemetry import spans as tel


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """configure() overrides are sticky by design — drop them after each
    test so the rest of the suite stays env-driven (off)."""
    yield
    tel.configure(None)
    tel.reset()


# ---------------------------------------------------------------- recorder


def test_disabled_mode_overhead_guard():
    """ADT_TRACE=0 span enter/exit must stay near-free (< 1µs median is
    the design target; asserted loosely for shared CI hosts)."""
    tel.configure("0")
    assert not tel.tracing_enabled()
    reps, batch = 50, 400
    per_op = []
    for _ in range(reps):
        t0 = time.perf_counter_ns()
        for _ in range(batch):
            with tel.span("hot.noop", "test"):
                pass
        per_op.append((time.perf_counter_ns() - t0) / batch)
    median_ns = statistics.median(per_op)
    assert median_ns < 5000, "disabled span overhead %dns/op" % median_ns
    # and nothing was recorded
    assert tel.get_recorder().events() == []


def test_nested_spans_record_parent_ids_and_durations():
    tel.configure("1")
    rec = tel.get_recorder()
    with tel.span("outer", "test", k=2) as outer:
        assert tel.current_span_id() == outer.id
        with tel.span("inner", "test"):
            time.sleep(0.001)
    assert tel.current_span_id() == 0
    events = {e.name: e for e in rec.events()}
    assert set(events) == {"outer", "inner"}
    assert events["inner"].parent_id == events["outer"].span_id
    assert events["outer"].parent_id == 0
    # inner completed first but nests inside outer's interval
    assert events["outer"].dur_ns >= events["inner"].dur_ns > 0
    assert events["outer"].args == {"k": 2}


def test_a_phase_of_set_up_keeps_gauges_counters_and_memory():
    """The recorder's half of the set-up account, no build: a ``setup``
    span is an ordinary span that is also kept, with the device's memory
    as args, the gauges set while it was live and the counters as they
    stood when the outermost ended; ``clear()`` keeps all of it."""
    rec = tel.TraceRecorder(capacity=16)
    rec.gauge_set("before", 1)
    with rec.span("setup.build", tel.SETUP_CAT):
        rec.gauge_set("lean_head.chunks", 13)
        with rec.span("setup.capture", tel.SETUP_CAT, k=1):
            rec.counter_add("compile.traces", 2)
        rec.counter_add("compile.traces")   # after the inner phase ended
    rec.gauge_set("after", 1)
    rec.counter_add("compile.traces")       # after set-up: the window's
    rec.clear()
    acc = rec.setup_account()
    assert [p["name"] for p in acc["phases"]] == ["setup.capture",
                                                  "setup.build"]
    inner, outer = acc["phases"]
    assert inner["parent"] == outer["id"] and outer["parent"] == 0
    assert inner["args"] == {
        "k": 1, "hbm_in_use": 0, "hbm_peak": 0, "trace_lower_s": 0.0,
        "backend_compile_s": 0.0, "cache_load_s": 0.0, "programs": {}}
    assert acc["gauges"] == {"lean_head.chunks": 13.0}
    assert acc["counters"] == {"compile.traces": 3.0}
    assert rec.events() == [] and rec.gauges() == {}
    rec.clear_setup()
    assert rec.setup_account() == {"phases": [], "counters": {},
                                   "gauges": {}}


def test_jax_events_go_under_the_live_span_and_sum_into_the_phase():
    rec = tel.TraceRecorder(capacity=16)
    rec.jax_event("jax.trace", 0.001, "outside")   # no phase: ring only
    with rec.span("setup.build", tel.SETUP_CAT):
        rec.jax_event("jax.trace", 0.25, "loss_fn")
        with rec.span("setup.first_step", tel.SETUP_CAT):
            with rec.span("dstep.dispatch", "dstep") as live:
                rec.jax_event("jax.trace", 0.001, "inner")
                rec.jax_event("jax.backend_compile", 0.0005, "eager")
                rec.jax_event("jax.trace", 0.5, "outer")  # began before both
                rec.jax_event("jax.lower", 0.002, "jit(outer)")
                rec.jax_event("jax.cache_load", 0.125, "jit(outer)")
    first, build = rec.setup_account()["phases"]
    # a trace inside a trace is counted once; each phase holds what JAX
    # did beneath IT, not beneath a phase inside it
    assert first["args"]["trace_lower_s"] == pytest.approx(0.502)
    assert first["args"]["backend_compile_s"] == pytest.approx(0.0005)
    assert first["args"]["cache_load_s"] == 0.125
    assert first["args"]["programs"] == {"eager": 1, "jit(outer)": 1}
    assert build["args"]["trace_lower_s"] == 0.25
    assert build["args"]["programs"] == {}
    ring = [e for e in rec.events() if e.cat == tel.JAX_CAT]
    assert len(ring) == 7 and ring[0].parent_id == 0
    assert ring[0].args == {"fun_name": "outside"}
    assert ring[1].args == {"fun_name": "loss_fn", "phase": "setup.build"}
    assert all(e.parent_id == live.id
               and e.args["phase"] == "setup.first_step" for e in ring[2:])


@pytest.mark.parametrize("mode, kept", [("0", 0), ("1", 1), ("sampled", 1)])
def test_set_up_spans_follow_the_mode_and_are_never_sampled_out(mode, kept):
    tel.configure(mode, sample=1000)
    for _ in range(3):   # the stride would keep the first span only
        with tel.span("hot", "test"):
            pass
    with tel.span("setup.build", tel.SETUP_CAT):
        pass
    assert len(tel.setup_account()["phases"]) == kept
    assert [e.name for e in tel.get_recorder().events()
            if e.cat == tel.SETUP_CAT] == ["setup.build"] * kept


def test_counters_and_gauges_work_with_tracing_disabled():
    tel.configure("0")
    tel.counter_add("runner.steps", 3)
    tel.counter_add("custom.thing", 2.5)
    tel.gauge_set("prefetch.queue_depth", 4)
    c = tel.counters()
    assert c["runner.steps"] == 3.0
    assert c["custom.thing"] == 2.5
    assert tel.get_recorder().gauges()["prefetch.queue_depth"] == 4.0


def test_default_registry_exposes_at_least_ten_counters():
    tel.configure("0")
    text = export.metrics_text()
    counter_lines = [ln for ln in text.splitlines()
                     if ln.startswith("# TYPE") and ln.endswith("counter")]
    assert len(counter_lines) >= 10
    assert "adt_runner_steps_total" in text
    assert "adt_ps_bytes_pulled_total" in text


def test_sampled_mode_records_one_in_n():
    tel.configure("sampled", capacity=4096, sample=4)
    for _ in range(100):
        with tel.span("s", "test"):
            pass
    n = len(tel.get_recorder().events())
    assert n == 25, "sampled 1/4 of 100 spans -> 25, got %d" % n
    # instants are rare diagnostic markers: NEVER sampled out
    for _ in range(5):
        tel.instant("coord.breaker_open", "coord")
    instants = [e for e in tel.get_recorder().events()
                if e.name == "coord.breaker_open"]
    assert len(instants) == 5


def test_exported_timestamps_are_wall_clock_based():
    """perf_counter origins are arbitrary per process; exports re-base
    onto the wall clock so scraped traces from different hosts land on
    one comparable timeline."""
    rec = tel.TraceRecorder(capacity=8, sample=1, pid=1, host="h")
    with rec.span("s", "test"):
        pass
    trace = export.chrome_trace(rec)
    ts_us = next(e["ts"] for e in trace["traceEvents"] if e["ph"] == "X")
    assert abs(ts_us - time.time_ns() / 1e3) < 300e6  # within 5 minutes


def _count_spans(n=8):
    before = len(tel.get_recorder().events())
    for _ in range(n):
        with tel.span("s", "test"):
            pass
    return len(tel.get_recorder().events()) - before


def test_reset_resyncs_stride_and_mode_from_one_source(monkeypatch):
    """reset() re-derives BOTH the mode and the recorder's sampling
    stride from one source — a stale stride would silently drop spans
    while tracing_enabled() claims full-record mode."""
    tel.configure(None)  # env-driven
    monkeypatch.setenv("ADT_TRACE", "1")
    tel.reset()  # what autodist_tpu.reset() calls
    assert tel.tracing_enabled()
    assert _count_spans(8) == 8
    monkeypatch.setenv("ADT_TRACE", "sampled")
    monkeypatch.setenv("ADT_TRACE_SAMPLE", "4")
    tel.reset()
    assert _count_spans(8) == 2  # stride followed the mode


def test_configure_override_is_sticky_across_reset(monkeypatch):
    """An explicit configure() choice must survive autodist_tpu.reset()
    (run between every programmatic build) — without stickiness a traced
    session silently reverts to the env default and records nothing."""
    monkeypatch.delenv("ADT_TRACE", raising=False)
    tel.configure("1")
    tel.reset()
    assert tel.tracing_enabled()
    assert _count_spans(4) == 4
    tel.configure(None)  # back to env-driven: default off
    tel.reset()
    assert not tel.tracing_enabled()
    assert _count_spans(4) == 0


def test_ring_buffer_bounds_and_counts_drops():
    rec = tel.TraceRecorder(capacity=8, sample=1, pid=1, host="h")
    for i in range(20):
        with rec.span("s%d" % i, "test"):
            pass
    assert len(rec.events()) == 8
    assert rec.dropped_events == 12
    assert [e.name for e in rec.events()] == ["s%d" % i for i in range(12, 20)]


# ------------------------------------------------------------------ export


def _record_some(rec):
    with rec.span("a", "catA", n=1):
        with rec.span("b", "catB"):
            pass
    rec.counter_add("runner.steps", 2)
    rec.gauge_set("depth", 1)


def test_chrome_trace_schema_and_validation():
    rec = tel.TraceRecorder(capacity=64, sample=1, pid=101, host="hostx")
    _record_some(rec)
    trace = export.chrome_trace(rec)
    assert export.validate_chrome_trace(trace) == []
    json.dumps(trace)  # serializable end to end
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a", "b"}
    for e in xs:
        assert e["pid"] == 101
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert "span_id" in e["args"]
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "hostx:101" for e in meta)
    cs = {e["name"]: e["args"]["value"] for e in trace["traceEvents"]
          if e["ph"] == "C"}
    assert cs["runner.steps"] == 2.0 and cs["depth"] == 1.0


def test_validate_rejects_malformed_traces():
    assert export.validate_chrome_trace({}) == ["missing traceEvents list"]
    bad = {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 0,
                            "ts": "soon", "dur": 1.0}]}
    assert any("non-numeric ts" in e
               for e in export.validate_chrome_trace(bad))
    assert any("no span" in e
               for e in export.validate_chrome_trace(
                   {"traceEvents": [{"ph": "M", "name": "m", "pid": 1}]}))
    # counters-only exports (ADT_TRACE=0 registry mode) are VALID
    rec = tel.TraceRecorder(capacity=4, sample=1, pid=3, host="h")
    rec.counter_add("ps.pulls", 1)
    assert export.validate_chrome_trace(export.chrome_trace(rec)) == []
    # the error list truncates even when every event is malformed
    garbage = {"traceEvents": [{"bogus": i} for i in range(1000)]}
    errs = export.validate_chrome_trace(garbage)
    assert len(errs) < 30 and any(e.startswith("...") for e in errs)


def test_merge_keeps_processes_on_distinct_tracks():
    """Two in-proc recorders standing in for two worker processes: the
    merged timeline must keep one track per process, even on pid
    collision (two single-process hosts with the same OS pid)."""
    r1 = tel.TraceRecorder(capacity=64, sample=1, pid=500, host="host-a")
    r2 = tel.TraceRecorder(capacity=64, sample=1, pid=500, host="host-b")
    _record_some(r1)
    _record_some(r2)
    merged = export.merge_traces([export.chrome_trace(r1),
                                  export.chrome_trace(r2)])
    pids = {e["pid"] for e in merged["traceEvents"] if e["ph"] == "X"}
    assert len(pids) == 2, "pid collision collapsed the tracks"
    assert export.validate_chrome_trace(merged) == []
    assert set(merged["otherData"]["processes"]) == {"host-a:500",
                                                     "host-b:500"}


class _FakeCoordClient:
    """In-proc stand-in for CoordinationClient's blob API — the scrape
    plumbing without a socket."""

    def __init__(self):
        self.blobs = {}

    def bput(self, key, version, payload, token=None):
        self.blobs[key] = (version, payload)

    def bget(self, key):
        return self.blobs.get(key)


def test_publish_and_scrape_cluster_merges_workers():
    client = _FakeCoordClient()
    for worker, pid in (("w0", 700), ("w1", 701)):
        rec = tel.TraceRecorder(capacity=64, sample=1, pid=pid,
                                host="node-%s" % worker)
        _record_some(rec)
        rec.counter_add("ps.pulls", 1 if worker == "w0" else 7)
        export.publish_telemetry(client, worker, rec)
    scraped = export.scrape_cluster(client, ["w0", "w1", "w-dead"])
    assert scraped["workers"] == ["w0", "w1"]
    assert scraped["missing"] == ["w-dead"]
    assert export.validate_chrome_trace(scraped["trace"]) == []
    pids = {e["pid"] for e in scraped["trace"]["traceEvents"]
            if e["ph"] == "X"}
    assert pids == {700, 701}
    text = scraped["metrics_text"]
    assert 'adt_ps_pulls_total{worker="w0"} 1' in text
    assert 'adt_ps_pulls_total{worker="w1"} 7' in text


@pytest.mark.slow
def test_scrape_over_real_coordination_service():
    """End-to-end scrape over the REAL coordination-service wire: two
    'workers' (in-proc recorders, distinct process identities) publish
    versioned telemetry blobs, the coordinator scrapes and merges —
    the deployed-cluster path, minus the extra OS processes."""
    from autodist_tpu.runtime.coordination import (CoordinationClient,
                                                   CoordinationServer)
    port = 15917
    srv = CoordinationServer(port=port)
    srv.start()
    try:
        for worker, pid in (("w0", 910), ("w1", 911)):
            rec = tel.TraceRecorder(capacity=64, sample=1, pid=pid,
                                    host="node-%s" % worker)
            _record_some(rec)
            client = CoordinationClient("127.0.0.1", port)
            export.publish_telemetry(client, worker, rec)
            client.close()
        coord = CoordinationClient("127.0.0.1", port)
        scraped = export.scrape_cluster(coord, ["w0", "w1"])
        coord.close()
        assert scraped["workers"] == ["w0", "w1"]
        assert scraped["missing"] == []
        assert export.validate_chrome_trace(scraped["trace"]) == []
        assert {e["pid"] for e in scraped["trace"]["traceEvents"]
                if e["ph"] == "X"} == {910, 911}
        assert 'adt_runner_steps_total{worker="w0"} 2' \
            in scraped["metrics_text"]
    finally:
        srv.stop()


def test_metrics_text_prometheus_shape():
    rec = tel.TraceRecorder(capacity=4, sample=1, pid=1, host="h")
    rec.counter_add("a.b-c", 2)
    rec.gauge_set("g", 1.5)
    text = export.metrics_text(rec, labels={"worker": "w9"})
    assert '# TYPE adt_a_b_c_total counter' in text
    assert 'adt_a_b_c_total{worker="w9"} 2' in text
    assert 'adt_g{worker="w9"} 1.5' in text


def test_metrics_text_emits_help_lines():
    """Strict scrapers want # HELP before # TYPE for every metric —
    counters, gauges AND histograms."""
    rec = tel.TraceRecorder(capacity=4, sample=1, pid=1, host="h")
    rec.gauge_set("prefetch.queue_depth", 2)
    rec.hist_observe("serve.latency_ms", 1.0)
    lines = export.metrics_text(rec).splitlines()
    assert "# HELP adt_runner_steps_total" \
        in {ln.rsplit(" autodist_tpu", 1)[0] for ln in lines
            if ln.startswith("# HELP")}
    # every TYPE line is immediately preceded by its HELP line
    for i, ln in enumerate(lines):
        if ln.startswith("# TYPE "):
            mname = ln.split()[2]
            assert lines[i - 1].startswith("# HELP %s " % mname), ln
    assert any(ln.startswith("# HELP adt_serve_latency_ms ")
               for ln in lines)
    assert any(ln.startswith("# HELP adt_prefetch_queue_depth ")
               for ln in lines)


def test_metrics_text_escapes_label_values():
    """Label values with backslash/quote/newline must escape per the
    exposition format or a strict scraper rejects the whole page."""
    rec = tel.TraceRecorder(capacity=4, sample=1, pid=1, host="h")
    rec.counter_add("a.b", 1)
    text = export.metrics_text(rec, labels={"worker": 'w"1\\x\nend'})
    assert 'worker="w\\"1\\\\x\\nend"' in text
    assert "\nadt_a_b_total{" in text  # the raw newline never leaked
    sample = next(ln for ln in text.splitlines()
                  if ln.startswith("adt_a_b_total"))
    # one line, and every quote inside the value is escaped: exactly the
    # two delimiter quotes remain unescaped
    import re
    assert len(re.findall(r'(?<!\\)"', sample)) == 2


# --------------------------------------------------- instrumented runtime


def _build_runner(builder, params, loss_fn, batch, opt=None):
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=builder)
    runner = ad.build(loss_fn, opt or optax.adam(0.1), params, batch)
    runner.init(params)
    return runner


def _problem(n_batches=8, seed=0):
    rng = np.random.RandomState(seed)
    params = {"w": jnp.asarray(rng.randn(4, 2).astype(np.float32)),
              "b": jnp.zeros((2,), jnp.float32)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] + p["b"] - batch["y"]) ** 2)

    batches = [{"x": rng.randn(16, 4).astype(np.float32),
                "y": rng.randn(16, 2).astype(np.float32)}
               for _ in range(n_batches)]
    return params, loss_fn, batches


def test_fused_fit_traces_multiple_subsystems(tmp_path):
    """The acceptance run: fit(fuse_steps=4) with tracing on produces a
    Perfetto-loadable trace with dispatch + PS + checkpoint spans and a
    registry exposing >= 10 counters."""
    tel.configure("1")
    params, loss_fn, batches = _problem()
    # the build helper runs autodist_tpu.reset(); the configure()
    # override is sticky, so tracing stays armed through it
    runner = _build_runner(S.PS(), params, loss_fn, batches[0])
    assert tel.tracing_enabled()
    from autodist_tpu.checkpoint.saver import Saver
    saver = Saver(directory=str(tmp_path), async_save=False)
    hist = runner.fit(list(batches), fuse_steps=4, metrics_every=2,
                      save_every=4, saver=saver)
    assert len(hist) == len(batches)

    rec = tel.get_recorder()
    cats = {e.cat for e in rec.events()}
    assert {"runner", "dstep", "ps", "ckpt"} <= cats, cats
    names = {e.name for e in rec.events()}
    assert {"runner.dispatch", "dstep.dispatch", "dstep.pull_ps",
            "ps.pull", "ckpt.write"} <= names, names

    # exported trace is Perfetto-loadable
    path = str(tmp_path / "trace.json")
    export.write_trace(path)
    trace = export.load_trace(path)
    assert export.validate_chrome_trace(trace) == []

    # the registry exposes >= 10 counters, several of them live
    counters = rec.counters()
    assert len(counters) >= 10
    assert counters["runner.steps"] == len(batches)
    assert counters["dstep.dispatches"] >= 2
    assert counters["ps.pulls"] >= 1
    assert counters["ckpt.saves"] >= 1

    # step_stats merges the registry with a stable shape
    stats = runner.step_stats()
    assert stats["telemetry"]["dispatches"] == counters["dstep.dispatches"]
    assert stats["telemetry"]["d2h_bytes"] > 0
    autodist_tpu.reset()


def test_prefetcher_counts_and_logs_dropped_tail():
    tel.configure("0")
    from autodist_tpu.data.prefetch import DevicePrefetcher
    batches = [{"x": np.zeros((6, 2), np.float32)} for _ in range(7)]
    pf = DevicePrefetcher(iter(batches), lambda b: b, stack=3)
    consumed = list(pf)
    assert len(consumed) == 2  # 7 = 2 full stacks + a dropped tail of 1
    assert pf.dropped_batches == 1
    assert pf.dropped_examples == 6
    c = tel.counters()
    assert c["prefetch.dropped_batches"] == 1
    assert c["prefetch.dropped_examples"] == 6
    assert c["prefetch.batches"] == 2


# ------------------------------------------------------------------- drift


def _local_spec():
    return ResourceSpec.from_dict({
        "nodes": [{"address": "127.0.0.1", "cpus": 8, "chief": True,
                   "network_bandwidth": 25}],
        "slice": {"ici_bandwidth": 100}})


@pytest.mark.parametrize("builder", [S.AllReduce, S.PS],
                         ids=["AllReduce", "PS"])
def test_drift_report_feeds_calibration(builder, tmp_path):
    """Measured dispatch spans + static collective profile join against
    the cost model into a drift report calibration.fit can consume."""
    params, loss_fn, batches = _problem()
    runner = _build_runner(builder(), params, loss_fn, batches[0])
    tel.configure("1")
    for b in batches[:4]:
        runner.run(b)
    report = drift.report_for_runner(runner, resource_spec=_local_spec(),
                                     batch=batches[0])
    assert report.num_steps == 4
    assert report.measured_step_s > 0
    assert report.predicted_step_s > 0
    terms = {t.term: t for t in report.terms}
    assert terms["step"].measured_s == report.measured_step_s
    assert terms["step"].ratio > 0
    # per-collective measured-vs-predicted rows exist when the program
    # has collectives (the 8-way data-parallel gradient reduce)
    kinds = {c.kind for c in report.collectives}
    if builder is S.AllReduce:
        assert "reduce" in kinds
        row = next(c for c in report.collectives if c.kind == "reduce")
        assert row.measured_wire_bytes > 0
        assert row.ratio > 0

    # serialization + CLI table
    d = report.to_dict()
    json.dumps(d)
    path = report.save(str(tmp_path / "drift.json"))
    assert drift.load_report(path)["strategy_id"] == report.strategy_id
    table = report.format_table()
    assert "drift report" in table and "collective" in table

    # the calibration feed: fitted scales are finite and positive
    cal = drift.fit_calibration([report])
    for scale in (cal.compute_scale, cal.ar_scale, cal.ps_scale,
                  cal.latency_scale):
        assert np.isfinite(scale) and scale > 0
    autodist_tpu.reset()


def test_fit_calibration_requires_measurements():
    report = drift.DriftReport(
        strategy_id="s", num_steps=0, predicted_step_s=1.0,
        measured_step_s=None, terms=[], collectives=[],
        breakdown={"compute_s": 1.0, "allreduce_s": 0.0, "ps_s": 0.0,
                   "latency_s": 0.0, "mp_s": 0.0},
        counters={})
    with pytest.raises(ValueError, match="measured"):
        drift.fit_calibration([report])


# ------------------------------------------------------------ log format


def test_json_log_format_carries_span_ids():
    import logging as std_logging
    from autodist_tpu.utils import logging as adt_logging
    fmt = adt_logging.make_formatter("json")
    record = std_logging.LogRecord("autodist_tpu", std_logging.WARNING,
                                   "file.py", 12, "retry %d", (3,), None)
    line = json.loads(fmt.format(record))
    assert line["msg"] == "retry 3"
    assert line["level"] == "WARNING"
    assert "span_id" not in line  # no live span
    tel.configure("1")
    with tel.span("coord.backoff", "coord"):
        line = json.loads(fmt.format(record))
    assert line["span_id"] > 0
    # text mode still renders the classic format
    text = adt_logging.make_formatter("text").format(record)
    assert "retry 3" in text and not text.startswith("{")


def test_set_format_switches_live_handlers(monkeypatch):
    from autodist_tpu.utils import logging as adt_logging
    logger = adt_logging.get_logger()
    adt_logging.set_format("json")
    try:
        assert all(isinstance(h.formatter, adt_logging._JsonFormatter)
                   for h in logger.handlers)
    finally:
        adt_logging.set_format("text")


# --------------------------------------------------------------------- CLI


def test_cli_inspect_validate_merge_diff_drift(tmp_path, capsys):
    from autodist_tpu.telemetry import cli
    r1 = tel.TraceRecorder(capacity=64, sample=1, pid=11, host="a")
    r2 = tel.TraceRecorder(capacity=64, sample=1, pid=12, host="b")
    _record_some(r1)
    _record_some(r2)
    p1 = str(tmp_path / "t1.json")
    p2 = str(tmp_path / "t2.json")
    export.write_trace(p1, r1)
    export.write_trace(p2, r2)

    assert cli.main(["validate", p1]) == 0
    assert cli.main(["inspect", p1]) == 0
    out = capsys.readouterr().out
    assert "a" in out and "runner.steps" in out

    merged = str(tmp_path / "merged.json")
    assert cli.main(["merge", merged, p1, p2]) == 0
    merged_trace = export.load_trace(merged)
    assert export.validate_chrome_trace(merged_trace) == []
    # cluster totals SUM across processes (each worker counted steps=2)
    assert cli._counters(merged_trace)["runner.steps"] == 4.0
    assert cli._counters(export.load_trace(p1))["runner.steps"] == 2.0
    assert cli.main(["diff", p1, p2]) == 0

    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": []}, f)
    assert cli.main(["validate", bad]) == 1

    report = drift.DriftReport(
        strategy_id="s", num_steps=2, predicted_step_s=0.01,
        measured_step_s=0.02,
        terms=[drift.TermDrift("step", 0.01, 0.02)],
        collectives=[drift.CollectiveDrift("reduce", 100.0, 150.0)],
        breakdown={}, counters={})
    rpath = report.save(str(tmp_path / "drift.json"))
    assert cli.main(["drift", rpath]) == 0
    out = capsys.readouterr().out
    assert "reduce" in out and "strategy=s" in out


# ------------------------------------------------------------- histograms


def test_histogram_observe_quantiles_and_validation():
    h = tel.Histogram()
    assert h.quantile(0.5) is None  # empty
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):
        h.observe(v)
    assert h.count == 5 and h.sum == 110.0
    assert h.min == 1.0 and h.max == 100.0
    # quantiles interpolate inside the bucket but never leave the data
    assert h.min <= h.quantile(0.5) <= h.max
    assert h.quantile(0.99) <= h.max
    assert h.quantile(1.0) == h.max
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        h.quantile(1.5)
    with pytest.raises(ValueError, match="sorted"):
        tel.Histogram(bounds=(3.0, 1.0))
    with pytest.raises(ValueError, match="non-empty"):
        tel.Histogram(bounds=())
    # wire-format round trip (the cross-process scrape path)
    d = h.to_dict()
    assert d["p50"] == h.quantile(0.5) and d["p99"] == h.quantile(0.99)
    back = tel.Histogram.from_dict(d)
    assert back.to_dict() == d


def test_histogram_registry_prometheus_and_chrome_export():
    rec = tel.TraceRecorder(capacity=16, sample=1, pid=42, host="h")
    for v in (0.5, 2.0, 2.5, 40.0):
        rec.hist_observe("serve.latency_ms", v)
    assert rec.hist_quantile("serve.latency_ms", 0.5) is not None
    assert rec.hist_quantile("nope", 0.5) is None
    text = export.metrics_text(rec, labels={"worker": "w0"})
    assert "# TYPE adt_serve_latency_ms histogram" in text
    # cumulative le buckets merge the caller's labels, end at +Inf
    assert 'adt_serve_latency_ms_bucket{worker="w0",le="+Inf"} 4' in text
    assert 'adt_serve_latency_ms_sum{worker="w0"} 45' in text
    assert 'adt_serve_latency_ms_count{worker="w0"} 4' in text
    buckets = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
               if l.startswith("adt_serve_latency_ms_bucket")]
    assert buckets == sorted(buckets)  # cumulative by construction
    trace = export.chrome_trace(rec)
    assert export.validate_chrome_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "C"}
    assert {"serve.latency_ms.p50", "serve.latency_ms.p99"} <= names


def test_histogram_survives_publish_scrape_round_trip():
    client = _FakeCoordClient()
    rec = tel.TraceRecorder(capacity=16, sample=1, pid=7, host="n0")
    rec.hist_observe("serve.latency_ms", 3.0)
    rec.hist_observe("serve.latency_ms", 9.0)
    export.publish_telemetry(client, "w0", rec)
    scraped = export.scrape_cluster(client, ["w0"])
    text = scraped["metrics_text"]
    assert 'adt_serve_latency_ms_count{worker="w0"} 2' in text
    assert 'adt_serve_latency_ms_sum{worker="w0"} 12' in text


def test_module_level_histogram_helpers_and_reset():
    tel.hist_observe("serve.latency_ms", 5.0)
    assert tel.hist_quantile("serve.latency_ms", 0.5) is not None
    assert "serve.latency_ms" in tel.histograms()
    tel.get_recorder().clear()
    assert tel.hist_quantile("serve.latency_ms", 0.5) is None
    assert tel.histograms() == {}
