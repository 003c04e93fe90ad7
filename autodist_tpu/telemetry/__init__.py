"""Runtime telemetry: span tracing, metrics registry, drift tracking.

The runtime observability layer (docs/observability.md):

- :mod:`~autodist_tpu.telemetry.spans` — the thread-safe ring-buffer
  :class:`TraceRecorder` and the ``span()``/``counter_add()`` helpers the
  framework's hot paths are instrumented with (near-zero cost when
  ``ADT_TRACE=0``), and the set-up account (:func:`setup_account`): the
  phases of build, init and the first step with what JAX compiled or
  loaded beneath them, kept past ``clear()``;
- :mod:`~autodist_tpu.telemetry.scopes` — the one table of
  ``jax.named_scope`` names inside the compiled programs, and
  :func:`scope_map`: HLO instruction name → ``op_name`` of a running
  program, by its XLA module name (computed on demand), and
  :func:`step_account`: what the same compile says the program holds on
  a device (scratch, arguments, outputs, generated code);
- :mod:`~autodist_tpu.telemetry.export` — Chrome-trace/Perfetto JSON,
  Prometheus ``metrics_text()``, and cross-process publish/scrape over
  the coordination service;
- :mod:`~autodist_tpu.telemetry.drift` — measured-vs-predicted drift
  reports feeding ``simulator/calibration.py``;
- :mod:`~autodist_tpu.telemetry.cluster` — NTP-style clock-offset
  handshake over the coordination service (step-aligned merged
  timelines) + the fleet-coordinated profiling flag;
- :mod:`~autodist_tpu.telemetry.goodput` — attributed wall-time
  decomposition (compute / collective-wait / PS-wire / host-input /
  checkpoint / rollback-replay), cross-worker skew, straggler flagging;
- :mod:`~autodist_tpu.telemetry.blackbox` — the always-on bounded
  flight recorder, dumped atomically on divergence/rollback/breaker-open
  and fatal signals;
- ``python -m autodist_tpu.telemetry`` — inspect/merge/diff/validate
  trace files, print drift/goodput tables, read blackbox dumps, post
  fleet profiling windows.
"""
from autodist_tpu.telemetry.spans import (  # noqa: F401
    TraceRecorder, configure, counter_add, counters, current_span_id,
    gauge_set, get_recorder, instant, reset, setup_account, span,
    tracing_enabled)
from autodist_tpu.telemetry.scopes import (  # noqa: F401
    SCOPES, register_program, registered_programs, scope, scope_map,
    step_account)
from autodist_tpu.telemetry.export import (  # noqa: F401
    chrome_trace, merge_traces, metrics_text, publish_telemetry,
    scrape_cluster, validate_chrome_trace, write_trace)
from autodist_tpu.telemetry.drift import (  # noqa: F401
    DriftReport, build_report, fit_calibration, report_for_runner)
from autodist_tpu.telemetry.cluster import (  # noqa: F401
    ClockOffset, ClockSyncResponder, estimate_clock_offset,
    request_profile, step_alignment, sync_recorder_clock)
from autodist_tpu.telemetry.goodput import (  # noqa: F401
    GoodputReport, StragglerEwma, cluster_goodput)
from autodist_tpu.telemetry.goodput import (  # noqa: F401
    build_report as build_goodput_report)
from autodist_tpu.telemetry.blackbox import (  # noqa: F401
    FlightRecorder, get_flight_recorder)

__all__ = [
    "TraceRecorder", "configure", "counter_add", "counters",
    "current_span_id", "gauge_set", "get_recorder", "instant", "reset",
    "setup_account", "span", "tracing_enabled",
    "SCOPES", "register_program", "registered_programs", "scope",
    "scope_map", "step_account",
    "chrome_trace", "merge_traces", "metrics_text", "publish_telemetry",
    "scrape_cluster", "validate_chrome_trace", "write_trace",
    "DriftReport", "build_report", "fit_calibration", "report_for_runner",
    "ClockOffset", "ClockSyncResponder", "estimate_clock_offset",
    "request_profile", "step_alignment", "sync_recorder_clock",
    "GoodputReport", "StragglerEwma", "cluster_goodput",
    "build_goodput_report",
    "FlightRecorder", "get_flight_recorder",
]
