"""What both drivers share: the profiler window, the spans' export, the
device's memory peak. Nothing here knows a model or a traffic mix."""
import shutil
import tempfile
import threading
import time

from benchmark.trace import reduce as tr
from benchmark.trace import xplane


class TraceWindow:
    """A ``jax.profiler`` trace of a short part of the measured window.

    ``start()`` and ``stop()`` may be called from any thread; ``load()``,
    after the window, reads the trace (written to a temporary directory
    outside the checkout) into a table and deletes it. ``window_ns`` is the traced interval on the host's
    ``perf_counter_ns`` clock, taken inside the profiler session, and
    ``table`` the events with ``offset_ns`` = trace clock - host clock.
    """

    def __init__(self):
        self.table = None
        self.window_ns = None
        self.offset_ns = None
        self.align_error_us = None
        self._dir = None
        self._lock = threading.Lock()

    def start(self):
        import jax
        with self._lock:
            self._dir = tempfile.mkdtemp(prefix="adt_bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # one event per Python call: noise
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            # one interval on both clocks: the annotation lands in the
            # trace, the two readings bracket its start on the host clock
            before = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(tr.ALIGN_EVENT):
                after = time.perf_counter_ns()
            self._align = (before, after)
            self._t0 = time.perf_counter_ns()

    def stop(self):
        """End the profiler session (idempotent). Reading the trace is left
        to ``load``, after the measured window."""
        import jax
        with self._lock:
            if self._dir is None or self.window_ns is not None:
                return
            t1 = time.perf_counter_ns()
            jax.profiler.stop_trace()
            self.window_ns = (self._t0, t1)

    def load(self):
        """Read the trace into ``table`` and delete the files."""
        self.stop()
        if self._dir is None or self.table is not None:
            return
        try:
            self.table = xplane.load(xplane.find_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        before, after = self._align
        off = tr.align_offset_ns(self.table, (before + after) // 2)
        if off is not None:
            self.offset_ns = off
            # the annotation began between the two readings
            self.align_error_us = (after - before) / 2e3

    @property
    def started(self):
        return self._dir is not None

    def window_on_trace_clock(self):
        if self.window_ns is None or self.offset_ns is None:
            return None
        return (self.window_ns[0] + self.offset_ns,
                self.window_ns[1] + self.offset_ns)


def add_telemetry(rec):
    """The program's telemetry of the window into the record: spans as
    (name, start_ns, end_ns, args) on the perf_counter_ns clock, and the
    counters that moved."""
    from autodist_tpu import telemetry
    recorder = telemetry.get_recorder()
    rec["spans"] = [(e.name, e.ts_ns, e.ts_ns + e.dur_ns, dict(e.args or {}))
                    for e in recorder.events()]
    rec["counters"] = {k: v for k, v in recorder.counters().items() if v}


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip (0 where the backend does not
    report it, as on the CPU rehearsal)."""
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
