"""Runner / WrappedSession — steady-state execution.

Analog of reference ``autodist/runner.py:78-132``. The reference's
``WrappedSession`` targets the local gRPC TF server, auto-runs initializers,
and routes ``run`` through the Remapper; here the "session" owns the
TrainState, routes feeds/fetches through the Remapper, and invokes the
jitted SPMD step (JAX dispatch to the TPU runtime replaces the gRPC session
client). Step tracing (the reference's chrome-trace dump,
``runner.py:66-75,123-131``) maps to ``jax.profiler`` traces written under
``/tmp/autodist_tpu/traces``.
"""
import itertools
import os
import time
from typing import Any, Optional

import jax
import numpy as np

from autodist_tpu import const
from autodist_tpu.remapper import Remapper
from autodist_tpu.telemetry import scopes
from autodist_tpu.telemetry import spans as tel
from autodist_tpu.train_state import TrainState
from autodist_tpu.utils import logging


_NO_BATCH = object()  # next(it, _NO_BATCH): the source is exhausted


class MetricsHandle:
    """Device-resident step metrics from ``Runner.run(sync=False)`` or
    ``Runner.run_superstep``: the dispatch returned immediately, and the
    device→host readback is deferred until :meth:`result` (or any
    mapping-style access — ``handle["loss"]`` — which forces it). This is
    what lets the steady-state loop stay free of per-step host
    round-trips: handles accumulate device-side and one readback
    materializes many steps' metrics at a ``metrics_every`` boundary."""

    __slots__ = ("_device", "_remapper", "_host", "microsteps", "_observer",
                 "step")

    def __init__(self, device_metrics, remapper, microsteps: int = 1,
                 observer=None, step: Optional[int] = None):
        self._device = device_metrics
        self._remapper = remapper
        self._host = None
        self.microsteps = microsteps
        # index of the (first) microstep these metrics belong to: the
        # readback's spans carry it, so a step's spans share it
        self.step = step
        # called once per MICROSTEP (in order) when the handle
        # materializes — the sentinel's verdict intake; consumed on first
        # result() so re-reads never replay observations
        self._observer = observer

    @property
    def materialized(self) -> bool:
        return self._host is not None

    def result(self):
        """Host metrics (forces the device→host copy on first call).
        Superstep handles return stacked ``[k, ...]`` leaves."""
        if self._host is None:
            with tel.span("runner.readback", "runner",
                          microsteps=self.microsteps, step=self.step):
                # the wait for the step's outputs is the DEVICE's time
                # (goodput: compute), the copy after it the host's
                with tel.span("runner.wait_device", "runner",
                              step=self.step):
                    jax.block_until_ready(self._device)
                with tel.span("runner.fetch", "runner", step=self.step):
                    self._host = self._remapper.remap_fetch(self._device)
            self._device = None  # free the device buffers
            tel.counter_add("runner.readbacks")
            if tel.tracing_enabled() and isinstance(self._host, dict):
                # what the DEVICE counted in these steps (the loss's
                # telemetry.device_counters; [k] per name when fused)
                for name, value in self._host.get("counters", {}).items():
                    tel.counter_add(name, float(np.sum(value)))
            tel.counter_add("runner.d2h_bytes", sum(
                getattr(np.asarray(leaf), "nbytes", 0)
                for leaf in jax.tree_util.tree_leaves(self._host)))
            if self._observer is not None:
                # consume BEFORE calling: unstack() re-enters result()
                obs, self._observer = self._observer, None
                for m in self.unstack():
                    obs(m)
        return self._host

    def unstack(self) -> list:
        """Per-microstep host metrics — ``microsteps`` dicts of unstacked
        leaves (a length-1 list for plain-step handles)."""
        host = self.result()
        if self.microsteps == 1:
            return [host]
        return [jax.tree_util.tree_map(lambda a, _i=i: np.asarray(a)[_i],
                                       host)
                for i in range(self.microsteps)]

    def __getitem__(self, key):
        return self.result()[key]

    def __iter__(self):
        return iter(self.result())

    def keys(self):
        return self.result().keys()

    def items(self):
        return self.result().items()

    def __repr__(self):
        state = "materialized" if self.materialized else "device-resident"
        return "MetricsHandle(microsteps=%d, %s)" % (self.microsteps, state)


class Runner:
    """Owns a DistributedStep + TrainState and runs steps."""

    def __init__(self, distributed_step, tracing: bool = False,
                 hbm_budget_bytes: Optional[float] = None,
                 sentinel=None):
        self._dstep = distributed_step
        # per-device HBM budget for memory_report(): AutoDist passes the
        # resource spec's chip capacity; a bare Runner has no budget and
        # memory_report only estimates (no ADT501/502 gate)
        self._hbm_budget = hbm_budget_bytes
        self._remapper = Remapper(distributed_step.mesh,
                                  distributed_step.mesh_axis,
                                  seq_axis=distributed_step.seq_axis,
                                  batch_axes=distributed_step.batch_axes,
                                  seq_keys=getattr(distributed_step,
                                                   "seq_feed_keys", None))
        self._tracing = tracing
        self._trace_started = False
        self.state: Optional[TrainState] = None
        # _step_count counts MICROSTEPS (optimizer applies) — the unit the
        # staleness-pacing and mirror-check protocols are defined over; a
        # fused superstep advances it by k. _superstep_count counts jitted
        # dispatches (run/run_superstep calls) — the unit wall-time
        # samples are taken in.
        self._step_count = 0
        self._superstep_count = 0
        # wall time of every run() call (first element includes compile);
        # bounded so week-long jobs don't grow a list forever — the first
        # step and a sliding window of recent steps carry all the signal
        # step_stats() reports
        self._first_step_s: Optional[float] = None
        self._recent_step_s: list = []
        self._total_step_s = 0.0
        self._coord = None
        self._mirror_coord = None
        self._staleness = int(distributed_step.metadata.get("staleness", 0))
        # bounded-staleness pacing is a cross-process property; within one
        # SPMD program all replicas are already lockstep. Async PS paces
        # itself through the parameter service (no step barrier at all).
        if (self._staleness > 0 and const.ENV.ADT_NUM_PROCESSES.val > 1
                and not distributed_step.metadata.get("async")):
            self._coord = self._connect_coordination(
                "staleness pacing (window=%d)" % self._staleness)
        # async multi-process jobs heartbeat time-based so the chief's
        # watchdog can tell a deadlocked-but-alive worker from a healthy
        # one (sync jobs without staleness are collective-lockstep: a
        # wedged peer shows up as a wedged collective, not silence)
        self._async_hb = None
        self._last_hb = 0.0
        self._hb_enabled = (distributed_step.metadata.get("async")
                            and const.ENV.ADT_NUM_PROCESSES.val > 1)
        if self._hb_enabled:
            self._async_hb = self._connect_coordination(
                "async liveness heartbeats")
        self._atexit_cb = None
        if const.ENV.ADT_NUM_PROCESSES.val > 1:
            # goodbye-on-exit: a worker whose script simply ends must
            # deregister, or its last heartbeat ages into a false death.
            # Registered through a weakref so a discarded runner (and its
            # TrainState) is not pinned for the process lifetime; close()
            # unregisters explicitly.
            import atexit
            import weakref
            ref = weakref.ref(self)

            def _close_if_alive(_r=ref):
                runner = _r()
                if runner is not None:
                    runner.close()
            self._atexit_cb = _close_if_alive
            atexit.register(_close_if_alive)
        # ---- training health sentinel (runtime/sentinel.py): None
        # defers to ADT_SENTINEL; an active policy consumes the in-graph
        # verdicts at readback boundaries and drives skip-budget
        # accounting, rollback and save quarantine
        from autodist_tpu.runtime import sentinel as sentinel_lib
        policy = sentinel_lib.resolve_policy(sentinel)
        self._sentinel = (sentinel_lib.Sentinel(policy, self)
                          if policy is not None else None)
        self._sentinel_diags = []
        if self._sentinel is not None:
            from autodist_tpu.analysis import rules as rules_lib
            self._sentinel_diags = rules_lib.verify_sentinel(
                policy, distributed_step.metadata)
            for d in self._sentinel_diags:
                logging.warning("%s", d)
        # one-shot "compiling" grace around first-dispatch compilation:
        # a long XLA compile must not age this worker into a false death
        # at the chief's heartbeat watchdog
        self._compile_grace_marked = False
        self._compile_grace_cleared = False
        # ---- elastic membership plane (runtime/elastic.py): when a
        # membership is installed (in-run elastic jobs), readback
        # boundaries poll the cluster epoch; a bump parks a pending
        # reconfigure that executes at the next SAFE point (never inside
        # a dispatch or metrics materialization)
        from autodist_tpu.runtime import elastic as elastic_lib
        self._membership = elastic_lib.current()
        self._reconfigure_fn = None   # wired by AutoDist (rebuild + re-shard)
        self._reconfig_pending = None  # (epoch, roster) awaiting a safe point
        self._epoch_poll_at = 0.0
        self._last_reconfigure_s = None
        self._reconfigs = 0
        # ---- preemption plane (runtime/preemption.py): advance-notice
        # graceful departure — SIGTERM-with-deadline, maintenance events
        # and operator drains all park a notice the readback boundaries
        # consume (cluster-agreed rescue checkpoint, then planned handoff)
        from autodist_tpu.runtime import preemption as preemption_lib
        self._preempt = preemption_lib.PreemptionGuard(self)
        # (step, snapshot) pre-staged while a planned departure is
        # pending, so the reconfigure span skips the snapshot work
        self._prestaged = None
        # ---- cluster observability plane (telemetry/): arm the flight
        # recorder (always-on bounded black box; also installs the
        # SIGTERM/exit dump hooks per ADT_BLACKBOX*), the online
        # straggler detector, and the fleet-profiling window state
        from autodist_tpu.telemetry import blackbox as blackbox_lib
        from autodist_tpu.telemetry import cluster as cluster_lib
        from autodist_tpu.telemetry import goodput as goodput_lib
        blackbox_lib.get_flight_recorder()
        self._straggler = goodput_lib.StragglerEwma()
        self._straggler_mark_at = 0.0
        # fleet-profiling window: (seq, first_step, last_step) from the
        # coordination-service flag (polled at ADT_PROFILE_POLL_S) or the
        # serviceless ADT_PROFILE_STEPS env; seq 0 = the env window
        env_window = cluster_lib.parse_profile_env(
            const.ENV.ADT_PROFILE_STEPS.val)
        self._profile_window = ((0,) + env_window) if env_window else None
        self._profile_active = False
        self._profile_done_seq = -1
        self._profile_poll_at = 0.0
        self._profile_coord = None  # lazily shares an existing client
        self._fused_k = 0  # microsteps of the last fused superstep
        self._register_programs()

    # ------------------------------------------- programs, by module name

    def _register_programs(self):
        """Make the compiled programs inspectable by name
        (``telemetry.scope_map``) under XLA's module name, ``jit_`` + the
        jitted function's name. Nothing is lowered until someone asks."""
        dstep = self._dstep
        for fn, lower in ((dstep._step_fn, self._lower_step),
                          (dstep._eval_fn, self._lower_eval)):
            if getattr(fn, "__name__", None):
                scopes.register_program("jit_" + fn.__name__, lower)
        if dstep._fused_builder is not None:
            scopes.register_program("jit_local_multi", self._lower_fused)

    def _state_avals(self):
        if self.state is None:
            raise RuntimeError("no state yet: Runner.init() comes first")
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), self.state)

    def _lower_on_example(self, fn):
        """``fn`` (state, ps values, batch) as this runner runs it: the
        live state's shapes and shardings, the build's example batch
        placed as ``remap_feed`` places one."""
        ps_avals, _ = self._dstep._ps_avals()
        return fn.lower(
            self._state_avals(), ps_avals,
            self._remapper.feed_avals(self._dstep.model_item.example_batch))

    def _lower_step(self):
        return self._lower_on_example(self._dstep._step_fn)

    def _lower_eval(self):
        return self._lower_on_example(self._dstep._eval_fn)

    def _lower_fused(self):
        """The fused k-microstep program, at the k of the last superstep."""
        if not self._fused_k:
            raise RuntimeError("no fused superstep has run yet")
        ps_avals, opt_avals = self._dstep._ps_avals(with_opt=True,
                                                    wire=False)
        return self._dstep._fused_fn().lower(
            self._state_avals(), ps_avals, opt_avals,
            self._remapper.feed_avals(self._dstep.model_item.example_batch,
                                      stack=self._fused_k))

    def _connect_coordination(self, purpose: str = "staleness pacing"):
        from autodist_tpu.runtime.coordination import CoordinationClient
        from autodist_tpu.runtime.resilience import (
            ResilientCoordinationClient)
        host = (const.ENV.ADT_COORDINATOR_ADDR.val.split(":")[0]
                or "127.0.0.1")
        port = const.ENV.ADT_COORDSVC_PORT.val
        try:
            # one raw connect as the reachability probe (the resilient
            # client connects lazily and would retry with backoff — too
            # slow a way to learn the service simply is not deployed)
            CoordinationClient(host, port).close()
        except OSError as e:
            logging.warning("coordination service unreachable (%s); "
                            "%s disabled", e, purpose)
            return None
        # steady state rides the resilient client: per-RPC deadlines,
        # reconnect with backoff, idempotent STEP/BARRIER retry — a
        # service blip mid-run degrades to a retried RPC instead of
        # killing pacing/heartbeats with the connection
        logging.info("%s active via %s", purpose, host)
        return ResilientCoordinationClient(host, port)

    @property
    def distributed_step(self):
        return self._dstep

    @property
    def remapper(self):
        return self._remapper

    def init(self, params, opt_state=None) -> TrainState:
        """Initialize distributed state (the reference's auto-run of
        initializers on session creation, ``runner.py:97-100``).

        Under ``ADT_AUTO_RESUME`` (set by the sync-elastic whole-job
        restart, or by the user for at-most-once resume), a committed
        checkpoint in ``ADT_CKPT_DIR`` is restored over the fresh init —
        every process calls init(), so the restore's collective placement
        runs everywhere.

        With tracing on this is the ``setup.init`` span of the set-up
        account, holding ``setup.init_state`` or ``setup.restore``."""
        with tel.span("setup.init", tel.SETUP_CAT):
            state = self._init(params, opt_state)
            # the state owns copies (``init_state``'s ``place_var``): what
            # the build kept of the initial parameters is read for shapes
            # only from here on
            self._dstep.release_initial_params()
            return state

    def _init(self, params, opt_state) -> TrainState:
        m = self._membership
        if m is not None and getattr(m, "joined_late", False):
            # grow-on-join: this worker was admitted into a RUNNING job —
            # the survivors broadcast the run's state (the chief sends at
            # the end of its reconfigure); a fresh init or a stale
            # checkpoint would diverge from the live run
            from autodist_tpu.runtime import elastic as elastic_lib
            snap = elastic_lib.broadcast_state(None)
            elastic_lib.adopt_snapshot(self, snap)
            m.joined_late = False
            try:
                m.ack(m.epoch)
            except OSError:
                pass
            logging.warning("elastic: adopted broadcast state at step %d "
                            "(grow-on-join)", snap.get("step") or 0)
            return self.state
        if const.ENV.ADT_AUTO_RESUME.val:
            # probe BOTH checkpoint formats — a sync-elastic job that
            # checkpoints through ShardedSaver (the scale path) must
            # auto-resume from its shard files, not fail fast because no
            # plain-format meta exists; when both exist, the newer step
            # wins. latest_checkpoint runs the fast integrity validation,
            # so torn save attempts (a crash mid-save is exactly when
            # auto-resume runs) and damaged steps are skipped up front,
            # and restore() falls back further if damage only surfaces
            # while reading (ckpt.fallback counts every skip).
            from autodist_tpu.checkpoint import latest_checkpoint
            _, saver = latest_checkpoint(const.ENV.ADT_CKPT_DIR.val)
            if saver is not None:
                # restore() builds the placed state itself — a fresh
                # init_state first would materialize the whole tree on
                # device just to throw it away
                try:
                    with tel.span("setup.restore", tel.SETUP_CAT):
                        _, step = saver.restore(self)
                except FileNotFoundError as e:
                    # every candidate was skipped as torn/corrupt
                    if const.ENV.ADT_NUM_PROCESSES.val > 1:
                        raise RuntimeError(
                            "ADT_AUTO_RESUME: no valid checkpoint to "
                            "resume from (%s) — peers restoring different "
                            "steps would diverge, refusing to start "
                            "fresh" % e) from e
                    logging.warning("ADT_AUTO_RESUME: %s; starting fresh",
                                    e)
                else:
                    logging.warning("ADT_AUTO_RESUME: restored step %d "
                                    "from %s (%s)", step,
                                    const.ENV.ADT_CKPT_DIR.val,
                                    type(saver).__name__)
                    return self.state
            elif const.ENV.ADT_NUM_PROCESSES.val > 1:
                # one process starting fresh while lockstep peers restore
                # step N diverges every collective — fail loudly (usual
                # cause: the checkpoint dir is not shared across hosts)
                raise RuntimeError(
                    "ADT_AUTO_RESUME is set but no valid committed "
                    "checkpoint exists in %s on this process — a "
                    "multi-process resume needs the checkpoint directory "
                    "shared across hosts (run `python -m "
                    "autodist_tpu.checkpoint ls --dir %s` to inspect)"
                    % (const.ENV.ADT_CKPT_DIR.val,
                       const.ENV.ADT_CKPT_DIR.val))
            else:
                logging.warning("ADT_AUTO_RESUME set but no valid "
                                "checkpoint in %s; starting fresh",
                                const.ENV.ADT_CKPT_DIR.val)
        with tel.span("setup.init_state", tel.SETUP_CAT):
            self.state = self._dstep.init_state(params, opt_state)
        self.notify_state_restored()  # fresh init resets the LR scale
        return self.state

    _RECENT_WINDOW = 512

    @property
    def _heartbeat_every_s(self) -> float:
        # a quarter of the watchdog's window: three missable beats
        return max(0.25, const.ENV.ADT_HEARTBEAT_TIMEOUT_S.val / 4.0)

    def _start_trace_if_due(self):
        # _profile_active: a fleet window already owns jax.profiler — a
        # second start_trace would raise; the first-step trace defers to
        # a later dispatch (self._tracing stays armed)
        if self._tracing and not self._trace_started \
                and not self._profile_active:
            os.makedirs(const.DEFAULT_TRACE_DIR, exist_ok=True)
            jax.profiler.start_trace(os.path.join(
                const.DEFAULT_TRACE_DIR, time.strftime("%Y%m%d-%H%M%S")))
            self._trace_started = True

    def _stop_trace_if_due(self, metrics):
        # not inside the first step: ``_first_step`` stops the trace after
        # its span has ended (an annotation still open at the stop is lost)
        if self._tracing and self._trace_started \
                and self._first_step_s is not None:
            jax.block_until_ready(metrics)
            jax.profiler.stop_trace()
            self._trace_started = False
            self._tracing = False  # trace only the first step, like FULL_TRACE runs

    # ------------------------------------------- fleet-coordinated profiling

    def _profile_client(self):
        """A coordination client to poll the fleet profiling flag with —
        reuse whatever this runner already opened (pacing, liveness,
        mirror); never dial a connection just for profiling."""
        for client in (self._coord, self._async_hb, self._mirror_coord):
            if client not in (None, False):
                return client
        return None

    def _maybe_fleet_profile(self):
        """The fleet-profiling window machinery (the generalization of
        the first-step ``tracing=True`` hook above): the chief posts
        "profile steps N..M" on the coordination service
        (``telemetry.request_profile`` / ``python -m
        autodist_tpu.telemetry profile N M``), every worker polls the
        flag at ``ADT_PROFILE_POLL_S``, and each captures a
        ``jax.profiler`` trace for the SAME step window — one
        XLA-level profile per worker, step-aligned with the merged
        telemetry trace it lands next to. ``ADT_PROFILE_STEPS=N:M``
        arms the same window locally without a service.

        Touches LOCAL state only — it runs inside the dispatch span and
        the per-dispatch wall-time sample; the KV poll lives in
        :meth:`_poll_profile_window` (called from ``_after_dispatch``
        next to the other control-plane RPCs) so a retrying poll during
        a service blip neither masquerades as compute time in the
        goodput decomposition nor feeds the straggler EWMA a false
        outlier."""
        if self._profile_window is None:
            return
        seq, first, last = self._profile_window
        step = self._step_count  # the step the NEXT dispatch runs
        if not self._profile_active:
            if first <= step <= last and not self._trace_started:
                worker = const.ENV.ADT_WORKER.val or "chief"
                out = os.path.join(
                    const.DEFAULT_TRACE_DIR,
                    "fleet-%d-%s" % (seq, worker.replace(":", "_")))
                os.makedirs(out, exist_ok=True)
                try:
                    jax.profiler.start_trace(out)
                except RuntimeError as e:  # another trace in flight
                    logging.warning("fleet profiling: start_trace failed "
                                    "(%s) — window #%d skipped", e, seq)
                    self._profile_done_seq = seq
                    self._profile_window = None
                    return
                self._profile_active = True
                tel.counter_add("profiler.windows")
                tel.instant("profiler.window_start", "runner", seq=seq,
                            step=step, first=first, last=last)
                logging.info("fleet profiling: capturing steps %d..%d "
                             "into %s", first, last, out)
            elif step > last:
                # the window is already behind this worker (posted too
                # late, or a rollback rewound past it): never arms
                self._profile_done_seq = max(self._profile_done_seq, seq)
                self._profile_window = None
            return

    def _poll_profile_window(self):
        """Poll the coordination-service profiling flag (at most every
        ``ADT_PROFILE_POLL_S``; 0 disables) and arm a fresh window for
        the NEXT dispatch. Runs in ``_after_dispatch`` with the other
        control-plane RPCs — see :meth:`_maybe_fleet_profile`."""
        poll_s = const.ENV.ADT_PROFILE_POLL_S.val
        if (self._profile_window is not None or self._profile_active
                or poll_s <= 0
                or time.monotonic() < self._profile_poll_at):
            return
        self._profile_poll_at = time.monotonic() + poll_s
        client = self._profile_client()
        if client is None:
            return
        from autodist_tpu.telemetry import cluster as cluster_lib
        with tel.span("runner.profile_poll", "runner"):
            window = cluster_lib.read_profile_window(client)
        if window is not None and window[0] > self._profile_done_seq:
            self._profile_window = window
            logging.info("fleet profiling window #%d armed: "
                         "steps %d..%d", *window)

    def _maybe_fleet_profile_stop(self):
        """Close the window AFTER the dispatch that ran its last step."""
        if not self._profile_active or self._profile_window is None:
            return
        seq, _first, last = self._profile_window
        if self._step_count > last:
            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                pass
            self._profile_active = False
            self._profile_done_seq = max(self._profile_done_seq, seq)
            self._profile_window = None
            tel.instant("profiler.window_stop", "runner", seq=seq,
                        step=self._step_count)

    def _compile_grace_begin(self):
        """Pre-compile heartbeat + one-shot ``compiling`` grace mark,
        sent just before the FIRST dispatch (which carries the XLA
        compile). A fused-k compile of a big bucket can exceed
        ``ADT_HEARTBEAT_TIMEOUT_S`` between step-driven beats, and the
        chief's watchdog would age this healthy worker into a false
        death; the mark (a wall-clock KV record the watchdog checks, see
        ``Coordinator._in_compile_grace``) buys ``ADT_COMPILE_GRACE_S``
        of silence, and is cleared the moment the first dispatch
        returns."""
        if self._superstep_count > 0 or self._compile_grace_marked:
            return
        client = self._async_hb or self._coord
        if client is None:
            return
        worker = const.ENV.ADT_WORKER.val or "chief"
        try:
            client.heartbeat(worker)
            # wall clock, not monotonic: the watchdog runs in ANOTHER
            # process; the grace window is minutes, so host clock skew
            # is noise
            client.put("compiling/%s" % worker, repr(time.time()))
            self._compile_grace_marked = True
            self._last_hb = time.monotonic()
        except (OSError, RuntimeError) as e:
            # best-effort: a rejected/unreachable mark must never stop
            # training — worst case the watchdog sees compile silence
            logging.warning("pre-compile heartbeat failed (%s); the "
                            "watchdog may see a long first compile as "
                            "silence", e)

    def _compile_grace_end(self):
        """Clear the one-shot compiling mark — steady-state silence must
        age normally again."""
        if not self._compile_grace_marked or self._compile_grace_cleared:
            return
        self._compile_grace_cleared = True
        client = self._async_hb or self._coord
        if client is None:
            return
        worker = const.ENV.ADT_WORKER.val or "chief"
        try:
            # "0" = epoch zero: instantly outside any grace window (the
            # line protocol needs a non-empty value token)
            client.put("compiling/%s" % worker, "0")
        except (OSError, RuntimeError):
            pass  # mark ages out via the grace window anyway

    def _maybe_sentinel_act(self):
        """Perform a pending sentinel rollback (or raise the typed
        ``TrainingDiverged``) at a SAFE point — before a dispatch or
        after a readback boundary, never from inside a metrics
        materialization."""
        if self._sentinel is not None:
            self._sentinel.maybe_act()

    # ---------------------------------------- in-run elastic reconfiguration

    def set_reconfigure_handler(self, fn):
        """Wire the rebuild half of an in-run reconfiguration:
        ``fn(runner, epoch, roster, snapshot)`` must re-join the process
        set, rebuild the mesh/programs for it, and re-place the state
        (AutoDist._elastic_reconfigure is the standard handler;
        ``snapshot`` is the in-memory host state, or None when some shard
        had no live local replica — then fall back to the last-good
        checkpoint re-shard)."""
        self._reconfigure_fn = fn

    def adopt_distributed_step(self, dstep):
        """Swap in a rebuilt DistributedStep (post-reconfigure): the
        remapper and staleness metadata follow the new mesh; step/dispatch
        counters and telemetry continue — it is the same logical run."""
        self._dstep = dstep
        self._remapper = Remapper(dstep.mesh, dstep.mesh_axis,
                                  seq_axis=dstep.seq_axis,
                                  batch_axes=dstep.batch_axes,
                                  seq_keys=getattr(dstep, "seq_feed_keys",
                                                   None))
        self._staleness = int(dstep.metadata.get("staleness", 0))
        self._register_programs()  # a map of the old programs is stale

    def _poll_epoch(self):
        """Readback-boundary membership poll (throttled to
        ``ADT_ELASTIC_POLL_S``): a published epoch newer than ours parks a
        pending reconfigure for the next safe point."""
        m = self._membership
        if m is None or self._reconfig_pending is not None:
            return
        now = time.monotonic()
        if now < self._epoch_poll_at:
            return
        self._epoch_poll_at = now + max(0.05,
                                        const.ENV.ADT_ELASTIC_POLL_S.val)
        info = m.peek()
        if info is not None and info[0] > m.epoch:
            self._reconfig_pending = info
            logging.warning(
                "elastic: cluster epoch %d published (we are at %d) — "
                "reconfiguring to %d member(s) at the next boundary",
                info[0], m.epoch, len(info[1]))

    def _maybe_preempt_act(self):
        """Drive a pending preemption notice at a SAFE point (next to the
        sentinel/reconfigure hooks): cluster-agreed rescue checkpoint,
        snapshot pre-staging, and — for a departing worker with no
        membership plane — the graceful exit itself."""
        if self._preempt.pending:
            self._preempt.maybe_act()

    def _prestage_snapshot(self):
        """Pre-stage the in-memory state snapshot for an ANNOUNCED
        membership change (one per boundary step): the leaver is known in
        advance, so the survivors take the flush + snapshot cost here —
        outside the reconfigure span — and the planned handoff's
        recorded downtime carries strictly less work than an unplanned
        shrink's."""
        if (self._prestaged is not None
                and self._prestaged[0] == self._step_count):
            return
        from autodist_tpu.runtime import elastic as elastic_lib
        self._dstep.flush_ps()
        self._prestaged = (self._step_count,
                           elastic_lib.snapshot_runner_state(self))

    def _maybe_reconfigure(self):
        """Execute a pending membership change at a SAFE point (no
        dispatch in flight, metrics all materialized): barrier with the
        other members of the new epoch, snapshot state from live local
        replicas, tear down / re-join the process set via the wired
        handler, and ack. Downtime is the ``elastic.reconfigure`` span."""
        if self._reconfig_pending is None:
            return
        (epoch, roster), self._reconfig_pending = \
            self._reconfig_pending, None
        m = self._membership
        from autodist_tpu.runtime import elastic as elastic_lib
        if m.worker not in roster:
            # UNTHROTTLED notice check: the shrink epoch can outrun the
            # throttled notice poll, and an announced leaver must never
            # take the zombie path
            if self._preempt.check_departure_now():
                # the epoch that excludes us is OUR announced departure:
                # hand off alive (serving drain, state flush, left stamp)
                # and exit gracefully — never the zombie fence-out
                self._preempt.depart(epoch, roster)
            # we were declared dead and survived anyway: a zombie. Every
            # write path is already fenced; this is the loud exit.
            raise elastic_lib.FencedOut("reconfigure", m.epoch, epoch,
                                        m.worker, roster)
        if self._reconfigure_fn is None:
            raise RuntimeError(
                "elastic epoch %d published but no reconfigure handler is "
                "wired on this Runner (AutoDist.build arms it for in-run "
                "elastic jobs)" % epoch)
        t0 = time.perf_counter()
        planned = (self._prestaged is not None
                   and self._prestaged[0] == self._step_count)
        with tel.span("elastic.reconfigure", "elastic", epoch=epoch,
                      world=len(roster), from_world=len(m.roster),
                      step=self._step_count, planned=planned):
            if planned:
                # announced departure: the snapshot was pre-staged at
                # this boundary (outside the span) — the planned path's
                # downtime edge over an unplanned shrink
                snapshot = self._prestaged[1]
            else:
                # land the fused PS carry / in-flight pushes, then
                # snapshot
                self._dstep.flush_ps()
                snapshot = elastic_lib.snapshot_runner_state(self)
            self._prestaged = None
            # superstep-aligned rendezvous of the NEW process set: nobody
            # tears down jax.distributed while a peer is still dispatching
            m.barrier_reconf(epoch, len(roster))
            self._reconfigure_fn(self, epoch, roster, snapshot)
            m.adopt(epoch, roster)
            try:
                m.ack(epoch)
            except OSError:
                logging.warning("elastic: ack for epoch %d failed (the "
                                "chief may escalate)", epoch)
        self._last_reconfigure_s = time.perf_counter() - t0
        self._reconfigs += 1
        tel.counter_add("elastic.reconfigs")
        tel.gauge_set("elastic.epoch", float(epoch))
        from autodist_tpu.telemetry import blackbox
        blackbox.record("elastic.reconfigure", epoch=epoch,
                        world=len(roster),
                        downtime_s=round(self._last_reconfigure_s, 6))
        logging.warning(
            "elastic: reconfigured to epoch %d (%d member(s)) in %.3fs",
            epoch, len(roster), self._last_reconfigure_s)

    def _sentinel_observer(self):
        return self._sentinel.observe if self._sentinel is not None else None

    def sentinel_save_veto(self) -> bool:
        """Consulted by the checkpoint savers: True while the sentinel
        quarantines saves (last verdict bad / rollback pending) — a
        poisoned state must never become the newest committed
        checkpoint."""
        return self._sentinel is not None and self._sentinel.quarantined

    def sentinel_healthy(self) -> bool:
        """The ``healthy`` stamp a checkpoint committed now should carry
        (True when no sentinel is active — an unguarded run has no
        evidence of ill health)."""
        return self._sentinel is None or self._sentinel.healthy()

    @property
    def sentinel(self):
        """The active :class:`~autodist_tpu.runtime.sentinel.Sentinel`
        (None when no policy is armed)."""
        return self._sentinel

    def notify_state_restored(self):
        """Re-sync the PROCESS-LOCAL halves of the sentinel's LR scale
        with the authoritative copy in the (restored or freshly
        initialized) state's sync_state. The scale lives in three
        places — in-graph (``sync_state["sentinel"]["lr_scale"]``, what
        checkpoints persist), ``PSStore.update_scale`` (host applies)
        and ``Sentinel.lr_scale`` (ladder accounting) — and a restore
        replaces only the first; without this hook an auto-resume after
        an escalation would train PS-resident and device-resident vars
        at DIFFERENT effective learning rates. Called by the savers'
        restore paths and by :meth:`init`."""
        scale = 1.0
        sync = getattr(self.state, "sync_state", None)
        if isinstance(sync, dict) and "sentinel" in sync:
            try:
                leaf = sync["sentinel"]["lr_scale"]
                shards = getattr(leaf, "addressable_shards", None)
                if shards:
                    # every shard carries the same scalar; reading a local
                    # shard works even when the global array spans
                    # processes (device_get would refuse it)
                    leaf = shards[0].data
                scale = float(np.asarray(jax.device_get(leaf)).ravel()[0])
            except (KeyError, IndexError, TypeError):
                pass
        store = getattr(self._dstep, "ps_store", None)
        if store is not None:
            store.update_scale = scale
        sen = getattr(self, "_sentinel", None)
        if sen is not None and sen.lr_scale != scale:
            logging.info("sentinel: lr_scale re-synced to %.4g from the "
                         "restored state", scale)
            sen.lr_scale = scale

    def _after_dispatch(self, microsteps: int):
        """Shared post-dispatch control plane: step accounting, liveness
        heartbeat, cross-process staleness pacing and mirror checks — all
        counted in MICROSTEPS, so a fused superstep advances the pacing
        protocol by its true k optimizer applies."""
        self._compile_grace_end()
        step = self._step_count
        self._step_count += microsteps
        self._superstep_count += 1
        tel.counter_add("runner.steps", microsteps)
        tel.counter_add("runner.supersteps")
        with tel.span("runner.control", "runner", step=step):
            self._maybe_fleet_profile_stop()
            self._poll_profile_window()
            self._poll_epoch()
            self._preempt.poll()
            self._maybe_heartbeat()
        if self._coord is not None:
            # bounded staleness across processes (the reference's size-s
            # token-queue semantics, ps_synchronizer.py:388-458): report our
            # step, then block while more than `staleness` ahead of the
            # slowest worker. The wait is a SPAN (collective_wait in the
            # goodput decomposition) with the global step as arg: time
            # parked here is skew caused by a slower peer, and the merged
            # timeline shows exactly which step paid it.
            worker = const.ENV.ADT_WORKER.val or "chief"
            self._coord.report_step(worker, self._step_count)
            self._coord.heartbeat(worker)
            with tel.span("runner.barrier", "runner",
                          step=self._step_count,
                          staleness=self._staleness):
                self._coord.wait_staleness(self._step_count,
                                           self._staleness)
        self._maybe_check_mirrors()

    def _record_step_time(self, t_begin: float, step: int):
        with tel.span("runner.step_time", "runner", step=step):
            elapsed = time.perf_counter() - t_begin
            self._total_step_s += elapsed
            if self._first_step_s is None:
                # includes trace + XLA compile (``_first_step`` puts the
                # setup.first_step span's duration here when tracing)
                self._first_step_s = elapsed
            else:
                self._recent_step_s.append(elapsed)
                if len(self._recent_step_s) > self._RECENT_WINDOW:
                    del self._recent_step_s[:len(self._recent_step_s) // 2]
                self._observe_straggler(elapsed)

    def _observe_straggler(self, elapsed: float):
        """Online slow-but-alive detection: sustained EWMA z-score
        outliers in this worker's dispatch wall time flip the
        ``telemetry.straggler`` gauge, emit an instant, and (multi-
        process) mark ``straggler/<worker>`` on the coordination
        service — the chief's watchdog reads the mark to distinguish a
        degraded-but-progressing worker from a dead one instead of
        recycling it (``Coordinator._is_straggling``)."""
        transition = self._straggler.observe(elapsed)
        if transition is None:
            # REFRESH the slow-but-alive mark while still flagged: the
            # watchdog's freshness window (2x heartbeat timeout) must
            # keep seeing a live mark for as long as the degradation
            # lasts — a single flag-time mark would age out and the
            # watchdog would recycle a worker that is still progressing
            if (self._straggler.flagged
                    and time.monotonic() - self._straggler_mark_at
                    > self._heartbeat_every_s):
                self._write_straggler_mark(repr(time.time()))
            return
        if transition == "flag":
            z = self._straggler.last_z
            tel.gauge_set("telemetry.straggler", round(z, 3))
            tel.counter_add("telemetry.straggler_flags")
            tel.instant("telemetry.straggler", "runner", z=round(z, 3),
                        step=self._step_count,
                        dispatch_s=round(elapsed, 6))
            from autodist_tpu.telemetry import blackbox
            blackbox.record("runner.straggler", z=round(z, 3),
                            step=self._step_count,
                            dispatch_s=round(elapsed, 6))
            logging.warning(
                "straggler: dispatch wall time %.4gs is %.1f sigma over "
                "the EWMA baseline for %d consecutive dispatches — "
                "flagging this worker slow-but-alive",
                elapsed, z, self._straggler.patience)
            self._write_straggler_mark(repr(time.time()))
        else:  # "clear"
            tel.gauge_set("telemetry.straggler", 0.0)
            tel.instant("telemetry.straggler_clear", "runner",
                        step=self._step_count)
            self._write_straggler_mark("0")

    def _write_straggler_mark(self, mark: str):
        self._straggler_mark_at = time.monotonic()
        client = self._async_hb or self._coord
        if client is not None:
            worker = const.ENV.ADT_WORKER.val or "chief"
            try:  # best-effort: the mark is advisory, never worth a stall
                client.put("straggler/%s" % worker, mark)
            except (OSError, RuntimeError):
                pass

    def run(self, batch, state: Optional[TrainState] = None,
            sync: bool = True) -> Any:
        """One training step on a host-global batch. ``sync=True``
        (default) returns host metrics, paying one device→host readback
        per step. ``sync=False`` returns a :class:`MetricsHandle` —
        device-resident, materialized lazily — so the steady-state loop
        never re-enters the host between steps; wall-time samples then
        measure dispatch-to-dispatch, not execution (the next forced
        readback re-syncs the clock)."""
        if self._first_step_s is None:
            return self._first_step(self._run, batch, state, sync)
        return self._run(batch, state, sync)

    def _first_step(self, run, *args):
        """The first dispatch, which traces, lowers and compiles the step
        program (or loads it from the compile cache), up to what its caller
        gets back: ``setup.first_step``, the last phase of the set-up
        account. With tracing on ``first_step_s`` is that span's own
        duration: one pair of clock readings serves both. A first-step
        profile (``tracing=True``) starts before the span and stops after
        it, so holds it."""
        self._start_trace_if_due()
        with tel.span("setup.first_step", tel.SETUP_CAT,
                      step=self._step_count) as span:
            out = run(*args)
        if span.id:
            self._first_step_s = span.dur_ns / 1e9
        self._stop_trace_if_due(out if self.state is None else self.state)
        return out

    def _prologue(self):
        """Before a dispatch, at a SAFE point: what was left pending."""
        with tel.span("runner.prologue", "runner", step=self._step_count):
            self._maybe_sentinel_act()  # a pending rollback replaces state
            self._maybe_preempt_act()   # a pending notice rescues/hands off
            self._maybe_reconfigure()   # a pending epoch re-forms the mesh

    def _run(self, batch, state, sync):
        t_begin = time.perf_counter()
        self._prologue()
        st = state if state is not None else self.state
        if st is None:
            raise RuntimeError("Runner.run before init()")
        self._compile_grace_begin()
        # the global step arg is what makes per-step skew visible on a
        # merged cluster timeline: every worker's dispatch for microstep
        # N carries step=N, so Perfetto (and cluster.step_alignment)
        # lines the tracks up per STEP, not just per run
        step = self._step_count
        with tel.span("runner.dispatch", "runner", microsteps=1, sync=sync,
                      step=step):
            with tel.span("runner.feed", "runner", step=step):
                sharded_batch = self._remapper.remap_feed(batch)
            self._maybe_fleet_profile()
            self._start_trace_if_due()
            self._check_ps_owner_health()
            # donate only the Runner-owned state; an explicitly-passed state
            # is a caller reference that must stay valid
            new_state, metrics = self._dstep(st, sharded_batch,
                                             donate=state is None, step=step)
            if state is None:
                self.state = new_state
            with tel.span("runner.release", "runner", step=step):
                # the last references to the donated state's arrays and to
                # the placed batch go here, not when this frame is torn
                # down after the span: freeing some 400 arrays per chip is
                # host time worth a name (2 ms a step on four chips)
                del st, sharded_batch
            self._after_dispatch(1)
            self._stop_trace_if_due(metrics)
            handle = MetricsHandle(metrics, self._remapper, microsteps=1,
                                   observer=self._sentinel_observer(),
                                   step=step)
            if sync:
                # result() pulls the metrics to host, so the step's device
                # work is complete: this wall time is an honest per-step
                # duration
                host_metrics = handle.result()
                self._record_step_time(t_begin, step)
                return ((new_state, host_metrics) if state is not None
                        else host_metrics)
            self._record_step_time(t_begin, step)
            return (new_state, handle) if state is not None else handle

    def run_superstep(self, stacked_batch, sync: bool = False):
        """One FUSED superstep: k microsteps (k = the stacked feed's
        leading dim) in a single donated jitted dispatch
        (``DistributedStep.multi_step``) — gradient collectives, PS
        updates and optimizer applies all stay on device; metrics come
        back stacked ``[k, ...]`` as a lazily-materialized
        :class:`MetricsHandle` (``sync=True`` forces the readback before
        returning). Heartbeats and staleness pacing advance by the true
        k microsteps."""
        if self._first_step_s is None:
            return self._first_step(self._run_superstep, stacked_batch, sync)
        return self._run_superstep(stacked_batch, sync)

    def _run_superstep(self, stacked_batch, sync):
        t_begin = time.perf_counter()
        self._prologue()
        if self.state is None:
            raise RuntimeError("Runner.run_superstep before init()")
        self._compile_grace_begin()
        step = self._step_count
        with tel.span("runner.feed", "runner", stacked=True, step=step):
            placed = self._remapper.remap_feed_stack(stacked_batch)
        leaves = jax.tree_util.tree_leaves(placed)
        k = self._fused_k = int(np.shape(leaves[0])[0]) if leaves else 1
        with tel.span("runner.dispatch", "runner", microsteps=k, sync=sync,
                      step=step):
            self._maybe_fleet_profile()
            self._start_trace_if_due()
            self._check_ps_owner_health()
            new_state, metrics = self._dstep.run_multi(self.state, placed,
                                                       step=step)
            with tel.span("runner.release", "runner", step=step):
                self.state = new_state  # the donated state's arrays go
                del placed
            self._after_dispatch(k)
            self._stop_trace_if_due(metrics)
            handle = MetricsHandle(metrics, self._remapper, microsteps=k,
                                   observer=self._sentinel_observer(),
                                   step=step)
            if sync:
                handle.result()
            self._record_step_time(t_begin, step)
            return handle.result() if sync else handle

    def lowered_text(self, batch, state: Optional[TrainState] = None,
                     fuse_steps: int = 1, program: str = "train",
                     donate: bool = False) -> str:
        """StableHLO text of the compiled step for ``batch`` — the input
        of the post-lowering lint pass (``analysis/lowered.py``) and the
        static HBM/schedule analyzers (``analysis/hlo.py``,
        ``analysis/memory.py``). Pure lowering: no step runs, host-PS
        values enter as avals. ``program="eval"`` lowers the
        forward-only eval program. With ``fuse_steps=k > 1``, lowers the
        fused k-microstep scan program (the stacked feed is synthesized
        as avals from ``batch``). ``donate=True`` lowers the donated
        variant that actually runs in steady state."""
        st = state if state is not None else self.state
        if st is None:
            raise RuntimeError("Runner.lowered_text before init()")
        placed = self._remapper.remap_feed(batch)
        if fuse_steps > 1 and program == "train":
            stacked = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(
                    (fuse_steps,) + tuple(np.shape(l)), l.dtype), placed)
            return self._dstep.lowered_text(st, stacked,
                                            fuse_steps=fuse_steps,
                                            donate=donate)
        return self._dstep.lowered_text(st, placed, program=program,
                                        donate=donate)

    def lint_lowered(self, batch, state: Optional[TrainState] = None,
                     fuse_steps: int = 1):
        """Run the lowered-program communication checks (ADT405-408) on
        this runner's compiled step; returns the Diagnostic list. With
        ``fuse_steps=k``, lints the fused scan program — ADT408 flags
        per-microstep host transfers inside the scan body."""
        from autodist_tpu.analysis import lowered as lowered_lib
        return lowered_lib.lint_runner(self, batch, state,
                                       fuse_steps=fuse_steps)

    def memory_report(self, batch, state: Optional[TrainState] = None,
                      fuse_steps: int = 1,
                      hbm_budget_bytes: Optional[float] = None,
                      donate: bool = True) -> dict:
        """Static per-device peak-HBM report of the compiled step for
        ``batch`` — buffer sizes from the lowered program's entry
        signature (sharding- and donation-aware) plus a liveness sweep
        for the temporaries, checked against the per-chip HBM budget
        (``ResourceSpec.chip_hbm_bytes()`` via AutoDist, or an explicit
        ``hbm_budget_bytes``). Pure lowering: nothing compiles, nothing
        allocates — OOM surfaces here as an ``ADT501`` diagnostic
        instead of a runtime crash. ``donate=True`` (default) analyzes
        the donated program that actually runs in steady state;
        ``fuse_steps=k`` analyzes the fused superstep program (whose
        un-donated carry is the ``ADT503`` hazard). See
        docs/performance.md for reading the report and sizing budgets.
        This is the lint BEFORE any compile, an estimate;
        ``telemetry.step_account()`` is the compiler's own word on the
        program that runs, read off the compile the scope map pays."""
        from autodist_tpu.analysis import hlo as hlo_lib
        from autodist_tpu.analysis import memory as memory_lib
        text = self.lowered_text(batch, state, fuse_steps=fuse_steps,
                                 donate=donate)
        program = hlo_lib.parse_hlo_text(text)
        est = memory_lib.estimate_from_text(program)
        schedule = hlo_lib.collective_schedule(program)
        budget = (hbm_budget_bytes if hbm_budget_bytes is not None
                  else self._hbm_budget)
        diags = memory_lib.donation_diagnostics(program,
                                                fuse_steps=fuse_steps)
        report = {
            "program": {"fuse_steps": fuse_steps, "donated": donate,
                        "num_partitions": est.num_partitions},
            "estimate": est.to_dict(),
            "peak_hbm_bytes": round(est.peak_hbm_bytes),
            "peak_hbm_gib": round(est.peak_hbm_bytes / memory_lib.GIB, 4),
            "collectives": {
                "count": len(schedule),
                "per_step_count": len(schedule.per_step()),
                "per_class_payload_bytes":
                    schedule.per_step().class_payload_bytes(),
            },
        }
        if budget is not None:
            diags = diags + memory_lib.budget_diagnostics(
                est.peak_hbm_bytes, budget, source="lowered-program")
            report.update(
                budget_bytes=round(budget),
                budget_gib=round(budget / memory_lib.GIB, 4),
                utilization=(round(est.peak_hbm_bytes / budget, 4)
                             if budget else None))
        report["diagnostics"] = diags
        return report

    def collective_schedule(self, batch, state: Optional[TrainState] = None,
                            program: str = "train", fuse_steps: int = 1):
        """The ordered collective schedule (kind, replica groups, payload
        bytes, loop depth) of one of this runner's compiled programs —
        see ``analysis/hlo.py``."""
        from autodist_tpu.analysis import hlo as hlo_lib
        text = self.lowered_text(batch, state, fuse_steps=fuse_steps,
                                 program=program)
        return hlo_lib.collective_schedule(text)

    def static_profile(self, batch, state: Optional[TrainState] = None,
                       fuse_steps: int = 1, topology=None):
        """Measured per-collective wire bytes of the compiled step — a
        ``StaticCollectiveProfile`` to attach to a ``Simulator`` /
        ``CostModel`` (``attach_static_profile``), replacing the jaxpr
        cost heuristics with what the lowering actually emits. Passing
        the resource spec's ``topology`` additionally attributes each
        replica group's ring edges to the link level they cross
        (``level_wire_bytes`` — the drift report's per-level rows)."""
        from autodist_tpu.simulator.cost_model import StaticCollectiveProfile
        schedule = self.collective_schedule(batch, state,
                                            fuse_steps=fuse_steps)
        n_dev = max(int(getattr(self._dstep.mesh, "size", 1)), 1)
        return StaticCollectiveProfile.from_schedule(
            schedule, default_group_size=n_dev, topology=topology)

    def lint_schedules(self, batch, state: Optional[TrainState] = None,
                       fuse_steps: int = 1):
        """Cross-program collective-schedule consistency (ADT510/511):
        the eval program — and, with ``fuse_steps=k > 1``, the fused
        superstep program's per-microstep body — must embed into the
        train step's schedule, or replicas running different programs on
        the same mesh deadlock in mismatched collectives."""
        from autodist_tpu.analysis import hlo as hlo_lib
        train = self.collective_schedule(batch, state)
        diags = list(hlo_lib.compare_schedules(
            train, self.collective_schedule(batch, state, program="eval"),
            "train", "eval"))
        if fuse_steps > 1:
            diags += hlo_lib.compare_schedules(
                train,
                self.collective_schedule(batch, state,
                                         fuse_steps=fuse_steps),
                "train", "fused")
        return diags

    def step_stats(self) -> dict:
        """Wall-time statistics over this runner's steps (the throughput
        companion to the reference's examples/sec hooks,
        ``examples/benchmark/utils/logs/hooks.py:28``): ``first_step_s``
        isolates trace+compile; ``steady_*`` percentiles describe the
        post-compile regime over a recent window; ``goodput`` is the
        fraction of total stepping wall time the job would have needed at
        steady median speed — compile time and host stalls show up as
        lost goodput.

        Fused accounting: wall-time samples are PER DISPATCH, so both
        counts are reported — ``supersteps`` (dispatches: what the timing
        samples and goodput are defined over) and ``microsteps``
        (optimizer applies: what examples/s math must multiply by the
        batch size; ×k under ``fit(fuse_steps=k)``). ``steps`` ==
        ``microsteps`` for backward compatibility (identical without
        fusion). Reading the stats never forces a device sync — under
        ``sync=False`` stepping the samples measure dispatch-to-dispatch
        time, re-synced at every metrics readback boundary.

        The shape is STABLE (a monitoring consumer can rely on every key
        existing): ``steady_*``/``goodput`` are None before any steady
        sample, and ``telemetry`` merges the process-wide registry
        counters (``telemetry/spans.py``) that attribute the wall time —
        jitted dispatches, metric readbacks and their D2H bytes, host-PS
        wire bytes, control-plane retries, prefetcher drops."""
        import statistics
        micro, sup = self._step_count, self._superstep_count
        out = {"steps": micro, "supersteps": sup, "microsteps": micro,
               # which compute tier the step program runs in ("f32" or
               # "bf16") — monitoring needs it to interpret loss jitter
               # and examples/s side by side across precision configs
               "compute_dtype": getattr(
                   getattr(self, "_dstep", None), "metadata",
                   {}).get("compute_dtype", "f32"),
               "total_s": round(self._total_step_s, 6),
               "first_step_s": (round(self._first_step_s, 6)
                                if self._first_step_s is not None else None),
               "steady_median_s": None, "steady_p10_s": None,
               "steady_p90_s": None, "goodput": None}
        recent = self._recent_step_s
        if recent:
            # method="inclusive": the default exclusive method extrapolates
            # past the observed range on small samples (a negative p10
            # after two steps); inclusive keeps percentiles within the data
            qs = (statistics.quantiles(recent, n=10, method="inclusive")
                  if len(recent) >= 2 else [recent[0]] * 9)
            out.update(
                steady_median_s=round(statistics.median(recent), 6),
                steady_p10_s=round(qs[0], 6),
                steady_p90_s=round(qs[-1], 6),
                # goodput is over DISPATCHES: recent samples are
                # per-dispatch durations, so the ideal-time numerator is
                # median x dispatch count, never median x microsteps
                goodput=round(min(1.0, statistics.median(recent) * sup
                              / self._total_step_s), 4)
                if self._total_step_s > 0 else None)
        c = tel.counters()
        out["telemetry"] = {
            "dispatches": c.get("dstep.dispatches", 0.0),
            "readbacks": c.get("runner.readbacks", 0.0),
            "d2h_bytes": c.get("runner.d2h_bytes", 0.0),
            "ps_bytes_pulled": c.get("ps.bytes_pulled", 0.0),
            "ps_bytes_pushed": c.get("ps.bytes_pushed", 0.0),
            "coord_retries": c.get("coord.retries", 0.0),
            "prefetch_dropped_batches": c.get("prefetch.dropped_batches",
                                              0.0),
        }
        # stable sub-dict (same contract as the telemetry merge): every
        # key exists whether or not a sentinel policy is armed (getattr:
        # partially-constructed runners must still report stats)
        sen = getattr(self, "_sentinel", None)
        out["sentinel"] = (sen.stats() if sen is not None else
                           {"skips": 0, "rollbacks": 0,
                            "last_grad_norm": None, "quarantined": False})
        # attributed goodput (telemetry/goodput.py): WHERE the wall time
        # went, not just how much was lost — None with tracing off (the
        # decomposition needs the span tree). Straggler stats are always
        # present (the EWMA runs on wall-time samples, no spans needed).
        straggler = getattr(self, "_straggler", None)
        out["straggler"] = (straggler.stats() if straggler is not None
                            else {"flagged": False, "flags": 0,
                                  "last_z": None, "ewma_s": None})
        report = self.goodput_report()
        out["goodput_breakdown"] = (
            {k: round(v, 6) for k, v in report.buckets.items()}
            if report is not None else None)
        # elastic plane (stable shape): epoch/reconfigure accounting for
        # monitoring
        m = getattr(self, "_membership", None)
        out["elastic"] = {
            "epoch": m.epoch if m is not None else None,
            "reconfigs": getattr(self, "_reconfigs", 0),
            "last_reconfigure_s": (
                round(self._last_reconfigure_s, 6)
                if getattr(self, "_last_reconfigure_s", None) is not None
                else None),
            "fenced_writes": c.get("elastic.fenced_writes", 0.0),
        }
        # preemption plane (stable shape): notice/rescue/handoff
        # accounting for monitoring
        guard = getattr(self, "_preempt", None)
        out["preempt"] = (guard.stats() if guard is not None else
                          {"notice": None, "notices": 0.0,
                           "rescue_saves": 0.0, "rescue_skips": 0.0,
                           "handoffs": 0.0, "last_handoff_s": None})
        return out

    def goodput_report(self):
        """The attributed wall-time decomposition of this process's
        training thread (:class:`telemetry.goodput.GoodputReport`):
        compute / collective-wait / PS-wire / host-input / readback /
        checkpoint / rollback-replay buckets that sum to the recorded
        wall time by construction. None when tracing is off (the
        decomposition needs the span tree); under ``ADT_TRACE=sampled``
        (or after ring-buffer drops) the report is flagged
        ``approximate`` — bucket *proportions* hold, absolute seconds
        scale with the stride."""
        if not tel.tracing_enabled():
            return None
        from autodist_tpu.telemetry import goodput as goodput_lib
        report = goodput_lib.build_report()
        if report.wall_s <= 0:
            return None
        return report

    def _check_ps_owner_health(self):
        """Fail LOUDLY when an async-PS owner apply loop of this process
        is dead (transport budget exhausted / thread crashed). Before
        this check the failure mode was a silent stall: the daemon thread
        died, queues backed up, and training "ran" forever applying
        nothing. Checked every step — it is two attribute reads when
        healthy."""
        store = getattr(self._dstep, "ps_store", None)
        if store is None or not getattr(store, "serving", False):
            return
        bad = store.owner_health_errors()
        if bad:
            raise RuntimeError(
                "async PS owner apply loop(s) dead — training cannot "
                "apply gradients: %s"
                % "; ".join("%s: %s" % (h, e) for h, e in bad))

    def _maybe_heartbeat(self):
        """Time-based liveness beat for async multi-process jobs. A failed
        beat RECONNECTS at the next due time instead of latching off: a
        worker that silently stopped heartbeating would age into a false
        death at the chief's watchdog — the one thing this beat exists to
        prevent.

        Deliberately STEP-DRIVEN, not a background thread: the beat means
        "this worker made training progress recently", which is the signal
        a deadlock detector needs — a daemon thread would keep beating
        while the main thread is wedged in a lock or syscall, masking
        exactly the hang being watched for. The flip side: legitimate
        non-stepping phases (long evals, slow data) read as silence, so
        ``ADT_HEARTBEAT_TIMEOUT_S`` must exceed the job's worst honest
        inter-step gap."""
        if not self._hb_enabled:
            return
        now = time.monotonic()
        if now - self._last_hb <= self._heartbeat_every_s:
            return
        if self._async_hb is None:
            self._async_hb = self._connect_coordination(
                "async liveness heartbeats (reconnect)")
            if self._async_hb is None:
                return  # retry at the next due beat
        try:
            self._async_hb.heartbeat(const.ENV.ADT_WORKER.val or "chief")
            self._last_hb = now
        except OSError as e:
            logging.warning("async heartbeat failed (%s); reconnecting at "
                            "the next beat", e)
            try:
                self._async_hb.close()
            except OSError:
                pass
            self._async_hb = None

    def _maybe_check_mirrors(self):
        """Sync multi-process PS keeps every process's host mirror
        bit-identical by determinism, not by serving; every
        ``ADT_PS_MIRROR_CHECK_EVERY`` steps compare an md5 digest of the
        mirrors across processes via the coordination service and fail
        fast on divergence (heterogeneous host XLA codegen would
        otherwise silently fork the replicas)."""
        every = const.ENV.ADT_PS_MIRROR_CHECK_EVERY.val
        store = getattr(self._dstep, "ps_store", None)
        if (every <= 0 or store is None or store.serving
                or const.ENV.ADT_NUM_PROCESSES.val < 2
                or self._step_count % every != 0
                or self._mirror_coord is False):  # disabled after a timeout
            return
        # a DEDICATED client: self._coord doubles as the "staleness pacing
        # on" flag in run(), which must stay off unless staleness > 0
        if self._mirror_coord is None:
            self._mirror_coord = self._connect_coordination("mirror check")
            if self._mirror_coord is None:
                self._mirror_coord = False
                return
        # the pipelined push for this step must land before the digest, or
        # processes would hash different apply versions (false divergence)
        self._dstep.flush_ps()
        digest = store.mirror_digest()
        worker = const.ENV.ADT_WORKER.val or "chief"
        # keys are scoped by strategy id (unique per run — a long-lived
        # service may retain a previous run's digests) with ONE key per
        # worker, overwritten each check (bounded KV growth); all
        # processes check at the same step multiples, and sync PS steps
        # are collective-lockstep, so the steps line up
        prefix = "mirror/%s" % getattr(self._dstep.strategy, "id", "run")
        self._mirror_coord.put("%s/%s" % (prefix, worker),
                               "%d:%s" % (self._step_count, digest))
        if worker == "chief":
            return  # workers compare against the chief's copy
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            val = self._mirror_coord.get("%s/chief" % prefix)
            if val is not None:
                chief_step, chief_digest = val.split(":", 1)
                if int(chief_step) >= self._step_count:
                    if (int(chief_step) == self._step_count
                            and chief_digest != digest):
                        raise RuntimeError(
                            "PS mirror divergence at step %d: %s has %s, "
                            "chief has %s" % (self._step_count, worker,
                                              digest, chief_digest))
                    return  # matched, or chief raced past — next check aligns
            time.sleep(0.01)
        # never saw a chief digest for this step: warn once and stop
        # checking rather than stalling 30s at every future check step
        logging.warning("mirror check: chief digest for step %d never "
                        "appeared; disabling further checks",
                        self._step_count)
        self._mirror_coord.close()
        self._mirror_coord = False

    def close(self):
        """Release everything the runner opened: coordination-service
        clients (pacing + liveness + mirror check, with a clean GOODBYE
        deregister so a finished worker is never counted dead) and the
        host-PS store's serving threads/sockets. Idempotent."""
        worker = const.ENV.ADT_WORKER.val or "chief"
        self._hb_enabled = False
        guard = getattr(self, "_preempt", None)
        if guard is not None:
            guard.close()
        if getattr(self, "_atexit_cb", None) is not None:
            import atexit
            try:
                atexit.unregister(self._atexit_cb)
            except Exception:  # noqa: BLE001 — unregister is best-effort
                pass
            self._atexit_cb = None
        for attr, say_goodbye in (("_coord", True), ("_async_hb", True),
                                  ("_mirror_coord", False)):
            client = getattr(self, attr, None)
            if client not in (None, False):
                try:
                    if say_goodbye:
                        client.goodbye(worker)
                except OSError:
                    pass
                finally:  # a failed goodbye must not leak the socket
                    try:
                        client.close()
                    except OSError:
                        pass
            setattr(self, attr, None)
        store = getattr(self._dstep, "ps_store", None)
        if store is not None:
            # land the in-flight pipelined push and stop its executor
            # threads BEFORE tearing the store down — a background push
            # against a closed store would fail into a never-awaited
            # Future, silently losing the last step's gradient
            try:
                self._dstep.close_ps()
            except Exception as e:  # noqa: BLE001 — close stays idempotent
                logging.warning("PS pipeline close failed: %s", e)
            store.close()

    def gather_params(self):
        return self._dstep.gather_params(self.state)

    # --------------------------------------------------- fit/evaluate facade

    def fit(self, batches, steps: Optional[int] = None,
            callbacks: Optional[list] = None, save_every: int = 0,
            saver=None, fuse_steps: int = 1, metrics_every: int = 1) -> list:
        """Train over an iterable of host batches (the reference's Keras
        ``model.fit`` path, which its patch routed into the distributed
        session — reference ``patch.py:96-197``). ``steps`` bounds infinite
        iterables (e.g. RecordFileDataset) without consuming a batch past
        the bound; ``callbacks`` are called as ``cb(step_index, metrics)``
        after every step. ``save_every=N`` checkpoints every N steps (and
        once at the end) through ``saver`` — default an async
        :class:`~autodist_tpu.checkpoint.saver.Saver` on ``ADT_CKPT_DIR``,
        which is exactly what sync-elastic recovery resumes from. Returns
        per-step metrics.

        ``fuse_steps=k > 1`` drives the FUSED engine: k consecutive
        batches are stacked into one ``[k, ...]`` feed (or taken
        pre-stacked from a ``DevicePrefetcher(..., stack=k)``) and run as
        one donated jitted superstep — no host re-entry between the k
        optimizer applies. ``metrics_every=n`` pays the device→host
        metrics readback only every n supersteps; between boundaries
        ZERO device→host copies happen. History entries stay
        per-microstep (one dict per batch), so examples/s math and parity
        with the per-step loop are unchanged; callbacks also fire
        per-microstep but only AT readback boundaries (their values are
        exact, their timing is deferred — a monitor that must run every
        step needs ``fuse_steps=1, metrics_every=1``). ``save_every``
        rounds UP to the next superstep boundary (a checkpoint cannot
        split a fused program). When ``fit`` does the stacking (a plain
        host-batch iterable), a trailing group smaller than k falls back
        to per-step execution, so any batch count is trained exactly;
        PRE-stacked sources cannot be split — ``DevicePrefetcher(stack=k)``
        drops a short tail (with a warning) and a ``steps`` bound that is
        not a multiple of k stops at the last whole superstep."""
        # one long span bracketing the whole fit window: the per-dispatch
        # spans nest inside it, so a trace shows the training phase as a
        # single labeled interval with its knobs as args
        with tel.span("runner.fit", "runner", fuse_steps=fuse_steps,
                      metrics_every=metrics_every, save_every=save_every):
            return self._fit(batches, steps, callbacks, save_every, saver,
                             fuse_steps, metrics_every)

    def _fit(self, batches, steps, callbacks, save_every, saver,
             fuse_steps, metrics_every) -> list:
        # the body of fit() — the public contract lives on fit's
        # docstring; split out only so the whole window runs inside one
        # "runner.fit" span
        src_k = getattr(batches, "stack_k", 1)
        if src_k != 1 and src_k != max(1, fuse_steps):
            # a stacked source feeding the wrong k would not fail loudly:
            # remap would split the [k] scan dim over replicas (or re-stack
            # an already-stacked feed) and broadcast-tolerant models would
            # silently train on mis-shaped data
            raise ValueError(
                "fit(fuse_steps=%d) fed a source pre-stacked with stack=%d"
                " — the stacks must match (DevicePrefetcher(stack=k) pairs"
                " with fit(fuse_steps=k))" % (fuse_steps, src_k))
        if save_every > 0 and saver is None:
            from autodist_tpu.checkpoint.saver import Saver
            saver = Saver(directory=const.ENV.ADT_CKPT_DIR.val,
                          async_save=True)
        if self._sentinel is not None and saver is not None:
            # rollback restores from where fit checkpoints
            self._sentinel.attach_saver(saver)
        if saver is not None:
            # the rescue checkpoint commits where fit checkpoints too
            self._preempt.attach_saver(saver)
        if fuse_steps > 1 or metrics_every > 1:
            return self._fit_pipelined(batches, steps, callbacks, save_every,
                                       saver, max(1, fuse_steps),
                                       max(1, metrics_every))
        history = []
        it = iter(batches if steps is None
                  else itertools.islice(batches, steps))
        try:
            for i in itertools.count():
                # the loop's own host work under names of its own: the
                # source's next() (a DevicePrefetcher places the
                # replacement batch inside it) and the callbacks
                with tel.span("runner.next_batch", "runner",
                              step=self._step_count):
                    batch = next(it, _NO_BATCH)
                if batch is _NO_BATCH:
                    break
                metrics = self.run(batch)
                with tel.span("runner.callbacks", "runner",
                              step=self._step_count - 1):
                    history.append(metrics)
                    for cb in (callbacks or ()):
                        cb(i, metrics)
                if save_every > 0 and (i + 1) % save_every == 0:
                    saver.save(self)
            # the LAST step's verdict may have pended a rollback; act
            # before the trailing save so a hard-fail surfaces from fit
            self._maybe_sentinel_act()
            if save_every > 0 and history and len(history) % save_every != 0:
                saver.save(self)  # final partial window
        finally:
            # even on an exception path, a failed async checkpoint write
            # must surface — never look like a success
            if saver is not None:
                saver.wait()
        return history

    def _fit_pipelined(self, batches, steps, callbacks, save_every, saver,
                       k: int, metrics_every: int) -> list:
        """The fused / async steady-state driver behind
        ``fit(fuse_steps=k, metrics_every=n)``: supersteps dispatch with
        ``sync=False`` and their :class:`MetricsHandle`\\ s accumulate
        device-side; one readback per n supersteps (and one at the end)
        materializes them into the per-microstep history."""
        history: list = []
        pending: list = []  # un-materialized MetricsHandles, in step order

        def materialize():
            # pop each handle BEFORE firing its callbacks: a callback that
            # raises must not leave the handle queued, or the finally-path
            # materialize would re-run its side effects (double
            # checkpoint/log writes) on the way out
            while pending:
                handle = pending.pop(0)
                with tel.span("runner.unstack", "runner", step=handle.step):
                    per_step = handle.unstack()
                with tel.span("runner.callbacks", "runner",
                              step=handle.step):
                    for m in per_step:
                        idx = len(history)
                        history.append(m)
                        for cb in (callbacks or ()):
                            cb(idx, m)

        # a DevicePrefetcher in matching stack mode yields pre-stacked,
        # pre-placed [k, ...] feeds — consume them whole; any other source
        # yields plain batches that are grouped and stacked here
        pre_stacked = k > 1 and getattr(batches, "stack_k", 1) == k
        it = iter(batches)

        def next_batch():
            # raises StopIteration through the span, like next(it)
            with tel.span("runner.next_batch", "runner",
                          step=self._step_count):
                return next(it)
        micro_done, last_save, supersteps = 0, 0, 0
        try:
            while steps is None or micro_done < steps:
                if pre_stacked:
                    if steps is not None and micro_done + k > steps:
                        logging.warning(
                            "fit: steps=%d is not a multiple of "
                            "fuse_steps=%d on a pre-stacked source; "
                            "stopping at %d microsteps", steps, k, micro_done)
                        break
                    try:
                        stacked = next_batch()
                    except StopIteration:
                        break
                    handles = [self.run_superstep(stacked, sync=False)]
                else:
                    group = []
                    while len(group) < k and (steps is None
                                              or micro_done + len(group)
                                              < steps):
                        try:
                            group.append(next_batch())
                        except StopIteration:
                            break
                    if not group:
                        break
                    if len(group) == k and k > 1:
                        from autodist_tpu.data.prefetch import stack_batches
                        handles = [self.run_superstep(stack_batches(group),
                                                      sync=False)]
                    else:
                        # trailing partial group: per-step, still async
                        handles = [self.run(b, sync=False) for b in group]
                pending.extend(handles)
                micro_done += sum(h.microsteps for h in handles)
                supersteps += 1
                if supersteps % metrics_every == 0:
                    materialize()
                    self._maybe_sentinel_act()
                if save_every > 0 and micro_done - last_save >= save_every:
                    # superstep-boundary rounding: the save covers every
                    # microstep dispatched so far (saver reads through
                    # flush_ps, which lands the fused PS carry)
                    saver.save(self)
                    last_save = micro_done
            materialize()
            self._maybe_sentinel_act()
            if save_every > 0 and micro_done > last_save:
                saver.save(self)  # final partial window
        finally:
            # NO materialize here: on an exception path the history is
            # lost with the raise, and firing user callbacks after one of
            # them (or the step) aborted would run side effects the
            # caller believes cancelled. Un-materialized handles just
            # drop their device buffers.
            del pending[:]
            # land the fused PS carry: after fit() returns, the host store
            # is authoritative again for checkpoints/eval/inspection
            self._dstep.flush_ps()
            if saver is not None:
                saver.wait()
        return history

    def evaluate(self, batches, steps: Optional[int] = None) -> dict:
        """Example-weighted mean of the SCALAR metrics over an iterable of
        host batches, without updating parameters (the reference's
        ``model.evaluate``). Runs the forward-only compiled program — no
        grads, no optimizer, no gradient collectives. Each batch's scalars
        are weighted by its example count (the leading dim of its first
        array leaf), so a ragged final batch contributes proportionally
        instead of skewing a mean-of-means; batches with no array leaves
        weight 1. Non-scalar metrics are skipped (warned once); aggregate
        those from per-step ``run`` output instead."""
        import numpy as np
        if self.state is None:
            raise RuntimeError("Runner.evaluate before init()")
        totals, weight, skipped = {}, 0.0, set()
        # ONE host-PS pull for the whole eval loop: no pushes happen
        # between eval batches, so the values cannot change — a consistent
        # snapshot, and per-batch re-pulls would be pure PCIe waste.
        # pull_ps is the public snapshot API; it also lands a dirty fused
        # superstep carry first, so eval-mid-fit sees every microstep.
        ps_vals = self._dstep.pull_ps()
        bounded = batches if steps is None else itertools.islice(batches, steps)
        for batch in bounded:
            n = self._batch_examples(batch)
            sharded = self._remapper.remap_feed(batch)
            metrics = self._dstep.evaluate(self.state, sharded,
                                           ps_vals=ps_vals)
            host = self._remapper.remap_fetch(metrics)
            for k, v in host.items():
                if np.ndim(v) == 0:
                    totals[k] = totals.get(k, 0.0) + float(v) * n
                elif k not in skipped:
                    skipped.add(k)
                    logging.warning("evaluate: skipping non-scalar metric "
                                    "%r (shape %s)", k, np.shape(v))
            weight += n
        if weight == 0.0:
            return {}
        return {k: v / weight for k, v in totals.items()}

    @staticmethod
    def _batch_examples(batch) -> int:
        """Leading-dim example count of one batch (1 if no array leaf —
        a weightless batch still counts once in the mean)."""
        for leaf in jax.tree_util.tree_leaves(batch):
            shape = np.shape(leaf)
            if len(shape) >= 1:
                return int(shape[0])
        return 1

    def predict(self, batch, serve_fn, ps_vals=None) -> dict:
        """One-shot forward-only inference on a host batch: run the
        compiled fetch program (``DistributedStep.predict_program``) and
        return ``serve_fn(params, batch)``'s outputs on host, under the
        user's original names (via the Remapper — sharded per-example
        outputs reassemble into the global batch order).

        This is the ad-hoc single call; sustained traffic wants the
        serving engine (``autodist_tpu/serving/``), which adds bucketed
        batch shapes (zero steady-state recompiles), request
        micro-batching, per-request latency accounting, and graceful
        degradation. ``ps_vals`` lets a caller loop reuse one host-PS
        snapshot across calls (as :meth:`evaluate` does); the program
        runs un-donated here because the caller may hold references to
        the placed batch."""
        if self.state is None:
            raise RuntimeError("Runner.predict before init()")
        program = self._dstep.predict_program(serve_fn, donate_batch=False,
                                              example_batch=batch)
        if ps_vals is None:
            ps_vals = self._dstep.pull_ps()
        sharded = self._remapper.remap_feed(batch)
        return self._remapper.remap_fetch(
            program(self.state, ps_vals, sharded))


class WrappedSession:
    """Thin session facade over Runner for reference-style ergonomics
    (``session.run(feed)`` loops)."""

    def __init__(self, runner: Runner):
        self._runner = runner

    def run(self, feed_dict=None, **kwargs):
        batch = feed_dict if feed_dict is not None else kwargs
        return self._runner.run(batch)

    def fit(self, batches, steps=None, callbacks=None, save_every=0,
            saver=None, fuse_steps=1, metrics_every=1):
        return self._runner.fit(batches, steps=steps, callbacks=callbacks,
                                save_every=save_every, saver=saver,
                                fuse_steps=fuse_steps,
                                metrics_every=metrics_every)

    def evaluate(self, batches, steps=None):
        return self._runner.evaluate(batches, steps=steps)

    def predict(self, feed_dict, serve_fn, ps_vals=None):
        """Forward-only fetches for one fed batch (``Runner.predict``)."""
        return self._runner.predict(feed_dict, serve_fn, ps_vals=ps_vals)

    @property
    def state(self):
        return self._runner.state

    def gather_params(self):
        return self._runner.gather_params()
