"""InferenceEngine — bucketed forward-only execution of one strategy.

The engine owns the serving-side compiled programs of a built Runner:

- **one forward program per padded batch-bucket size** (e.g. {1, 8, 32,
  128}), derived from the same gather-params + fill-PS-holes path
  ``Runner.evaluate`` runs (``DistributedStep.predict_program``) with the
  batch buffers donated — after :meth:`warmup` every request executes a
  cached XLA executable, ZERO recompiles in steady state (asserted by
  :meth:`recompiles_after_warmup` in ``tests/test_serving.py``);
- **a host-PS snapshot** shared across requests: values are pulled once
  and refreshed at most every ``snapshot_max_age_s`` — a high-QPS tier
  must not pay one PCIe pull per request for values that change at
  training cadence;
- **graceful degradation** wired into the PR 1 resilience plane: when
  the snapshot refresh fails (coordination-service blip, circuit breaker
  open, async-PS owner unreachable), the engine keeps serving the LAST
  good snapshot for up to ``degraded_batches`` consecutive batches —
  the same staleness-window contract the training-side degraded pull
  honors — counting each one (``serve.degraded``); past the window it
  raises the typed :class:`ServingUnavailable` so callers shed load in
  bounded time instead of hanging on a dead control plane.

Requests are SINGLE EXAMPLES: pytrees shaped like one row of the
training batch (no leading batch dim), usually without the label leaves.
``stack_batches(..., pad_to=bucket)`` stacks a group into the bucket's
``[bucket, ...]`` feed; rows past the real request count are repeats of
the last example and are masked out of the fetches before fan-out.
"""
import dataclasses
import threading
import time
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from autodist_tpu import const
from autodist_tpu.data.prefetch import stack_batches
from autodist_tpu.telemetry import scopes
from autodist_tpu.telemetry import spans as tel
from autodist_tpu.utils import logging


class ServingUnavailable(RuntimeError):
    """Typed load-shed: the serving tier cannot answer right now —
    queue overflow, a PS snapshot staler than the strategy's window
    with the control plane still unreachable, or a drain for a planned
    departure. Callers retry/hedge elsewhere; nothing hangs.

    ``retry_after_s`` (when set) is the shed's Retry-After: how long the
    caller should wait — or route elsewhere — before retrying; a
    draining replica sets it from ``ADT_DRAIN_RETRY_AFTER_S`` so load
    balancers back off instead of hammering the leaver."""

    def __init__(self, *args, retry_after_s=None):
        super().__init__(*args)
        self.retry_after_s = retry_after_s


@dataclasses.dataclass
class ServingConfig:
    """Engine + batcher knobs (docs/serving.md has sizing guidance).

    ``buckets``: padded batch sizes, each a multiple of the mesh's batch
    replica count (None = {1, 8, 32, 128} rounded up to multiples).
    ``max_delay_ms``: the batching deadline — how long the first request
    of a group may wait for company (the latency the batcher TRADES for
    throughput). ``max_queue``: backpressure bound; submits past it shed.
    ``snapshot_max_age_s``: host-PS snapshot refresh period.
    ``degraded_batches``: consecutive batches that may serve the last
    good snapshot while refresh fails (None = max(strategy staleness,
    ``ADT_PS_MAX_LAG``, 1)).

    Brownout (overload-graceful degradation, docs/serving.md): when the
    queue sits above ``brownout_queue_frac * max_queue`` for
    ``brownout_sustain_s``, the batcher widens the group deadline by
    ``brownout_delay_factor`` so dispatches run at full buckets —
    maximum throughput at bounded p99 instead of shedding earlier than
    necessary. ``brownout_delay_factor=1.0`` disables the mode."""

    buckets: Optional[Sequence[int]] = None
    max_delay_ms: float = 2.0
    max_queue: int = 1024
    snapshot_max_age_s: float = 0.1
    degraded_batches: Optional[int] = None
    brownout_queue_frac: float = 0.75
    brownout_sustain_s: float = 1.0
    brownout_delay_factor: float = 4.0

    def __post_init__(self):
        if self.max_delay_ms < 0:
            raise ValueError("max_delay_ms must be >= 0")
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if (self.degraded_batches is not None
                and self.degraded_batches < 0):
            raise ValueError("degraded_batches must be >= 0")
        if not 0.0 < self.brownout_queue_frac <= 1.0:
            raise ValueError("brownout_queue_frac must be in (0, 1]")
        if self.brownout_sustain_s < 0:
            raise ValueError("brownout_sustain_s must be >= 0")
        if self.brownout_delay_factor < 1.0:
            raise ValueError("brownout_delay_factor must be >= 1.0 "
                             "(1.0 disables brownout)")


DEFAULT_BUCKETS = (1, 8, 32, 128)


class InferenceEngine:
    """Bucketed forward-only inference over a built (initialized) Runner.

    ``serve_fn(full_params, batch) -> fetches`` defines the fetch set —
    per-example outputs under the user's own names (e.g. ``{"score":
    apply_fn(p, b["user"], b["item"])}``); the Remapper returns them on
    host in global batch order. ``example_request`` is ONE example
    (leaves without the batch dim) fixing the feed structure — usually
    the training batch minus labels."""

    def __init__(self, runner, serve_fn: Callable, example_request,
                 config: Optional[ServingConfig] = None):
        self._runner = runner
        self._dstep = runner.distributed_step
        self._serve_fn = serve_fn
        self._example_request = example_request
        self.config = config or ServingConfig()
        replicas = runner.remapper.num_replicas
        self.buckets = self._resolve_buckets(self.config.buckets, replicas)
        # ONE jitted program; XLA specializes per bucket shape under it.
        # The example feed passed here fixes the feed STRUCTURE; warmup
        # fixes the shapes. Built at the LARGEST bucket: the lowering
        # classifies output leaves as per-example by their local-batch
        # leading dim, and a big bucket makes that dim distinctive — at
        # the smallest bucket local rows can degenerate to 1 and a
        # replicated (1, ...) output would be mistaken for batch rows.
        self._program = self._dstep.predict_program(
            serve_fn, donate_batch=True,
            example_batch=stack_batches([example_request],
                                        pad_to=self.buckets[-1]))
        # PS snapshot + degradation state (guarded: run_batch may be
        # called from a batcher thread while predict() runs inline)
        self._lock = threading.Lock()
        self._ps_vals = None
        self._snap_t = 0.0
        self._degraded_used = 0
        self.stats = {"batches": 0, "padded_rows": 0, "degraded": 0,
                      "snapshot_refreshes": 0}
        self._warmed = False
        self._cache_size_after_warmup = None
        # inspectable by module name (telemetry.scope_map), on demand
        scopes.register_program("jit_" + self._program.fn.__name__,
                                self._lower_largest_bucket)

    def _lower_largest_bucket(self):
        """The forward program at its largest bucket (one module name
        covers every bucket; the others differ in shapes only)."""
        ps_avals, _ = self._dstep._ps_avals()
        feed = stack_batches([self._example_request],
                             pad_to=self.buckets[-1])
        return self._program.fn.lower(
            self._runner._state_avals(), ps_avals,
            self._runner.remapper.feed_avals(feed))

    @staticmethod
    def _resolve_buckets(buckets, replicas: int) -> Tuple[int, ...]:
        if buckets is None:
            # round the defaults up to replica multiples (batch dims must
            # split evenly over the mesh's batch axes) and dedup
            buckets = sorted({max(-(-b // replicas), 1) * replicas
                              for b in DEFAULT_BUCKETS})
        buckets = tuple(sorted(int(b) for b in buckets))
        if not buckets or buckets[0] < 1:
            raise ValueError("buckets must be positive, got %r"
                             % (buckets,))
        if len(set(buckets)) != len(buckets):
            raise ValueError("duplicate buckets: %r" % (buckets,))
        bad = [b for b in buckets if b % replicas]
        if bad:
            raise ValueError(
                "bucket sizes %s are not multiples of the %d batch "
                "replicas — padded bucket batches must split evenly "
                "over the mesh" % (bad, replicas))
        return buckets

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest bucket holding ``n`` requests."""
        if n < 1:
            raise ValueError("empty request group")
        for b in self.buckets:
            if n <= b:
                return b
        raise ServingUnavailable(
            "request group of %d exceeds the largest bucket %d — the "
            "micro-batcher caps groups at max(buckets)" % (n, self.buckets[-1]))

    # ------------------------------------------------------------ snapshot

    @property
    def _degraded_bound(self) -> int:
        if self.config.degraded_batches is not None:
            return self.config.degraded_batches
        store = getattr(self._dstep, "ps_store", None)
        staleness = store.max_staleness() if store is not None else 0
        return max(staleness, const.ENV.ADT_PS_MAX_LAG.val, 1)

    def _snapshot(self):
        """The host-PS values feed of the next dispatch: a placed device
        snapshot, refreshed at most every ``snapshot_max_age_s``. Refresh
        failures degrade to the last good snapshot within the window,
        then shed with :class:`ServingUnavailable` — the engine object
        stays alive and retries the refresh on the next batch."""
        if getattr(self._dstep, "ps_store", None) is None:
            return {}
        now = time.monotonic()
        if (self._ps_vals is not None
                and now - self._snap_t < self.config.snapshot_max_age_s):
            return self._ps_vals
        try:
            vals = self._dstep.pull_ps()
        except (OSError, RuntimeError, TimeoutError) as e:
            # CoordinationUnavailable / CircuitOpenError are OSErrors; the
            # store's exhausted degraded-serve window raises RuntimeError;
            # an owner that never published raises TimeoutError
            if (self._ps_vals is not None
                    and self._degraded_used < self._degraded_bound):
                self._degraded_used += 1
                self.stats["degraded"] += 1
                tel.counter_add("serve.degraded")
                tel.instant("serve.degraded_snapshot", "serve",
                            used=self._degraded_used,
                            bound=self._degraded_bound)
                logging.warning(
                    "serving: PS snapshot refresh failed (%s); serving "
                    "last snapshot (degraded batch %d/%d)", e,
                    self._degraded_used, self._degraded_bound)
                return self._ps_vals
            raise ServingUnavailable(
                "PS snapshot refresh failed and the degraded window "
                "(%d batches) is exhausted: %s"
                % (self._degraded_bound, e)) from e
        self._ps_vals = vals
        self._snap_t = now
        self._degraded_used = 0
        self.stats["snapshot_refreshes"] += 1
        return vals

    # ------------------------------------------------------------- execute

    def warmup(self):
        """Compile every bucket once (one dispatch each, on repeats of
        the example request). After warmup, steady-state serving is
        recompile-free — :meth:`recompiles_after_warmup` proves it."""
        for b in self.buckets:
            with tel.span("serve.warmup", "serve", bucket=b):
                self.run_batch([self._example_request] * b)
        self._warmed = True
        self._cache_size_after_warmup = self._program._cache_size()
        tel.counter_add("serve.compiles", self._cache_size_after_warmup)
        return self

    def recompiles_after_warmup(self) -> int:
        """Compiled-specialization count growth since :meth:`warmup` —
        the zero-recompile serving contract (0 in steady state; 0 before
        any warmup, when there is no baseline to grow from)."""
        if self._cache_size_after_warmup is None:
            return 0
        return max(0, self._program._cache_size()
                   - self._cache_size_after_warmup)

    def run_batch(self, requests) -> Tuple[dict, int]:
        """Execute one request group: pad to the nearest bucket, dispatch
        the bucket's compiled program, read fetches back, mask the padded
        rows. Returns ``(host_fetches, n)`` with every leading-dim leaf
        sliced to the ``n`` real requests (global batch order)."""
        n = len(requests)
        bucket = self.bucket_for(n)
        host = stack_batches(list(requests), pad_to=bucket)
        with self._lock:
            # stats read-modify-writes stay under the engine lock: run_batch
            # may race predict() from another thread, and a dropped += would
            # silently underreport batches/padded_rows in stats()
            if bucket > n:
                self.stats["padded_rows"] += bucket - n
                tel.counter_add("serve.padded_rows", bucket - n)
            state = self._runner.state
            if state is None:
                raise RuntimeError("InferenceEngine over an uninitialized "
                                   "Runner — call runner.init() first")
            t0 = time.perf_counter()
            with tel.span("serve.dispatch", "serve", n=n, bucket=bucket):
                ps_vals = self._snapshot()
                placed = self._runner.remapper.remap_feed(host)
                device_out = self._program(state, ps_vals, placed)
            t1 = time.perf_counter()
            with tel.span("serve.readback", "serve", n=n, bucket=bucket):
                fetched = self._runner.remapper.remap_fetch(device_out)
            # per-request goodput buckets: the serving analog of the
            # training decomposition — dispatch (program + snapshot +
            # placement) vs readback (D2H) latency distributions, the
            # third bucket (queue wait) observed by the micro-batcher
            tel.hist_observe("serve.dispatch_ms",
                             (t1 - t0) * 1e3)
            tel.hist_observe("serve.readback_ms",
                             (time.perf_counter() - t1) * 1e3)
            self.stats["batches"] += 1
        tel.counter_add("serve.batches")
        import jax
        # slice by the lowering's own per-leaf classification, not by
        # shape: a replicated fetch whose leading dim equals the bucket
        # size must come back whole
        masked = jax.tree_util.tree_map(
            lambda is_batch, a: (np.asarray(a)[:n] if is_batch else a),
            self._program.batch_mask, fetched)
        return masked, n

    def predict(self, requests) -> list:
        """Convenience: run a request list through one padded batch and
        return one fetch tree PER REQUEST (row i of every batch-dim
        leaf)."""
        fetched, n = self.run_batch(requests)
        return self.fan_out(fetched, n)

    def fan_out(self, fetched, n: int) -> list:
        """Split one masked fetch tree into ``n`` per-request trees (row
        ``i`` of every batch-dim leaf, replicated leaves shared)."""
        import jax
        return [jax.tree_util.tree_map(
            lambda is_batch, a, _i=i: (np.asarray(a)[_i] if is_batch
                                       else a),
            self._program.batch_mask, fetched)
            for i in range(n)]
