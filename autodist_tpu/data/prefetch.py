"""Device prefetch: overlap host->device transfer with device compute.

The piece of the reference's input pipeline that actually buys steps/s on
TPU: while step N computes, batch N+1 (and N+2, ...) is already being
placed on the mesh. JAX transfers are async, so the prefetcher simply runs
the Remapper's sharded placement ``depth`` batches ahead of consumption —
a transfer queue, no threads needed; the native loader's worker threads
(record_dataset) keep the host side ahead of the transfers.
"""
import collections
from typing import Callable, Iterable, Iterator

import numpy as np

from autodist_tpu.telemetry import spans as tel


def stack_batches(group, pad_to: int = None):
    """Stack a list of same-structure batches into one ``[k, ...]`` feed
    (the fused engine's input shape). Device-resident leaves stack on
    device (``jnp.stack`` — no host round-trip); host leaves via
    ``np.stack``. The ONE stacking rule, shared by
    :class:`DevicePrefetcher`'s stack mode, ``Runner.fit``'s grouping
    path, and the serving micro-batcher.

    ``pad_to=n`` (>= len(group)) PADS the stacked leading dim to ``n`` by
    repeating the last element — the serving path's pad-to-bucket rule
    (a short request group runs on the nearest compiled bucket shape
    instead of recompiling; repeated rows are real data, so no model can
    NaN on them, and the caller masks rows ``>= len(group)`` out of the
    fetches). Training callers keep the default (no padding): a padded
    TRAINING step would silently weight the repeated examples into the
    gradient."""
    import jax
    if not group:
        raise ValueError("stack_batches on an empty group — nothing to "
                         "stack (or pad)")
    if pad_to is not None:
        if pad_to < len(group):
            raise ValueError(
                "stack_batches(pad_to=%d) with %d items — pad_to must be "
                ">= the group size" % (pad_to, len(group)))
        group = list(group) + [group[-1]] * (pad_to - len(group))

    def stack(*ls):
        if isinstance(ls[0], jax.Array):
            if not all(getattr(l, "is_fully_addressable", True)
                       for l in ls):
                # a multi-process global array cannot be re-stacked
                # process-locally; jnp.stack's raw error would not say
                # what to do about it
                raise ValueError(
                    "cannot stack multi-process global arrays into a "
                    "fused [k, ...] feed — feed host numpy batches, or "
                    "pre-stack with DevicePrefetcher(stack=k) so the "
                    "placement happens once, already stacked")
            return jax.numpy.stack(ls)
        return np.stack([np.asarray(l) for l in ls])
    return jax.tree_util.tree_map(stack, *group)


class DevicePrefetcher:
    """Wraps a host-batch iterator; yields device-resident (mesh-sharded)
    batches with ``depth`` placements in flight.

    ``place`` converts one host batch to device form — by default the
    runner's ``remapper.remap_feed`` (pass a Runner), or any callable.

        pf = DevicePrefetcher(dataset, runner, depth=2)
        for batch in pf:                      # already on the mesh
            metrics = runner.run(batch)       # remap_feed is a no-op here

    ``stack=k`` (> 1) is the fused-engine feed mode: k consecutive host
    batches are stacked into ONE ``[k, ...]`` feed and placed via
    ``remapper.remap_feed_stack`` — the whole superstep's data lands in a
    single transfer, issued behind the previous superstep's compute:

        pf = DevicePrefetcher(dataset, runner, depth=2, stack=4)
        runner.fit(pf, fuse_steps=4, metrics_every=8)

    (``fit`` recognizes a matching ``stack_k`` and consumes the items
    whole instead of re-grouping.) A trailing group smaller than k is
    dropped with a warning — a smaller stack would force a recompile of
    the fused program.
    """

    def __init__(self, iterable: Iterable, runner_or_place, depth: int = 2,
                 stack: int = 1):
        if stack < 1:
            raise ValueError("stack must be >= 1")
        self.stack_k = stack
        if callable(runner_or_place):
            # custom placement callable: in stack mode it receives the
            # already-stacked [k, ...] host batch
            self._place: Callable = runner_or_place
        elif stack > 1:
            self._place = runner_or_place.remapper.remap_feed_stack
        else:
            self._place = runner_or_place.remapper.remap_feed
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._depth = depth
        self._it = iter(iterable)
        self._queue = collections.deque()
        self._exhausted = False
        self._placed = 0  # queue items placed so far (the span's item=)
        # observable data-loss accounting (stack mode's dropped tails)
        self.dropped_batches = 0
        self.dropped_examples = 0

    def _next_host_item(self):
        """One queue item's host batch: a plain batch, or a [k, ...]
        stacked group in stack mode. Raises StopIteration when done."""
        if self.stack_k == 1:
            return next(self._it)
        group = []
        for _ in range(self.stack_k):
            try:
                group.append(next(self._it))
            except StopIteration:
                break
        if not group:
            raise StopIteration
        if len(group) < self.stack_k:
            # count the DATA cost of the drop, not just the event: the
            # tail's examples never train (once per epoch — the iterator
            # is exhausted exactly once), and the registry exposes the
            # running totals so a multi-epoch job can see the loss rate
            examples = sum(self._batch_examples(b) for b in group)
            self.dropped_batches += len(group)
            self.dropped_examples += examples
            tel.counter_add("prefetch.dropped_batches", len(group))
            tel.counter_add("prefetch.dropped_examples", examples)
            tel.instant("prefetch.dropped_tail", "prefetch",
                        batches=len(group), examples=examples)
            from autodist_tpu.utils import logging
            logging.warning(
                "DevicePrefetcher(stack=%d): dropping trailing group of "
                "%d batch(es) / %d example(s) this epoch — a short stack "
                "would recompile the fused program (totals so far: %d "
                "batches, %d examples)", self.stack_k, len(group),
                examples, self.dropped_batches, self.dropped_examples)
            raise StopIteration
        return stack_batches(group)

    @staticmethod
    def _batch_examples(batch) -> int:
        """Leading-dim example count of one host batch (0 if opaque)."""
        import jax
        for leaf in jax.tree_util.tree_leaves(batch):
            shape = np.shape(leaf)
            if len(shape) >= 1:
                return int(shape[0])
        return 0

    def _fill(self):
        while not self._exhausted and len(self._queue) < self._depth:
            try:
                host_batch = self._next_host_item()
            except StopIteration:
                self._exhausted = True
                return
            # placement is async: this enqueues the transfer and returns
            with tel.span("prefetch.place", "prefetch",
                          stack=self.stack_k, item=self._placed):
                self._queue.append(self._place(host_batch))
            self._placed += 1
        # occupancy AFTER filling: 0 here means the consumer is about to
        # stall on the host side — the starvation signal
        tel.gauge_set("prefetch.queue_depth", len(self._queue))

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        self._fill()
        if not self._queue:
            raise StopIteration
        out = self._queue.popleft()
        tel.counter_add("prefetch.batches")
        self._fill()  # immediately start the replacement transfer
        return out

    def take(self, n: int) -> Iterator:
        """Bounded view: yield at most n batches (for infinite datasets)."""
        for _ in range(n):
            try:
                yield next(self)
            except StopIteration:
                return
