"""PS synchronizer kernel.

Analog of reference
``autodist/kernel/synchronization/ps_synchronizer.py`` (761 LoC of graph
surgery). The reference's machinery maps onto TPU as follows:

- *In-graph apply* (share replica-0 variable, aggregate local grads on the
  worker CPU, ``ps_synchronizer.py:105-152,460-535``): under SPMD all local
  replicas already share one logical variable; the local aggregation is the
  first hop of the single ``psum``.
- *Between-graph apply* (place var+update on the PS device, per-worker
  accumulators, token-queue barriers, ``:171-176,335-458,556-633``): with
  ``local_replication=False`` (no proxy — the reference's default) the
  variable takes the REAL host-offloaded PS data path in ``parallel/ps.py``:
  values + optimizer state rest in host memory, pulled to device each step,
  gradients pushed back and applied host-side — and this kernel is never
  instantiated. This class handles only the **proxied** case
  (``local_replication=True``, the reference's worker-local cache): the
  variable rests on device, and the synchronous dance — "push grads, owner
  averages over num_workers, applies, workers wait for the token" — is
  exactly the semantics of one mean ``psum`` followed by a (redundantly
  computed, hence communication-free) update: every device leaves the step
  with the identical post-update value, which is what the token queue
  guaranteed.
- *Staleness* (``:388-458``): bounded staleness is a runtime-scheduling
  property on TPU, implemented by the Runner's cross-process pacing
  through the native coordination service
  (``runtime/coordination.py``): each process reports its step and blocks
  while more than ``staleness`` steps ahead of the slowest worker — the
  semantics the reference built from size-``s`` token queues. Fully-async
  PS (``sync=False``) is a host-store property (``parallel/ps.py``); an
  async PROXIED var is contradictory (a device-cached copy updated in
  lockstep cannot be async) and warns.
"""
from autodist_tpu.kernel.synchronization.synchronizer import Synchronizer


class PSSynchronizer(Synchronizer):
    def __init__(self, var_name, config, num_replicas, mesh_axis="data",
                 layout=None, extra_axes=(), dcn_axes=()):
        super().__init__(var_name, config, num_replicas, mesh_axis, layout,
                         extra_axes, dcn_axes)
        self.reduction_destination = getattr(config, "reduction_destination", "")
        self.local_replication = getattr(config, "local_replication", False)
        self.sync_mode = getattr(config, "sync", True)
        self.staleness = getattr(config, "staleness", 0)
        # host<->device wire format of the no-proxy PS path (consumed by
        # plan_host_ps -> PSVarPlan; this kernel only lowers the PROXIED
        # case, where there is no host wire to quantize)
        self.wire_dtype = getattr(config, "wire_dtype", "fp32") or "fp32"
        if self.wire_dtype == "int8" and self.local_replication:
            from autodist_tpu.utils import logging
            logging.warning(
                "var %s: wire_dtype=int8 with local_replication=True is "
                "ignored — a proxied PS var is device-resident and its "
                "sync is an on-device psum, no host wire exists (ADT310)",
                var_name)
        if not self.sync_mode:
            from autodist_tpu.utils import logging
            logging.warning(
                "var %s: sync=False with local_replication=True is "
                "contradictory — a device-cached proxy updates in lockstep; "
                "drop the proxy to get the async host-PS path", var_name)

    def plain_sum_axes(self):
        if self.layout is not None and self.layout.partitioned:
            return None
        return (self.mesh_axis,) + self.extra_axes

    def sync(self, grad, state):
        if self.layout is not None and self.layout.partitioned:
            local = self.psum_extra(self.layout.reduce_scatter_grad(grad))
            return local / self.num_replicas, state
        return self.psum(grad) / self.num_replicas, state
