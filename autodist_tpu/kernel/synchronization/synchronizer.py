"""Synchronizer base class.

Analog of reference ``autodist/kernel/synchronization/synchronizer.py:23-104``:
holds the cluster context (replica count, worker id, chief-ness) and provides
the factory-by-name ``create``. Where the reference's synchronizers rewrite
graph edges (``in_graph_apply``/``between_graph_apply``), ours contribute a
gradient transform to the lowered SPMD step: ``sync(grad, state) ->
(synced_grad_in_storage_layout, new_state)``. The reference's two phases map
onto TPU as: in-graph apply = the intra-mesh collective (one XLA op spans
all local replicas); between-graph apply = the same collective spanning
hosts over ICI/DCN — SPMD erases the distinction, which is precisely why the
reference's AllReduce ``between_graph_apply`` was already a no-op
(``all_reduce_synchronizer.py:199-201``).
"""
from abc import ABC, abstractmethod

import jax

from autodist_tpu import const


class Synchronizer(ABC):
    def __init__(self, var_name: str, config, num_replicas: int,
                 mesh_axis: str = const.DATA_AXIS, layout=None,
                 extra_axes: tuple = (), dcn_axes: tuple = ()):
        self.var_name = var_name
        self.config = config
        self.num_replicas = num_replicas  # TOTAL devices reducing this grad
        self.mesh_axis = mesh_axis        # axis carrying partitioned shards
        self.extra_axes = tuple(extra_axes)  # further axes (seq, ...) to reduce
        self.dcn_axes = tuple(dcn_axes)   # axes crossing hosts (spec=DCN hint)
        self.layout = layout  # VarLayout

    def psum(self, x):
        return jax.lax.psum(x, (self.mesh_axis,) + self.extra_axes)

    def psum_extra(self, x):
        """Reduce over the non-data axes only (after a data-axis
        reduce-scatter has handled the data axis)."""
        if not self.extra_axes:
            return x
        return jax.lax.psum(x, self.extra_axes)

    def plain_sum_axes(self):
        """The mesh axes ``sync`` sums over, where ``sync`` is nothing but
        ``psum(grad, axes) / num_replicas`` with no state; None otherwise.
        The lowering issues such sums in the order the backward pass
        completes their gradients, for the compiler to pack the small
        ones (``parallel/collectives.py:plan_grad_sync_groups``)."""
        return None

    @abstractmethod
    def sync(self, grad, state):
        """Inside shard_map: reduce this variable's gradient across the data
        axis, returning it in the variable's *storage* layout (full for
        replicated vars, local shard for partitioned ones)."""

    def state_init(self, grad_shape, dtype):
        """Per-step carried state (compressor residuals); None if stateless."""
        return None

    @staticmethod
    def create(kind_name: str, *args, **kwargs) -> "Synchronizer":
        """Factory by subclass name (reference ``synchronizer.py:90-104``)."""
        from autodist_tpu.kernel.synchronization.all_reduce_synchronizer import (
            AllReduceSynchronizer)
        from autodist_tpu.kernel.synchronization.ps_synchronizer import PSSynchronizer
        subclasses = {c.__name__: c for c in (AllReduceSynchronizer, PSSynchronizer)}
        if kind_name not in subclasses:
            raise ValueError("unknown synchronizer %r" % kind_name)
        return subclasses[kind_name](*args, **kwargs)
