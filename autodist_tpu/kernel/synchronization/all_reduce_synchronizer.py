"""AllReduce synchronizer kernel.

Analog of reference
``autodist/kernel/synchronization/all_reduce_synchronizer.py:102-130``: the
reference replaces each replica's gradient with a CollectiveReduce (mean via
merge=Add, final=Div) keyed so all workers agree. Here the collective is
``jax.lax.psum`` over the mesh's data axis — XLA lowers it onto ICI
(intra-slice) or DCN (cross-slice) per the mesh; the ``spec`` hint is kept
as metadata. Compression wraps the collective
(``kernel/synchronization/compressor.py``); partitioned variables take the
reduce-scatter path (each device receives only its shard of the summed
gradient — the ICI-native realization of "partition then all-reduce each
shard", reference ``partitioned_all_reduce_strategy.py:71-117``).

Sparse gradients: the reference all-gathers indices+values
(``all_reduce_synchronizer.py:132-173``). JAX gradients arrive dense; the
sparse fast path lives in ``ops/embedding.py`` (row-gathered updates) and is
routed by the lowering when a variable is marked sparse.
"""
from autodist_tpu.kernel.synchronization import compressor as compressor_lib
from autodist_tpu.kernel.synchronization.synchronizer import Synchronizer
from autodist_tpu.utils import logging


class AllReduceSynchronizer(Synchronizer):
    def __init__(self, var_name, config, num_replicas, mesh_axis="data",
                 layout=None, extra_axes=(), dcn_axes=()):
        super().__init__(var_name, config, num_replicas, mesh_axis, layout,
                         extra_axes, dcn_axes)
        self.compressor = compressor_lib.create(
            getattr(config, "compressor", None), var_name)
        # wire_dtype="int8" lowers the collective itself to the blockwise
        # two-phase quantized all-reduce: implemented by substituting the
        # Int8CompressorEF wire codec (error feedback keeps training
        # honest), which the bucketing layer then arms with the mesh axes.
        # A var that also names an explicit compressor keeps it (the
        # conflict is the linter's ADT310 error).
        self.wire_dtype = getattr(config, "wire_dtype", "fp32") or "fp32"
        if (self.wire_dtype == "int8"
                and self.compressor.name == "NoneCompressor"
                and not (layout is not None and layout.partitioned)):
            self.compressor = compressor_lib.create("Int8CompressorEF",
                                                    var_name)
        # NOTE: int8 wire arming happens in bucket_reduce — every
        # unpartitioned int8 var is concatable and routed into a bucket;
        # this per-var compressor only serves the psum fallback paths
        self.group = getattr(config, "group", 0)
        self.spec = getattr(config, "spec", "AUTO")
        # collective algorithm: auto | ring | rhd | hier (strategy/base.py
        # docs; resolution semantics in analysis/topology.py). Consumed in
        # psum() and by the bucketing layer via graph_transformer.
        self.schedule = (getattr(config, "schedule", "auto")
                         or "auto").lower()
        if (layout is not None and layout.partitioned
                and self.compressor.name != "NoneCompressor"):
            logging.warning("var %s: compressor %s is ignored on the "
                            "partitioned (reduce-scatter) path", var_name,
                            self.compressor.name)
        if (layout is not None and layout.partitioned
                and self.wire_dtype == "int8"):
            logging.warning("var %s: wire_dtype=int8 is ignored on the "
                            "partitioned (reduce-scatter) path (ADT310)",
                            var_name)

    def psum(self, x):
        """The ``spec`` hint and the ``schedule`` knob are consumed here:
        ``DCN`` (or ``schedule=hier`` when the mesh has cross-host axes)
        lowers the reduction to the bandwidth-hierarchical form
        (reduce-scatter over ICI, all-reduce the shard over DCN,
        all-gather over ICI) so the slow cross-host links carry 1/N_ici
        of the payload; ``schedule=rhd`` lowers to the explicit
        reduce-scatter + all-gather composition (recursive
        halving/doubling shape). AUTO/ICI ring takes the single fused
        psum and lets XLA schedule it; ``hier`` on a mesh with no
        cross-host axes falls back to that ring (resolver refusal —
        there is nothing to hierarchize)."""
        axes = (self.mesh_axis,) + self.extra_axes
        dcn = tuple(a for a in axes if a in self.dcn_axes)
        if (self.spec == "DCN" or self.schedule == "hier") and dcn:
            from autodist_tpu.parallel.collectives import hierarchical_psum
            ici = tuple(a for a in axes if a not in self.dcn_axes)
            return hierarchical_psum(x, ici, dcn)
        if self.schedule == "rhd":
            from autodist_tpu.parallel.collectives import rhd_psum
            return rhd_psum(x, axes)
        return super().psum(x)

    def plain_sum_axes(self):
        axes = (self.mesh_axis,) + self.extra_axes
        routed = (((self.spec == "DCN" or self.schedule == "hier")
                   and any(a in self.dcn_axes for a in axes))
                  or self.schedule == "rhd")
        if (routed or self.compressor.name != "NoneCompressor"
                or (self.layout is not None and self.layout.partitioned)):
            return None
        return axes

    def state_init(self, grad_shape, dtype):
        return self.compressor.state_init(grad_shape, dtype)

    def sync(self, grad, state):
        if self.layout is not None and self.layout.partitioned:
            # reduce-scatter over the data axis, plain psum over any extra
            # axes, then normalize to mean over all devices
            local = self.psum_extra(self.layout.reduce_scatter_grad(grad))
            return local / self.num_replicas, state
        reduced, new_state = self.compressor.reduce(grad, state, self.psum)
        return reduced / self.num_replicas, new_state
