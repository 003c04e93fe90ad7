"""AllReduce strategy: dense gradient all-reduce across all replicas.

Analog of reference ``autodist/strategy/all_reduce_strategy.py:40-90``: every
(dense) variable gets an ``AllReduceSynchronizer``; variables are grouped in
index order into buckets of ``chunk_size`` (group id = idx // chunk_size,
reference ``:60-67``) — the reference feeds groups to TF's ScopedAllocator
pass; we feed them to our gradient-bucketing concat/all-reduce/split in
``parallel/collectives.py`` (on TPU the XLA all-reduce combiner does the
same job; explicit buckets also enable per-group compression).

Sparse (embedding) variables take the sparse all-gather path inside the
lowering, mirroring the reference's sparse branch
(``all_reduce_synchronizer.py:132-173``).
"""
from autodist_tpu.strategy.base import (AllReduceSynchronizer, GraphConfig,
                                        Strategy, StrategyBuilder, VarConfig)
from autodist_tpu.strategy.ps_strategy import replica_devices


class AllReduce(StrategyBuilder):
    def __init__(self, chunk_size: int = 128, all_reduce_spec: str = "AUTO",
                 compressor: str = "NoneCompressor",
                 wire_dtype: str = "fp32", compute_dtype: str = "f32"):
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        self.chunk_size = chunk_size
        self.all_reduce_spec = all_reduce_spec
        self.compressor = compressor
        # "int8": blockwise-quantized two-phase all-reduce wire (dense
        # float vars only; sparse/integer vars keep fp32 — ADT310)
        self.wire_dtype = wire_dtype
        # "bf16": managed bf16 compute tier (f32 master params/opt-state/
        # accumulation — the shape rules.verify_numerics certifies)
        self.compute_dtype = compute_dtype

    def build(self, model_item, resource_spec) -> Strategy:
        from autodist_tpu.parallel.collectives import wire_quantizable
        nodes = []
        for idx, name in enumerate(model_item.trainable_var_names):
            info = model_item.var_infos.get(name)
            # dense float, >= one scale block (ADT310/311 stay un-emitted
            # by construction — same gate as the searcher's canon)
            quantizable = wire_quantizable(info, min_block=True)
            nodes.append(VarConfig(
                var_name=name,
                synchronizer=AllReduceSynchronizer(
                    spec=self.all_reduce_spec,
                    compressor=self.compressor,
                    group=idx // self.chunk_size,
                    wire_dtype=(self.wire_dtype if quantizable else "fp32"))))
        return Strategy(node_config=nodes,
                        graph_config=GraphConfig(
                            replicas=replica_devices(resource_spec),
                            compute_dtype=self.compute_dtype))
