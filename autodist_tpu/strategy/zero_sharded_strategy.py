"""ZeroSharded strategy: replicated params, cross-replica sharded update.

The ZeRO stage-1 weight update (arXiv 2004.13336) as a zoo builder: every
eligible dense variable gets a :class:`ZeroShardedSynchronizer` — the
lowering reduce-scatters its gradient over the data axis, applies the
optimizer to each replica's owned 1/P flat shard only (optimizer state is
created sharded, never materialized whole), and all-gathers the update
back onto the replicated params. Same wire bytes as AllReduce
(rs + ag = the 2(P-1)/P ring factor), ~(P-1)/P less optimizer-state HBM
per chip — the memory/speed trade axis the PR 4 static HBM analyzer
(ADT501) gates on and the PR 6 searcher exploits.

Ineligible variables fall back to plain AllReduce, so built plans lint
clean by construction (the searcher's canon applies the same gates):

- sparse (gather-indexed) variables: the reduce-scatter would densify
  their batch-row-sized gradient to the full table (ADT312); they keep
  the (ids, values) sparse wire.
- variables smaller than one per-replica shard: the padding + collective
  launch overhead exceeds the opt-state saving (ADT313).

``wire_dtype="int8"`` additionally quantizes both wire crossings through
the blockwise codec (dense float vars of >= one scale block; the rest
stay fp32 — ADT310/311 by construction, same as the AllReduce builder).
"""
from autodist_tpu.strategy.base import (AllReduceSynchronizer, GraphConfig,
                                        Strategy, StrategyBuilder, VarConfig,
                                        ZeroShardedSynchronizer)
from autodist_tpu.strategy.ps_strategy import replica_devices


def zero_shardable(info, num_replicas: int) -> bool:
    """The ONE eligibility gate for ZeroSharded sync, shared by this
    builder, the search space's canon, and the ADT313 lint (ADT312/313
    stay un-emitted by construction): dense variables with at least one
    element per replica shard."""
    if info is None or getattr(info, "sparse", False):
        return False
    if getattr(info, "num_elements", 0) < max(int(num_replicas), 1):
        return False
    return True


def zero_wire_quantizable(info, num_replicas: int) -> bool:
    """int8 eligibility for the ZeRO rs/ag wire: dense float AND at
    least one scale block PER SHARD — the kernel rounds each replica's
    shard up to whole blocks, so a variable below ``P x block`` elements
    would ship MORE padded int8 bytes than the fp32 wire. Shared by the
    builder and the searcher's canon so the cost model's padded pricing
    and the emitted plans agree."""
    from autodist_tpu.parallel.collectives import (wire_block_size,
                                                   wire_quantizable)
    if not wire_quantizable(info):
        return False
    return (getattr(info, "num_elements", 0)
            >= max(int(num_replicas), 1) * wire_block_size())


class ZeroSharded(StrategyBuilder):
    def __init__(self, chunk_size: int = 128, wire_dtype: str = "fp32",
                 compute_dtype: str = "f32"):
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        # chunk_size buckets the AllReduce FALLBACK vars (small/sparse)
        self.chunk_size = chunk_size
        # "int8": blockwise-quantized rs + update all-gather wire (dense
        # float vars of >= one scale block only — ADT310/311)
        self.wire_dtype = wire_dtype
        # "bf16": managed bf16 compute beside the f32 sharded master —
        # the 2004.13336 pairing (bf16 compute, f32 shard update)
        self.compute_dtype = compute_dtype

    def build(self, model_item, resource_spec) -> Strategy:
        n_replicas = max(len(resource_spec.devices), 1)
        nodes = []
        for idx, name in enumerate(model_item.trainable_var_names):
            info = model_item.var_infos.get(name)
            if zero_shardable(info, n_replicas):
                quantizable = zero_wire_quantizable(info, n_replicas)
                nodes.append(VarConfig(
                    var_name=name,
                    synchronizer=ZeroShardedSynchronizer(
                        wire_dtype=(self.wire_dtype if quantizable
                                    else "fp32"))))
            else:
                nodes.append(VarConfig(
                    var_name=name,
                    synchronizer=AllReduceSynchronizer(
                        group=idx // self.chunk_size)))
        return Strategy(node_config=nodes,
                        graph_config=GraphConfig(
                            replicas=replica_devices(resource_spec),
                            compute_dtype=self.compute_dtype))
