"""Strategy serialization round-trip (analog of reference ``tests/test_strategy_base.py``)."""
import json

import jax.numpy as jnp
import numpy as np
import optax

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.strategy.base import (AllReduceSynchronizer, GraphConfig,
                                        PSSynchronizer, Strategy, VarConfig)


def _sample():
    return Strategy(
        node_config=[
            VarConfig("w", AllReduceSynchronizer(spec="AUTO", compressor="HorovodCompressor", group=1)),
            VarConfig("emb", partitioner="2,1",
                      part_configs=[
                          VarConfig("emb/part_0", PSSynchronizer(reduction_destination="a:CPU:0")),
                          VarConfig("emb/part_1", PSSynchronizer(reduction_destination="b:CPU:0")),
                      ],
                      shard_sizes=[3, 2]),
        ],
        graph_config=GraphConfig(replicas=["a:TPU:0", "a:TPU:1"]))


def test_round_trip(tmp_path):
    s = _sample()
    path = s.serialize(str(tmp_path / "strat"))
    s2 = Strategy.deserialize(path=path)
    assert s2.to_dict() == s.to_dict()
    assert s2.id == s.id


def test_var_config_partition_props():
    s = _sample()
    node = s.find("emb")
    assert node.partition_axis == 0
    assert node.num_shards == 2
    assert s.find("w").num_shards == 1
    assert s.find("missing") is None


def test_nccl_alias_normalizes():
    ar = AllReduceSynchronizer(spec="NCCL")
    assert ar.spec == "ICI"


def test_a_stored_strategy_with_the_retired_overlap_key_loads(tmp_path):
    """A strategy file written before PR 27 may carry ``"overlap": true``
    in its graph config. It is input from outside the program: it loads,
    builds and lowers as the one gradient exchange there is."""
    params = {"w": jnp.ones((8, 4), jnp.float32),
              "b": jnp.zeros((4,), jnp.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)

    batch = {"x": np.ones((16, 8), np.float32),
             "y": np.zeros((16, 4), np.float32)}

    class Stored(S.AllReduce):
        def build(self, item, spec):
            d = super().build(item, spec).to_dict()
            assert "overlap" not in d["graph_config"]
            d["graph_config"]["overlap"] = True
            path = tmp_path / "stored"
            path.write_text(json.dumps(d))
            loaded = Strategy.deserialize(path=str(path))
            d["graph_config"].pop("overlap")
            assert loaded.to_dict() == d
            return loaded

    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=Stored(chunk_size=1))
    runner = ad.build(loss_fn, optax.sgd(0.1), params, batch)
    runner.init(params)
    meta = runner.distributed_step.metadata
    autodist_tpu.reset()
    assert [g["vars"] for g in meta["grad_sync_groups"]] == [["b", "w"]]
    assert not [k for k in meta if k.startswith("overlap")]
    assert not hasattr(runner.distributed_step.strategy.graph_config,
                       "overlap")
