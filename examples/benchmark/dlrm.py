"""DLRM recommender benchmark — the auto-strategy flagship.

The BASELINE target config: a large-embedding CTR model where the right
distribution plan is NOT obvious — giant uneven tables want load-balanced
or partitioned PS with the sparse wire, the dense MLPs want AllReduce —
so the default strategy here is ``AutoStrategy``, which ranks the
candidates with the analytic cost model (including the HBM feasibility
gate) and reports what it picked.
"""

if __package__ in (None, ""):  # direct invocation: put the repo root on sys.path
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__)))))
import argparse

import optax

import autodist_tpu as adt
from autodist_tpu import strategy
from autodist_tpu.models import dlrm
from autodist_tpu.utils.compile_cache import enable_compile_cache
from examples.benchmark.utils.logs import BenchmarkLogger, ExamplesPerSecondHook
from examples.benchmark.imagenet import make_builder


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--autodist_strategy", default="AutoStrategy",
                   help="AutoStrategy (default) ranks candidates with the "
                        "cost model; any named builder forces it")
    p.add_argument("--batch_size", type=int, default=2048)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--embed_dim", type=int, default=64)
    p.add_argument("--resource_spec", default=None)
    args = p.parse_args()
    enable_compile_cache()

    builder = (strategy.AutoStrategy()
               if args.autodist_strategy == "AutoStrategy"
               else make_builder(args.autodist_strategy, 512))
    ad = adt.AutoDist(resource_spec_file=args.resource_spec,
                      strategy_builder=builder)
    cfg = dlrm.DLRMConfig(embed_dim=args.embed_dim,
                          bottom_mlp=(512, 256, args.embed_dim))
    loss_fn, params, batch, _ = dlrm.make_train_setup(
        cfg, batch_size=args.batch_size)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    hook = ExamplesPerSecondHook(args.batch_size, every_n_steps=20,
                                 name="dlrm")
    m = runner.run(batch)
    for _ in range(args.steps - 1):
        m = runner.run(batch)
        hook.after_step()

    picked = None
    if isinstance(builder, strategy.AutoStrategy) and builder.last_ranking:
        picked = builder.last_ranking[0].label
    meta = runner.distributed_step.metadata
    table_bytes = sum(
        v.byte_size
        for n, v in runner.distributed_step.model_item.var_infos.items()
        if "table_" in n)
    BenchmarkLogger().log(
        model="dlrm", strategy=args.autodist_strategy,
        picked=picked, embedding_gb=round(table_bytes / 1e9, 2),
        sparse_wire_vars=len(meta["sparse_wire"]),
        ps_resident_vars=len(meta["ps_host_resident"]),
        examples_per_sec=round(hook.average, 1),
        final_loss=float(m["loss"]))


if __name__ == "__main__":
    main()
