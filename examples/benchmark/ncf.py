"""NCF (NeuMF) recommender benchmark harness.

Mirror of reference ``examples/benchmark/ncf.py`` (MovieLens NeuMF).
``--data ratings.dat`` runs the REAL pipeline (reference
``utils/recommendation/``): parse ml-1m-format ratings, leave-one-out
split, positives through the native record loader, per-batch negative
sampling, HR@10/NDCG@10 eval, and a sparse-wire byte report on the real
id distribution. Without ``--data`` it benchmarks on synthetic
interactions (the r2 behavior); a synthetic ml-1m-format slice ships at
``examples/benchmark/data/ml_tiny_synthetic.dat``.
"""

if __package__ in (None, ""):  # direct invocation: put the repo root on sys.path
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__)))))
import argparse
import os
import tempfile

import numpy as np
import optax

import autodist_tpu as adt
from autodist_tpu.models import ncf
from autodist_tpu.utils.compile_cache import enable_compile_cache
from examples.benchmark.utils.logs import BenchmarkLogger, ExamplesPerSecondHook
from examples.benchmark.imagenet import make_builder


def run_real_data(args, builder):
    from autodist_tpu.data import movielens
    data = movielens.load_ratings(args.data)
    train, holdout = movielens.leave_one_out_split(data)
    record_path = os.path.join(tempfile.gettempdir(),
                               "ncf_train_%d.adt" % os.getpid())
    movielens.write_train_records(train, record_path)
    try:
        _run_real_data_inner(args, builder, train, holdout, record_path)
    finally:
        for p in (record_path, record_path + ".json"):
            try:
                os.unlink(p)
            except OSError:
                pass


def _run_real_data_inner(args, builder, train, holdout, record_path):
    import math
    import jax
    from autodist_tpu.data import movielens
    # AutoDist BEFORE any device query (multi-node chief-launch joins the
    # distributed runtime at construction)
    ad = adt.AutoDist(resource_spec_file=args.resource_spec,
                      strategy_builder=builder)
    cfg = ncf.NCFConfig(num_users=train.num_users, num_items=train.num_items)
    loss_fn, params, _, apply_fn = ncf.make_train_setup(cfg)

    # global batch = pos x (1 + negatives) and must divide by the replica
    # count; round pos to the smallest multiple that makes it so
    group = 1 + args.neg_per_pos
    n_dev = len(jax.devices())
    step = n_dev // math.gcd(group, n_dev)
    pos_per_batch = max(step, (args.batch_size // group) // step * step)
    batches = movielens.train_batches(record_path, train, pos_per_batch,
                                      neg_per_pos=args.neg_per_pos)
    first = next(batches)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, first)
    runner.init(params)
    hook = ExamplesPerSecondHook(len(first["user"]), every_n_steps=20,
                                 name="ncf")
    m = runner.run(first)
    for _ in range(args.steps - 1):
        m = runner.run(next(batches))
        hook.after_step()

    # sparse-wire accounting on the real id distribution
    wire = sorted(runner.distributed_step.metadata["sparse_wire"])
    store = runner.distributed_step.ps_store
    extra = {}
    if store is not None and store.stats["pushes"]:
        dense = sum(int(np.prod(v.shape)) * 4
                    for n, v in
                    runner.distributed_step.model_item.var_infos.items()
                    if n in wire and n in store.plans)
        pushed = store.stats["bytes_pushed"] / store.stats["pushes"]
        extra = {"dense_grad_bytes": dense,
                 "pushed_bytes_per_step": round(pushed),
                 "wire_savings_x": round(dense / max(pushed, 1), 1)}

    gathered = runner.gather_params()

    def score_fn(users, items):
        import jax.numpy as jnp
        return apply_fn(gathered, jnp.asarray(users), jnp.asarray(items))

    ev = movielens.evaluate_hit_ndcg(score_fn, holdout, train,
                                     num_negatives=args.eval_negatives)
    BenchmarkLogger().log(model="ncf", strategy=args.autodist_strategy,
                          data=os.path.basename(args.data),
                          interactions=train.n,
                          users=train.num_users, items=train.num_items,
                          examples_per_sec=round(hook.average, 1),
                          final_loss=float(m["loss"]),
                          hr_at_10=round(ev["hr"], 4),
                          ndcg_at_10=round(ev["ndcg"], 4),
                          sparse_wire_vars=len(wire), **extra)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--autodist_strategy", default="PSLoadBalancing")
    p.add_argument("--batch_size", type=int, default=1024)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--resource_spec", default=None)
    p.add_argument("--data", default=None,
                   help="MovieLens ratings file (ml-1m .dat or csv); "
                        "omit for synthetic interactions")
    p.add_argument("--neg_per_pos", type=int, default=4)
    p.add_argument("--eval_negatives", type=int, default=99)
    args = p.parse_args()
    enable_compile_cache()

    builder = make_builder(args.autodist_strategy, 512)
    if args.data:
        run_real_data(args, builder)
        return
    ad = adt.AutoDist(resource_spec_file=args.resource_spec,
                      strategy_builder=builder)
    loss_fn, params, batch, _ = ncf.make_train_setup(
        batch_size=args.batch_size)
    step = ad.function(loss_fn, optimizer=optax.adam(1e-3), params=params)
    hook = ExamplesPerSecondHook(args.batch_size, every_n_steps=20, name="ncf")
    for _ in range(args.steps):
        m = step(batch)
        hook.after_step()
    BenchmarkLogger().log(model="ncf", strategy=args.autodist_strategy,
                          examples_per_sec=round(hook.average, 1),
                          final_loss=float(m["loss"]))


if __name__ == "__main__":
    main()
