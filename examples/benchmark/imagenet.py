"""ImageNet CNN benchmark harness.

Mirror of reference ``examples/benchmark/imagenet.py``: model selected by
``--model`` (resnet18/50/101, vgg16, inceptionv3, densenet121), strategy by
``--autodist_strategy`` (``:160-182``), per-model all-reduce chunk sizes
(``:150-158``), examples/sec logging. Synthetic ImageNet-shaped data.

  python examples/benchmark/imagenet.py --model resnet50 \
      --autodist_strategy AllReduce --batch_size 64 --steps 200
"""

if __package__ in (None, ""):  # direct invocation: put the repo root on sys.path
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__)))))
import argparse

import jax.numpy as jnp
import numpy as np
import optax

import autodist_tpu as adt
from autodist_tpu import strategy as S
from autodist_tpu import models
from autodist_tpu.utils.compile_cache import enable_compile_cache
from examples.benchmark.utils.logs import BenchmarkLogger, ExamplesPerSecondHook

# per-model chunk sizes, as tuned in the reference (imagenet.py:150-158:
# vgg16=25, resnet101=200, inceptionv3=30, else 512)
CHUNK_SIZES = {"resnet101": 200, "vgg16": 25, "inceptionv3": 30}

# ImageNet-shaped entries of the shared model registry (which also holds
# bert/lm/ncf); per-model defaults like inceptionv3's 299px live there
MODELS = ("resnet18", "resnet50", "resnet101", "vgg16", "inceptionv3",
          "densenet121")


def make_builder(name: str, chunk: int):
    builders = {
        "PS": lambda: S.PS(),
        "PSLoadBalancing": lambda: S.PSLoadBalancing(),
        "PartitionedPS": lambda: S.PartitionedPS(),
        "AllReduce": lambda: S.AllReduce(chunk_size=chunk),
        "PartitionedAR": lambda: S.PartitionedAR(chunk_size=chunk),
        "Parallax": lambda: S.Parallax(chunk_size=chunk),
    }
    return builders[name]()


def _make_record_dataset(example_batch, args):
    """Write a few batches of synthetic records once; return
    (dataset, record_path). The caller unlinks path/path+'.json' when done
    (~150-275 MB of synthetic images per run)."""
    import os
    import tempfile
    from autodist_tpu.data import RecordFileDataset, RecordFileWriter
    fd, path = tempfile.mkstemp(suffix=".adt", prefix="imagenet_bench_")
    os.close(fd)
    img_shape = tuple(example_batch["image"].shape[1:])
    rng = np.random.RandomState(0)
    with RecordFileWriter(path, fields=[("image", np.float32, img_shape),
                                        ("label", np.int32, ())]) as w:
        for _ in range(args.batch_size * 4):  # 4 batches, shuffled each epoch
            w.write({"image": rng.randn(*img_shape).astype(np.float32),
                     "label": np.int32(rng.randint(1000))})
    return RecordFileDataset(path, args.batch_size, seed=0, num_threads=2), path


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50", choices=sorted(MODELS))
    p.add_argument("--autodist_strategy", default="AllReduce")
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--image_size", type=int, default=None,
                   help="default 224 (299 for inceptionv3)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--resource_spec", default=None)
    p.add_argument("--bf16", action=argparse.BooleanOptionalAction,
                   default=True, help="bfloat16 compute (--no-bf16 for f32)")
    p.add_argument("--lr", type=float, default=None,
                   help="SGD lr (default 0.1; 0.01 for vgg16, whose "
                        "flatten-head gradients diverge at 0.1 from scratch)")
    p.add_argument("--record_pipeline", action="store_true",
                   help="feed through the native record loader + device "
                        "prefetcher instead of a fixed device-resident "
                        "batch (measures the full input path)")
    args = p.parse_args()
    enable_compile_cache()

    chunk = CHUNK_SIZES.get(args.model, 512)
    ad = adt.AutoDist(resource_spec_file=args.resource_spec,
                      strategy_builder=make_builder(args.autodist_strategy, chunk))
    kw = dict(batch_size=args.batch_size,
              dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    if args.image_size is not None:
        kw["image_size"] = args.image_size
    loss_fn, params, batch, _ = models.make_train_setup(args.model, **kw)
    lr = args.lr if args.lr is not None else (0.01 if args.model == "vgg16"
                                              else 0.1)
    # clip: from-scratch CNNs at benchmark lrs throw early gradient spikes
    # (vgg16's flatten head especially); clipping keeps every model finite
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.sgd(lr, momentum=0.9))
    # chains bypass the optimizer-capture patch; register so the serialized
    # strategy still records what optimizer trained it
    from autodist_tpu import patch
    patch.register_optimizer(opt, "sgd",
                             {"learning_rate": lr, "momentum": 0.9,
                              "clip_global_norm": 1.0})
    hook = ExamplesPerSecondHook(args.batch_size, every_n_steps=20,
                                 name=args.model)
    m = {"loss": float("nan")}
    if args.record_pipeline:
        # full input path: native loader threads -> device prefetcher ->
        # mesh-placed batches -> runner.fit
        import os
        from autodist_tpu.data import DevicePrefetcher
        runner = ad.build(loss_fn, opt, params, batch)
        runner.init(params)
        ds, record_path = _make_record_dataset(batch, args)
        try:
            with ds:
                history = runner.fit(DevicePrefetcher(ds, runner, depth=2),
                                     steps=args.steps,
                                     callbacks=[lambda i, _m: hook.after_step()])
        finally:
            for f in (record_path, record_path + ".json"):
                try:
                    os.unlink(f)
                except FileNotFoundError:
                    pass
        if history:
            m = history[-1]
    else:
        step = ad.function(loss_fn, optimizer=opt, params=params)
        for i in range(args.steps):
            m = step(batch)
            hook.after_step()
    BenchmarkLogger().log(model=args.model, strategy=args.autodist_strategy,
                          batch_size=args.batch_size,
                          examples_per_sec=round(hook.average, 1),
                          final_loss=float(m["loss"]))


if __name__ == "__main__":
    main()
