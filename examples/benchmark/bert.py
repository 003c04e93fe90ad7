"""BERT pretraining benchmark harness.

Mirror of reference ``examples/benchmark/bert.py`` (chunk_size 256 at
``:62``; strategy flag incl. Parallax): masked-LM pretraining on synthetic
sequences, samples/sec metric.

  python examples/benchmark/bert.py --config base --autodist_strategy Parallax
"""

if __package__ in (None, ""):  # direct invocation: put the repo root on sys.path
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__)))))
import argparse

import optax

import autodist_tpu as adt
from autodist_tpu.models import bert
from autodist_tpu.utils.compile_cache import enable_compile_cache
from examples.benchmark.utils.logs import BenchmarkLogger, ExamplesPerSecondHook
from examples.benchmark.imagenet import make_builder

CONFIGS = {"tiny": bert.BertConfig.tiny, "base": bert.BertConfig.base,
           "large": bert.BertConfig.large}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="base", choices=sorted(CONFIGS))
    p.add_argument("--autodist_strategy", default="Parallax")
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--seq_len", type=int, default=128)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--resource_spec", default=None)
    args = p.parse_args()
    enable_compile_cache()

    ad = adt.AutoDist(resource_spec_file=args.resource_spec,
                      strategy_builder=make_builder(args.autodist_strategy, 256))
    loss_fn, params, batch, _ = bert.make_train_setup(
        CONFIGS[args.config](), seq_len=args.seq_len,
        batch_size=args.batch_size)
    step = ad.function(loss_fn, optimizer=optax.adamw(1e-4), params=params)
    hook = ExamplesPerSecondHook(args.batch_size, every_n_steps=10, name="bert")
    for _ in range(args.steps):
        m = step(batch)
        hook.after_step()
    BenchmarkLogger().log(model="bert_" + args.config,
                          strategy=args.autodist_strategy,
                          samples_per_sec=round(hook.average, 1),
                          final_loss=float(m["loss"]))


if __name__ == "__main__":
    main()
