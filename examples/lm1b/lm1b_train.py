"""lm1b-style language-model training (words/sec metric).

Mirror of reference ``examples/lm1b/lm1b_train.py`` (``:62-75`` logs wps =
batch x num_replicas x log_frequency / elapsed): a causal transformer LM on
synthetic 1B-word-shaped data under PartitionedPS (the reference's lm1b
config per BASELINE.md).
"""

if __package__ in (None, ""):  # direct invocation: put the repo root on sys.path
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__)))))
import argparse
import dataclasses
import os
import time

import optax

import autodist_tpu as adt
from autodist_tpu import strategy as S
from autodist_tpu.models import lm
from autodist_tpu.utils.compile_cache import enable_compile_cache


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="tiny",
                   choices=["tiny", "byte", "default", "lm1b"])
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--seq_len", type=int, default=128)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--log_frequency", type=int, default=20)
    p.add_argument("--resource_spec", default=None)
    p.add_argument("--data", default="synthetic",
                   help="'synthetic', or a directory of text files to "
                        "tokenize (byte-level) through the native record "
                        "loader; 'docs' uses the repo's own documentation")
    p.add_argument("--decode", type=int, default=0, metavar="N",
                   help="after training, generate N tokens per prompt "
                        "through the continuous-batching DecodeEngine "
                        "(serving/decode.py)")
    args = p.parse_args()
    enable_compile_cache()

    cfg = {"tiny": lm.LMConfig.tiny, "default": lm.LMConfig,
           # byte-level vocab for raw-text corpora (--data), small dims
           "byte": lambda: lm.LMConfig(vocab_size=256, d_model=128,
                                       num_layers=2, num_heads=4,
                                       mlp_dim=256),
           "lm1b": lm.LMConfig.lm1b}[args.config]()
    if cfg.max_seq_len < args.seq_len:
        cfg = dataclasses.replace(cfg, max_seq_len=args.seq_len)
    ad = adt.AutoDist(resource_spec_file=args.resource_spec,
                      strategy_builder=S.PartitionedPS())
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=args.seq_len, batch_size=args.batch_size)
    step = ad.function(loss_fn, optimizer=optax.adam(1e-3), params=params)

    batches = None
    if args.data != "synthetic":
        # real text -> ADT1 records -> native loader (vocab must be
        # byte-level for raw text)
        import glob
        import tempfile
        from autodist_tpu.data import text as text_lib
        from autodist_tpu.data.record_dataset import RecordFileDataset
        if cfg.vocab_size < text_lib.BYTE_VOCAB:
            raise SystemExit("--data needs vocab_size >= 256 (byte tokens)")
        if args.data == "docs":
            repo = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            paths = text_lib.repo_docs_corpus(repo)
        else:
            paths = sorted(glob.glob(os.path.join(args.data, "*")))
        # per-process path: concurrent runs must not clobber each other's
        # records while the native loader has them mmapped
        rec = os.path.join(tempfile.gettempdir(),
                           "lm1b_text_%d.adt" % os.getpid())
        n = text_lib.write_lm_records(paths, rec, seq_len=args.seq_len)
        print("real-text corpus: %d files -> %d records" % (len(paths), n))
        ds = RecordFileDataset(rec, batch_size=args.batch_size, shuffle=True)
        batches = iter(ds)

    t0, words = time.perf_counter(), 0
    run_t0, run_words, m = None, 0, {"loss": float("nan")}
    for i in range(args.steps):
        m = step(batch if batches is None else next(batches))
        words += args.batch_size * args.seq_len
        if run_t0 is None:
            run_t0 = time.perf_counter()  # post-compile clock for the summary
        else:
            run_words += args.batch_size * args.seq_len
        if (i + 1) % args.log_frequency == 0:
            dt = time.perf_counter() - t0
            print("step %d loss %.4f wps %.1f" % (i + 1, m["loss"], words / dt))
            t0, words = time.perf_counter(), 0
    wps = run_words / (time.perf_counter() - run_t0) if run_words else 0.0
    print("lm1b done: %d steps, final loss %.4f, %.1f words/sec"
          % (args.steps, m["loss"], wps))

    if args.decode > 0:
        decode(step.get_runner(), cfg, args.decode, args.batch_size)


def decode(runner, cfg, n_tokens: int, batch_size: int):
    """Autoregressive generation from the trained checkpoint through the
    continuous-batching decode engine — the runnable entry point behind
    docs/serving.md."""
    import numpy as np

    from autodist_tpu.serving.decode import DecodeConfig, DecodeEngine

    replicas = runner.remapper.num_replicas
    slots = max(8 // max(replicas, 1), 1) * max(replicas, 1)
    setup = lm.make_decode_setup(cfg)
    engine = DecodeEngine(runner, setup, DecodeConfig(
        slots=slots, max_new_tokens=n_tokens,
        prefill_len=min(16, cfg.max_seq_len // 2)))
    engine.warmup()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (1 + i % 8,)).astype(np.int32)
               for i in range(min(batch_size, 2 * slots))]
    t0 = time.perf_counter()
    futures = [engine.submit(p) for p in prompts]
    results = [f.result(timeout=600) for f in futures]
    dt = time.perf_counter() - t0
    stats = engine.stats()
    total = sum(len(r["tokens"]) for r in results)
    for p, r in zip(prompts[:4], results[:4]):
        print("prompt %s -> %s (%s)" % (list(map(int, p)),
                                        list(map(int, r["tokens"])),
                                        r["finished"]))
    print("decode done: %d sequences, %d tokens, %.1f tokens/sec, "
          "token p50 %.2fms p99 %.2fms, recompiles after warmup: %d"
          % (len(results), total, total / dt,
             stats["token_p50_ms"] or 0.0, stats["token_p99_ms"] or 0.0,
             stats["recompiles_after_warmup"]))
    engine.close()


if __name__ == "__main__":
    main()
