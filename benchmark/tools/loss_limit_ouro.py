#!/usr/bin/env python3
"""What an ouro cell's ``loss_rtol`` refuses: the float32 reference with ONE
fault planted at a time, read exactly as ``drivers/train_fit.py`` reads a
run (the larger of the two relative distances of the loss at steps 0 and 1
from the sound reference's). ``tools/loss_limit.py`` is the same for an
olmoe cell and says what a reading means; the faults here are those a
looped model's step can have and its float32 reference can state: three
passes run instead of four, the norm between the passes left out (the next
pass starts from the un-normed state), the head read after the last pass
only, the gate left out (uniform weights over the passes), the entropy
term left out, the norms of the sub-layers' outputs left out, another Adam
step or none, and the whole step in a coarser precision
(``reference/olmoe.py:computed_in``).

    python3 benchmark/tools/loss_limit_ouro.py --workload ouro_2_6b_train_1chip \\
        --seed 4200000601 --out chiprun_out/pr44/loss_limit.jsonl

The readings are differences between two float32 computations. On the
chip (``chiprun``; ``"highest"`` precision, which ``train_check`` sets) a
reading takes about a minute. With ``--config-file`` and ``--traffic-set``
it runs at a tiny size; ``tests/test_ouro_cell.py`` calls :func:`readings`
that way.
"""
import argparse
import contextlib
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.tools.loss_limit import patched  # noqa: E402

# not a fault: the configuration's own precision, which the limit has to
# let through
WITHIN = ("computed_in_bfloat16",)


def faults():
    """{name: a context manager factory that plants it while the reference
    is traced}."""
    import jax.numpy as jnp
    from benchmark.reference import lm
    from benchmark.reference import ouro as ref
    sound = {name: getattr(ref, name) for name in ("layer", "states")}
    lr, eps = lm.ADAM["lr"], lm.ADAM["eps"]

    def with_(name, **fault):
        return lambda: patched(ref, name,
                               functools.partial(sound[name], **fault))

    def weights(of_pass):
        """Every pass's weight from its index alone, the gate unread."""
        def exit_weights(xs, p):
            return [jnp.full(x.shape[:-1], of_pass(t, len(xs)))
                    for t, x in enumerate(xs)]
        return lambda: patched(ref, "exit_weights", exit_weights)

    return {
        "three_passes_of_four": lambda: patched(ref, "T", ref.T - 1),
        "norm_between_passes_left_out": with_("states", norm_between=False),
        "head_after_the_last_pass_only": weights(
            lambda t, n: float(t == n - 1)),
        "gate_left_out_uniform_weights": weights(lambda t, n: 1.0 / n),
        "entropy_term_left_out": lambda: patched(ref, "BETA", 0.0),
        "output_norms_left_out": with_("layer", output_norms=False),
        "adam_lr_doubled": lambda: patched(
            lm, "adam_first_step",
            lambda p, g: p - 2 * lr * g / (jnp.abs(g) + eps)),
        "no_step": lambda: patched(lm, "adam_first_step", lambda p, g: p),
        "computed_in_bfloat16": lambda: ref.computed_in(jnp.bfloat16),
        "computed_in_float8_e4m3fn":
            lambda: ref.computed_in(jnp.float8_e4m3fn),
    }


def setup(config, traffic, seed):
    import importlib
    from benchmark.reference import ouro as ref
    family = importlib.import_module("benchmark.families." + config["family"])
    if family.reference is not ref:
        sys.exit("loss_limit_ouro: the faults are written for "
                 "reference/ouro.py")
    from autodist_tpu.models import lm
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    _, params, _, _ = lm.make_train_setup(
        family.model_config(config, seq), seq_len=seq, batch_size=batch,
        seed=seed)
    return family, params, family.host_batches(config, traffic, batch, seed, 2)


def readings(config, traffic, seed, rtol, only=None, emit=None):
    """[{"fault", "losses", "reading", "refused"}], the sound reference
    first (its reading is 0)."""
    import jax
    from benchmark.reference import ouro as ref
    _, params, pool = setup(config, traffic, seed)

    def losses():
        # a fresh function each time: JAX must trace under THIS fault
        return ref.train_check(
            lambda p, b: ref.nll_sum(p, b), ref.batch_weight, params,
            pool[0], pool[1], jax.devices()[:1])

    rows, sound = [], None
    planted = faults()
    for name in ["sound"] + [n for n in planted if not only or n in only]:
        with (contextlib.nullcontext() if name == "sound"
              else planted[name]()):
            got = [float(v) for v in losses()]
        sound = sound or got
        reading = max(abs(a - b) / abs(b) for a, b in zip(got, sound))
        row = {"fault": name, "seed": seed, "losses": got,
               "reading": reading, "rtol": rtol, "refused": reading > rtol}
        rows.append(row)
        if emit:
            emit(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--config-file")
    ap.add_argument("--traffic-set", action="append", default=[],
                    metavar="KEY=JSON")
    ap.add_argument("--only", help="comma-separated fault names")
    ap.add_argument("--out", help="append each row to this .jsonl file")
    args = ap.parse_args(argv)
    from benchmark import run
    _, cell, config, traffic = run.load_cell(args.workload, args.config_file)
    for item in args.traffic_set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)

    def emit(row):
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    rows = readings(config, traffic, args.seed, cell["loss_rtol"],
                    only=args.only and args.only.split(","), emit=emit)
    faulty = [r for r in rows[1:] if r["fault"] not in WITHIN]
    passed = [r["fault"] for r in faulty if not r["refused"]]
    print("loss_limit: %d of %d faults read over loss_rtol %g%s" % (
        len(faulty) - len(passed), len(faulty), cell["loss_rtol"],
        "; NOT refused: " + ", ".join(passed) if passed else ""),
        file=sys.stderr)


if __name__ == "__main__":
    main()
