#!/usr/bin/env python3
"""What a kimi_linear cell's ``loss_rtol`` refuses: the float32 reference
with ONE fault planted at a time, read exactly as ``drivers/train_fit.py``
reads a run (the larger of the two relative distances of the loss at
steps 0 and 1 from the sound reference's). ``tools/loss_limit.py`` is the
same for an olmoe cell and says what a reading means; the faults here are
those a Kimi-Linear step can have and its float32 reference can state: a
KDA layer's decay left out, its write strength fixed at 1, the shared
expert left out, gates not renormalised, one held expert lost, the
routed experts' scaling left out, another Adam step, and the whole step
in a coarser precision (``reference/olmoe.py:computed_in``).

    python3 benchmark/tools/loss_limit_kimi_linear.py --workload kimi_linear_train_1chip \\
        --seed 2900000601 --out chiprun_out/pr29/loss_limit.jsonl

The readings are differences between two float32 computations. At the
published widths the reference's token-by-token recurrence takes a CPU
several minutes a reading; on the chip (``chiprun``; ``"highest"``
precision, which ``train_check`` sets) a reading takes about half a
minute. With ``--config-file`` and ``--traffic-set`` it runs at a tiny
size; ``tests/test_kimi_linear.py`` calls :func:`readings` that way.
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
    from benchmark.reference import kimi_linear as ref
    from benchmark.reference import lm
    sound = {name: getattr(ref, name)
             for name in ("kda", "routing", "routed_ffn")}
    lr, eps = lm.ADAM["lr"], lm.ADAM["eps"]

    def replaced(name, fn):
        return lambda: patched(ref, name, fn)

    def one_expert_lost(h, m, top_k, held=None, shared=True):
        held = tuple(range(m["gate_proj"].shape[0])) if held is None else held
        cut = {k: (v[:-1] if k.endswith("_proj") else v) for k, v in m.items()}
        return sound["routed_ffn"](h, cut, top_k, held[:-1], shared)

    def not_renormalised(scores, bias, top_k):
        weight = sound["routing"](scores, bias, top_k)
        return scores * (weight > 0) * ref.SCALING

    return {
        "kda_decay_left_out": replaced(
            "kda", functools.partial(sound["kda"], decay=False)),
        "kda_beta_fixed_at_1": replaced(
            "kda", functools.partial(sound["kda"], write_strength=False)),
        "shared_expert_left_out": replaced(
            "routed_ffn", functools.partial(sound["routed_ffn"], shared=False)),
        "gates_not_renormalised": replaced("routing", not_renormalised),
        "gates_not_scaled": lambda: patched(ref, "SCALING", 1.0),
        "one_held_expert_lost": replaced("routed_ffn", one_expert_lost),
        "adam_lr_doubled": lambda: patched(
            lm, "adam_first_step",
            lambda p, g: p - 2 * lr * g / (jnp.abs(g) + eps)),
        "no_step": lambda: patched(lm, "adam_first_step", lambda p, g: p),
        "computed_in_bfloat16": lambda: ref.computed_in(jnp.bfloat16),
        "computed_in_float8_e4m3fn":
            lambda: ref.computed_in(jnp.float8_e4m3fn),
    }


def readings(config, traffic, seed, rtol, only=None, emit=None):
    """[{"fault", "losses", "reading", "refused"}], the sound reference
    first (its reading is 0)."""
    import importlib
    import jax
    from benchmark.reference import kimi_linear as ref
    family = importlib.import_module("benchmark.families." + config["family"])
    if family.reference is not ref:
        sys.exit("loss_limit_kimi_linear: the faults are written for "
                 "reference/kimi_linear.py")
    batch = traffic["batch_per_chip"]
    _, params, _ = family.train_setup(config, traffic, batch, seed)
    pool = family.host_batches(config, traffic, batch, seed, 2)

    def losses():
        # a fresh function each time: JAX must trace under THIS fault
        return ref.train_check(
            lambda p, b: ref.nll_sum(p, b, config["num_experts_per_token"]),
            ref.batch_weight, params, pool[0], pool[1], jax.devices()[:1])

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
