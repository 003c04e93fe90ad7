#!/usr/bin/env python3
"""What an olmoe cell's ``loss_rtol`` refuses: the float32 reference with
ONE fault planted at a time, read exactly as ``drivers/train_fit.py``
reads a run (the larger of the two relative distances of the loss at
steps 0 and 1 from the sound reference's).

    python3 benchmark/tools/loss_limit.py --workload olmoe_train_1chip \\
        --seed 2500000601 --out chiprun_out/loss_limit.jsonl

The program agrees with the sound reference to within its own noise (the
cell file gives the range the chip showed), so a program with the fault
reads what the faulty reference reads, give or take that noise. A limit
is worth its name only if every reading below is over it. The faults are
those a routed model's step can have and a float32 reference can state:
a loss term left out, routed pairs or their gates lost, another Adam
step, and the whole step computed in a coarser precision (every matmul
operand rounded, ``reference/olmoe.py:computed_in``).

Needs no chip: the readings are differences between two float32
computations, and the CPU gives them as the TPU's "highest" precision
does. With ``--config-file`` and ``--traffic-set`` (as ``run.py`` takes
them) it runs at a tiny size; ``tests/test_olmoe.py`` calls
:func:`readings` that way.
"""
import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@contextlib.contextmanager
def patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


# not a fault: the configuration's own precision, which the limit has to
# let through
WITHIN = ("computed_in_bfloat16",)


def faults():
    """{name: a context manager factory that plants it while the reference
    is traced}."""
    import jax.numpy as jnp
    from benchmark.reference import lm, olmoe
    sound_routing = olmoe.routing
    lr, eps = lm.ADAM["lr"], lm.ADAM["eps"]

    def routing_with(change):
        def routing(p, top_k):
            return change(p, top_k, *sound_routing(p, top_k))
        return lambda: patched(olmoe, "routing", routing)

    def over_capacity_dropped(p, top_k, weight, chosen):
        # Switch/GShard capacity at factor 2: an expert keeps its first
        # 2 T k / E pairs in token order, the others' gates are zero
        capacity = 2 * p.shape[0] * top_k // p.shape[1]
        rank = jnp.cumsum(weight > 0, axis=0)
        return weight * (rank <= capacity), chosen

    def adam_with(step):
        return lambda: patched(lm, "adam_first_step", step)

    return {
        "no_z_loss": lambda: patched(olmoe, "Z_COEF", 0.0),
        "no_load_balance_loss": lambda: patched(olmoe, "LB_COEF", 0.0),
        "eighth_expert_dropped": routing_with(
            lambda p, k, w, c: sound_routing(p, k - 1)),
        "over_capacity_2_dropped": routing_with(over_capacity_dropped),
        "gates_not_applied": routing_with(
            lambda p, k, w, c: ((w > 0).astype(w.dtype), c)),
        "gates_renormalised": routing_with(
            lambda p, k, w, c: (w / jnp.sum(w, axis=-1, keepdims=True), c)),
        "adam_lr_doubled": adam_with(
            lambda p, g: p - 2 * lr * g / (jnp.abs(g) + eps)),
        "adam_without_bias_correction": adam_with(
            # m = 0.1 g, v = 0.001 g^2 left as they are
            lambda p, g: p - lr * 0.1 * g / (jnp.sqrt(0.001) * jnp.abs(g)
                                             + eps)),
        "sgd_step": adam_with(lambda p, g: p - lr * g),
        "no_step": adam_with(lambda p, g: p),
        "computed_in_bfloat16": lambda: olmoe.computed_in(jnp.bfloat16),
        "computed_in_float8_e4m3fn":
            lambda: olmoe.computed_in(jnp.float8_e4m3fn),
        "computed_in_float8_e5m2": lambda: olmoe.computed_in(jnp.float8_e5m2),
    }


def readings(config, traffic, seed, rtol, only=None, emit=None):
    """[{"fault", "losses", "reading", "refused"}], the sound reference
    first (its reading is 0). ``emit`` is called with each row as it
    comes: a fault takes minutes at the published widths on a CPU."""
    import importlib
    import jax
    from benchmark.reference import olmoe
    family = importlib.import_module("benchmark.families." + config["family"])
    if family.reference is not olmoe:
        sys.exit("loss_limit: the faults are written for reference/olmoe.py")
    batch = traffic["batch_per_chip"]
    _, params, _ = family.train_setup(config, traffic, batch, seed)
    pool = family.host_batches(config, traffic, batch, seed, 2)

    def losses():
        # a fresh function each time: JAX must trace under THIS fault
        return olmoe.train_check(
            lambda p, b: olmoe.nll_sum(p, b), olmoe.batch_weight, params,
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
    sys.path[0] = ROOT
    from benchmark import run
    _, cell, config, traffic = run.load_cell(args.workload, args.config_file)
    for item in args.traffic_set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)

    def emit(row):
        print(json.dumps(row), flush=True)
        if args.out:
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
