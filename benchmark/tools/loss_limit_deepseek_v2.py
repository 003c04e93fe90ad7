#!/usr/bin/env python3
"""What a deepseek_v2 cell's ``loss_rtol`` refuses: the float32 reference
with ONE fault planted at a time, read exactly as ``drivers/train_fit.py``
reads a run (the larger of the two relative distances of the loss at
steps 0 and 1 from the sound reference's). ``tools/loss_limit.py`` is the
same for an olmoe cell and says what a reading means; the faults here are
those a DeepSeek-V2 step can have and its float32 reference can state: the
rotation left out, YaRN's blend left out (plain theta), YaRN's temperature
left out of the softmax scale, the latent's norm left out, the shared
experts at half their width or left out, the balance loss left out, gates
renormalised, one held expert lost, another Adam step, and the whole step
in a coarser precision (``reference/olmoe.py:computed_in``).

    python3 benchmark/tools/loss_limit_deepseek_v2.py --workload deepseek_v2_lite_train_1chip \\
        --seed 3100000601 --out chiprun_out/pr31/loss_limit.jsonl

The readings are differences between two float32 computations. At the
published widths a reading takes a CPU minutes; on the chip (``chiprun``;
``"highest"`` precision, which ``train_check`` sets) about half a minute.
With ``--config-file`` and ``--traffic-set`` it runs at a tiny size;
``tests/test_deepseek_v2_cell.py`` calls :func:`readings` that way.
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
    from benchmark.reference import deepseek_v2 as ref
    from benchmark.reference import lm
    sound = {name: getattr(ref, name)
             for name in ("mla", "routing", "routed_ffn", "swiglu")}
    lr, eps = lm.ADAM["lr"], lm.ADAM["eps"]

    def replaced(name, fn):
        return lambda: patched(ref, name, fn)

    def one_expert_lost(h, m, top_k, sequences, held=None, shared=True):
        held = tuple(range(m["gate_proj"].shape[0])) if held is None else held
        cut = {k: (v[:-1] if k.endswith("_proj") else v) for k, v in m.items()}
        return sound["routed_ffn"](h, cut, top_k, sequences, held[:-1], shared)

    def renormalised(s, top_k):
        weight, chosen = sound["routing"](s, top_k)
        return weight / jnp.sum(weight, axis=-1, keepdims=True), chosen

    def half_as_wide(h, m):
        half = m["shared"]["down_proj"]["kernel"].shape[0] // 2
        return sound["swiglu"](h, {
            "gate_proj": {"kernel": m["shared"]["gate_proj"]["kernel"][:, :half]},
            "up_proj": {"kernel": m["shared"]["up_proj"]["kernel"][:, :half]},
            "down_proj": {"kernel": m["shared"]["down_proj"]["kernel"][:half]}})

    def plain_theta(dim, yarn):
        import numpy as np
        return (ref.ROPE_THETA ** (-2.0 * np.arange(dim // 2) / dim)
                ).astype(np.float32)

    return {
        "rotation_left_out": replaced("rotate", lambda x, yarn: x),
        "yarn_blend_left_out": replaced("yarn_inv_freq", plain_theta),
        "yarn_temperature_left_out": replaced(
            "softmax_scale", lambda width, yarn: width ** -0.5),
        "latent_norm_left_out": replaced(
            "mla", functools.partial(sound["mla"], norm_latent=False)),
        "shared_experts_half_as_wide": replaced("shared_ffn", half_as_wide),
        "shared_experts_left_out": replaced(
            "routed_ffn", functools.partial(sound["routed_ffn"], shared=False)),
        "balance_loss_left_out": lambda: patched(ref, "ALPHA", 0.0),
        "gates_renormalised": replaced("routing", renormalised),
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
    from benchmark.reference import deepseek_v2 as ref
    family = importlib.import_module("benchmark.families." + config["family"])
    if family.reference is not ref:
        sys.exit("loss_limit_deepseek_v2: the faults are written for "
                 "reference/deepseek_v2.py")
    batch = traffic["batch_per_chip"]
    _, params, _ = family.train_setup(config, traffic, batch, seed)
    pool = family.host_batches(config, traffic, batch, seed, 2)

    def losses():
        # a fresh function each time: JAX must trace under THIS fault
        return ref.train_check(
            lambda p, b: ref.nll_sum(p, b, config["num_experts_per_tok"]),
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
