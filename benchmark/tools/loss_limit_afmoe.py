#!/usr/bin/env python3
"""What an afmoe cell's ``loss_rtol`` refuses: the float32 reference with ONE
fault planted at a time, read exactly as ``drivers/train_fit.py`` reads a run
(the larger of the two relative distances of the loss at steps 0 and 1 from
the sound reference's). ``tools/loss_limit.py`` is the same for an olmoe cell
and says what a reading means; the faults here are those a Trinity-Mini step
can have and its float32 reference can state: the attention's gate left out,
the gate read from the un-normed residual stream, the window ignored (every
layer global), a rotation on the global layer, the per-head QK-norm left out,
either output norm left out, ``route_scale`` left out, gates not
renormalised, the shared expert lost, one held expert lost, the embedding not
scaled, another Adam step than the one taken (none), and the whole step in a
coarser precision (``reference/olmoe.py:computed_in``; float8 also with the
cotangents left in float32, which stays finite).

    python3 benchmark/tools/loss_limit_afmoe.py \\
        --workload trinity_mini_train_1chip \\
        --seed 2100530601 --out chiprun_out/pr53/loss_limit.jsonl

The readings are differences between two float32 computations. On the chip
(``chiprun``; ``"highest"`` precision, which ``train_check`` sets) a reading
at the cell's 1 x 16,384 takes about two minutes. With ``--config-file`` and
``--traffic-set`` it runs at a tiny size; ``tests/test_afmoe_cell.py`` calls
:func:`readings` that way.
"""
import argparse
import contextlib
import functools
import json
import math
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
    import jax
    import jax.numpy as jnp
    from benchmark.reference import afmoe as ref
    from benchmark.reference import lm, olmoe
    sound = {name: getattr(ref, name) for name in (
        "visible", "layout", "layer", "attention", "routing", "routed_ffn",
        "embed")}

    def with_(name, **fault):
        return lambda: patched(ref, name,
                               functools.partial(sound[name], **fault))

    def swapped(name, other):
        return lambda: patched(ref, name, other)

    def one_expert_lost(m_in, m, top_k, held=None, shared=True):
        held = tuple(range(m["up_proj"].shape[0])) if held is None else held
        cut = {k: (v[:-1] if k in ("gate_proj", "up_proj", "down_proj")
                   else v) for k, v in m.items()}
        return sound["routed_ffn"](m_in, cut, top_k, held[:-1], shared)

    return {
        "gate_left_out": with_("attention", gated=False),
        "gate_reads_the_residual": with_("layer", gate_reads="residual"),
        "window_ignored": swapped(
            "visible", lambda rows, seq, window: sound["visible"](
                rows, seq, None)),
        "rotation_on_the_global_layer": swapped(
            "layout", lambda i: (True, sound["layout"](i)[1])),
        "qk_norm_left_out": with_("attention", qk_norm=False),
        "attn_out_norm_left_out": with_("layer", out_norms=("mlp",)),
        "mlp_out_norm_left_out": with_("layer", out_norms=("attn",)),
        "route_scale_left_out": with_("routing", scale=1.0),
        "gates_not_renormalised": with_("routing", renormalize=False),
        "shared_expert_lost": with_("routed_ffn", shared=False),
        "one_held_expert_lost": swapped("routed_ffn", one_expert_lost),
        "embedding_not_scaled": with_("embed", scaled=False),
        "no_step": lambda: patched(lm, "adam_first_step", lambda p, g: p),
        "computed_in_bfloat16": lambda: ref.computed_in(jnp.bfloat16),
        "computed_in_float8_e4m3fn":
            lambda: ref.computed_in(jnp.float8_e4m3fn),
        # the same rounding of every matmul operand with the COTANGENTS left
        # in float32 (a convert's transpose rounds them too, and those of an
        # NLL summed over 16,384 tokens pass e4m3fn's 448: not finite)
        "float8_e4m3fn_operands_float32_cotangents": lambda: patched(
            olmoe, "operand", lambda x: x + jax.lax.stop_gradient(
                x.astype(jnp.float8_e4m3fn).astype(jnp.float32) - x)),
    }


def setup(config, traffic, seed):
    import importlib
    from benchmark.reference import afmoe as ref
    family = importlib.import_module("benchmark.families." + config["family"])
    if family.reference is not ref:
        sys.exit("loss_limit_afmoe: the faults are written for "
                 "reference/afmoe.py")
    from autodist_tpu.models import lm
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    _, params, _, _ = lm.make_train_setup(
        family.model_config(config, seq), seq_len=seq, batch_size=batch,
        seed=seed)
    return family, params, family.host_batches(config, traffic, batch, seed, 2)


def readings(config, traffic, seed, rtol, only=None, emit=None):
    """[{"fault", "losses", "reading", "refused"}], the sound reference
    first (its reading is 0). A reading that is not finite is refused and
    recorded as null."""
    import jax
    from benchmark.reference import afmoe as ref
    _, params, pool = setup(config, traffic, seed)

    def losses():
        # a fresh function each time: JAX must trace under THIS fault
        return ref.train_check(
            lambda p, b: ref.nll_sum(p, b, window=config["sliding_window"]),
            ref.batch_weight, params,
            pool[0], pool[1], jax.devices()[:1])

    rows, sound = [], None
    planted = faults()
    for name in ["sound"] + [n for n in planted if not only or n in only]:
        with (contextlib.nullcontext() if name == "sound"
              else planted[name]()):
            got = [float(v) for v in losses()]
        sound = sound or got
        reading = max(abs(a - b) / abs(b) for a, b in zip(got, sound)) \
            if all(map(math.isfinite, got)) else math.nan
        row = {"fault": name, "seed": seed,
               "losses": [v if math.isfinite(v) else None for v in got],
               "reading": reading if math.isfinite(reading) else None,
               "rtol": rtol, "refused": not reading <= rtol}
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
