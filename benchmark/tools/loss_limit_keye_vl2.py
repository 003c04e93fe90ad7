#!/usr/bin/env python3
"""What a keye_vl2 cell's ``loss_rtol`` refuses: the float32 reference with
ONE fault planted at a time, read exactly as ``drivers/train_fit.py`` reads
a run (the larger of the two relative distances of the loss at steps 0 and
1 from the sound reference's). ``tools/loss_limit.py`` is the same for an
olmoe cell and says what a reading means; the faults here are those a
Keye-VL-2.0 step can have and its float32 reference can state: the choice
of keys left out (dense causal attention), ``topk`` halved, every query
head on K/V head 0, the per-head norm left out, the rotation left out, the
indexer alone in bfloat16, gates not renormalised, one held expert lost,
another Adam step or none, and the whole step in a coarser precision
(``reference/olmoe.py:computed_in``).

    python3 benchmark/tools/loss_limit_keye_vl2.py --workload keye_vl2_train_1chip \\
        --seed 3700000601 --out chiprun_out/pr37/loss_limit.jsonl

Beside the loss, ``--agreement`` reads on batch 0 and layer 0 (whose
input, the embedding's rows, program and reference share) the share of the
causal (query, key) pairs on which the PROGRAM's choice (its own modules,
at the configuration's dtype) and the reference's agree: what tells a
bfloat16 indexer from a float32 one, which the loss does not.

The readings are differences between two float32 computations. On the
chip (``chiprun``; ``"highest"`` precision, which ``train_check`` sets) a
reading takes about half a minute. With ``--config-file`` and
``--traffic-set`` it runs at a tiny size; ``tests/test_keye_vl2_cell.py``
calls :func:`readings` that way.
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
    from benchmark.reference import keye_vl2 as ref
    from benchmark.reference import lm
    sound = {name: getattr(ref, name)
             for name in ("attention", "routing", "routed_ffn", "indexer")}
    lr, eps = lm.ADAM["lr"], lm.ADAM["eps"]

    def replaced(name, fn):
        return lambda: patched(ref, name, fn)

    def attention_with(**fault):
        return replaced("attention",
                        functools.partial(sound["attention"], **fault))

    def topk_halved(h, a, topk, rope_dim):
        return sound["attention"](h, a, topk // 2, rope_dim)

    def indexer_in_bfloat16(h, ix, rope_dim):
        with ref.computed_in(jnp.bfloat16):
            q, k, w = sound["indexer"](h, ix, rope_dim)
        r = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return r(q), r(k), w

    def not_renormalised(p, top_k):
        import jax
        _, chosen = jax.lax.top_k(p, top_k)
        return p * jnp.sum(jax.nn.one_hot(chosen, p.shape[-1]), axis=1)

    def one_expert_lost(h, m, top_k, held=None):
        held = tuple(range(m["gate_proj"].shape[0])) if held is None else held
        cut = {k: (v[:-1] if k.endswith("_proj") else v) for k, v in m.items()}
        return sound["routed_ffn"](h, cut, top_k, held[:-1])

    return {
        "choice_left_out": attention_with(choose=False),
        "topk_halved": replaced("attention", topk_halved),
        "every_query_head_on_kv_head_0": attention_with(
            kv_head_of=lambda i, group: 0),
        "head_norm_left_out": attention_with(head_norm=False),
        "rotation_left_out": replaced("rope", lambda x: x),
        "indexer_in_bfloat16": replaced("indexer", indexer_in_bfloat16),
        "gates_not_renormalised": replaced("routing", not_renormalised),
        "one_held_expert_lost": replaced("routed_ffn", one_expert_lost),
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
    from benchmark.reference import keye_vl2 as ref
    family = importlib.import_module("benchmark.families." + config["family"])
    if family.reference is not ref:
        sys.exit("loss_limit_keye_vl2: the faults are written for "
                 "reference/keye_vl2.py")
    from autodist_tpu.models import lm
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    # (not ``family.train_setup``: the reference is handed the
    # configuration's own numbers below, so a tiny ``topk`` may be read)
    _, params, _, _ = lm.make_train_setup(
        family.model_config(config, seq), seq_len=seq, batch_size=batch,
        seed=seed)
    return family, params, family.host_batches(config, traffic, batch, seed, 2)


def readings(config, traffic, seed, rtol, only=None, emit=None):
    """[{"fault", "losses", "reading", "refused"}], the sound reference
    first (its reading is 0)."""
    import jax
    from benchmark.reference import keye_vl2 as ref
    _, params, pool = setup(config, traffic, seed)
    topk = config["sa_config"]["topk"]
    rope_dim = config["assumed"]["indexer_rope_dim"]

    def losses():
        # a fresh function each time: JAX must trace under THIS fault
        return ref.train_check(
            lambda p, b: ref.nll_sum(p, b, config["num_experts_per_tok"],
                                     None, topk, rope_dim),
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


def agreement(config, traffic, seed, attention="auto"):
    """The share of layer 0's causal (query, key) pairs of batch 0 on which
    the program's choice and the reference's agree, and of the pairs either
    chose the share both did."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from autodist_tpu.models import layers, lm
    from benchmark.reference import keye_vl2 as ref
    family, params, pool = setup(config, traffic, seed)
    seq = traffic["seq"]
    ids = jnp.asarray(pool[0]["tokens"][:, :seq])
    cfg = dataclasses.replace(family.model_config(config, seq), num_layers=1)
    attn_fn = None
    if attention == "flash" or (attention == "auto" and lm.auto_flash_attention(
            seq, cfg.head_dim, jax.default_backend())):
        from autodist_tpu.ops.flash_attention import make_flash_attn_fn
        attn_fn = make_flash_attn_fn(causal=True)

    def program(params, ids):
        _, state = lm.TransformerLM(cfg, attn_fn=attn_fn).apply(
            params, ids, method=lm.TransformerLM.hidden,
            mutable=["intermediates", "counters", "losses"],
            capture_intermediates=lambda m, _: isinstance(
                m, layers.SparseIndexer))
        return state["intermediates"]["layer_0"]["MultiHeadAttention_0"][
            "indexer"]["__call__"][0] != 0

    def both(params, ids):
        got = program(params, ids)
        f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        choice = lambda: ref.kept_in_layer_0(  # noqa: E731
            f32, ids, config["sa_config"]["topk"],
            config["assumed"]["indexer_rope_dim"])
        want = choice()
        # the yardstick for ``agree``: the reference against itself with
        # its indexer alone in bfloat16
        with faults()["indexer_in_bfloat16"]():
            coarse = choice()
        seen = jnp.tril(jnp.ones((seq, seq), bool))[None]
        return (jnp.sum((got == want) & seen), jnp.sum(got & want),
                jnp.sum(got | want), jnp.sum(got), jnp.sum(want),
                jnp.sum((coarse == want) & seen))
    with jax.default_matmul_precision("highest"):
        same, inter, union, n_got, n_want, coarse_same = (
            int(x) for x in jax.jit(both)(params, ids))
    pairs = int(np.prod(ids.shape)) * (seq + 1) // 2
    return {"check": "layer_0_choice_agreement", "seed": seed,
            "causal_pairs": pairs, "agree": same / pairs,
            "chosen_by_both_over_either": inter / union,
            "program_chose": n_got, "reference_chose": n_want,
            "a_bfloat16_indexer_would_agree": coarse_same / pairs}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--config-file")
    ap.add_argument("--traffic-set", action="append", default=[],
                    metavar="KEY=JSON")
    ap.add_argument("--only", help="comma-separated fault names")
    ap.add_argument("--agreement", action="store_true",
                    help="read the layer-0 choice agreement, no fault")
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
    if args.agreement:
        emit(agreement(config, traffic, args.seed))
        return
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
