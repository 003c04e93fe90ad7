"""Every preset of ``LMConfig`` at a tiny size, held to the differentiated
loss it traced to when ``tests/data/lm_pins.json`` was last written: the
jaxpr equation for equation (its text's hash and line count), loss and
gradient norm bit for bit, and the parameter tree. A change to ``models/``,
``ops/`` or ``parallel/`` that moves no preset's program passes here
untouched; one that does says which presets it moved.

To re-pin after a change that is meant to move a program::

    python tests/test_lm_pins.py --write

rewrites the presets' entries of the file (the other keys it holds stay),
and ``git diff tests/data/lm_pins.json`` shows which presets moved.
"""
import dataclasses
import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PINS = os.path.join(HERE, "data", "lm_pins.json")

if __name__ == "__main__":
    # the devices and the platform the tests see, before jax is imported
    sys.path.insert(0, os.path.dirname(HERE))
    import tests.conftest  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from autodist_tpu.models import lm  # noqa: E402

TINY_OLMOE = dict(vocab_size=256, d_model=64, num_heads=4, num_experts=8,
                  experts_per_token=2, mlp_dim=32)


def tiny_olmoe():
    return dataclasses.replace(
        lm.LMConfig.olmoe_1b_7b(num_layers=2, max_seq_len=16), **TINY_OLMOE)


def steps():
    """name -> (the presets of ``LMConfig`` the row stands for, how to build
    the tiny config, seq, rows, ``attention``). ``LMConfig.lm1b`` and
    ``LMConfig.tiny`` differ in widths alone, so one row holds both."""
    from tests.test_afmoe import tiny_config as afmoe
    from tests.test_deepseek_v2 import tiny_config as deepseek
    from tests.test_keye_vl2 import tiny_config as keye
    from tests.test_kimi_linear import tiny_config as kimi
    from tests.test_lfm2_moe import tiny_config as lfm2
    from tests.test_nemotron_h import tiny_config as nemotron_h
    from tests.test_ouro import tiny_config as ouro
    from tests.test_smallthinker import tiny_config as smallthinker
    return {
        "tiny_lm_step": (("lm1b", "tiny"), lm.LMConfig.tiny, 16, 4, "auto"),
        "tiny_olmoe_step": (("olmoe_1b_7b",), tiny_olmoe, 16, 4, "auto"),
        "tiny_olmoe_flash_step": (("olmoe_1b_7b",), tiny_olmoe, 16, 4,
                                  "flash"),
        "tiny_kimi_linear_step": (("kimi_linear_48b_a3b",), kimi, 32, 2,
                                  "auto"),
        "tiny_deepseek_v2_step": (("deepseek_v2_lite",), deepseek, 32, 2,
                                  "auto"),
        "tiny_deepseek_v2_flash_step": (("deepseek_v2_lite",), deepseek, 32,
                                        2, "flash"),
        "tiny_keye_vl2_step": (("keye_vl2_30b_a3b",), keye, 32, 2, "auto"),
        "tiny_keye_vl2_flash_step": (("keye_vl2_30b_a3b",), keye, 32, 2,
                                     "flash"),
        "tiny_lfm2_moe_step": (("lfm2_24b_a2b",), lfm2, 32, 2, "auto"),
        "tiny_ouro_step": (("ouro_2_6b",), ouro, 32, 2, "auto"),
        "tiny_nemotron_h_step": (("nemotron_twotower_30b_a3b",), nemotron_h,
                                 32, 2, "auto"),
        # (48 positions under a window of 10: the band's mask on XLA's
        # path; on the kernels' three 16-row tiles a side, 5 of 6 walked)
        "tiny_smallthinker_step": (("smallthinker_21b_a3b",), smallthinker,
                                   48, 2, "auto"),
        "tiny_smallthinker_flash_step": (("smallthinker_21b_a3b",),
                                         smallthinker, 48, 2, "flash"),
        # (the gate on both paths, W W G W W under a window of 10)
        "tiny_afmoe_step": (("trinity_mini_26b_a3b",), afmoe, 48, 2, "auto"),
        "tiny_afmoe_flash_step": (("trinity_mini_26b_a3b",), afmoe, 48, 2,
                                  "flash")}


def tree_digest(params):
    rows = sorted(("/".join(str(getattr(k, "key", k)) for k in path),
                   tuple(leaf.shape), str(leaf.dtype)) for path, leaf
                  in jax.tree_util.tree_flatten_with_path(params)[0])
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()


def traced(name):
    """The five fields of one preset's step, as ``make_train_setup`` builds
    it at seed 0."""
    _, make, seq, rows, attention = steps()[name]
    loss_fn, params, batch, _ = lm.make_train_setup(
        make(), seq_len=seq, batch_size=rows, seed=0, attention=attention)
    step = jax.jit(jax.value_and_grad(loss_fn)).trace(params, batch)
    text = str(step.jaxpr)
    loss, grads = step.lower().compile()(params, batch)
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    return {"jaxpr_sha256": hashlib.sha256(text.encode()).hexdigest(),
            "jaxpr_lines": text.count("\n"), "loss": float(loss).hex(),
            "gradnorm": float(norm).hex(),
            "param_tree_sha256": tree_digest(params)}


def pinned():
    with open(PINS) as f:
        return json.load(f)


@pytest.mark.parametrize("preset", sorted(steps()))
def test_a_presets_step_is_the_pinned_one(preset):
    assert traced(preset) == pinned()[preset], (
        "%s no longer traces to what tests/data/lm_pins.json holds. If the "
        "change was meant to move its program, re-pin with `python "
        "tests/test_lm_pins.py --write` and say in the PR which presets "
        "moved and why." % preset)


def test_every_preset_of_lmconfig_is_pinned():
    """A family that arrives with a new ``classmethod`` of ``LMConfig``
    arrives with a row of ``steps`` and an entry of the file."""
    presets = {name for name, attr in vars(lm.LMConfig).items()
               if isinstance(attr, classmethod)}
    assert presets == {p for row in steps().values() for p in row[0]}
    assert set(steps()) <= set(pinned())


def dumped(pins):
    """One preset an indented block; a parameter tree one leaf a line."""
    lines = []
    for key, value in pins.items():
        if isinstance(value, list):
            body = "[\n%s]" % ",\n".join("  " + json.dumps(row)
                                          for row in value)
        else:
            body = json.dumps(value, indent=1).replace("\n", "\n ")
        lines.append(" %s: %s" % (json.dumps(key), body))
    return "{\n%s\n}\n" % ",\n".join(lines)


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        sys.exit(__doc__)
    pins = pinned()
    for name in sorted(steps()):
        new = traced(name)
        print("%-28s %s" % (name, "as pinned" if pins.get(name) == new
                            else "MOVED"))
        pins[name] = new
    with open(PINS, "w") as f:
        f.write(dumped(pins))
