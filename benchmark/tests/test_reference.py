"""Each plain reference agrees with the program's own model at a tiny size
(both in float32 here, so the tolerance is rounding only)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny(name):
    with open(os.path.join(HERE, "configs", name)) as f:
        return json.load(f)  # float32, so the tolerance is rounding only


@pytest.mark.parametrize("family_name,config_file", [
    ("lm", "lm_tiny.json")])
def test_loss_and_adam_step_match_the_program(family_name, config_file):
    import importlib
    import optax
    family = importlib.import_module("benchmark.families." + family_name)
    config = tiny(config_file)
    traffic = {"seq": 16}
    loss_fn, params, _ = family.train_setup(config, traffic, 8, seed=5)
    b0, b1 = family.host_batches(config, traffic, 8, 5, 2)
    opt = optax.adam(1e-3)
    with jax.default_matmul_precision("highest"):
        l0, grads = jax.value_and_grad(loss_fn)(params, b0)
        updates, _ = opt.update(grads, opt.init(params), params)
        l1 = loss_fn(optax.apply_updates(params, updates), b1)
    ref = family.reference
    r0, r1 = ref.train_check(ref.nll_sum, ref.batch_weight, params, b0, b1,
                             jax.devices()[:1], block_rows=4)
    assert r0 == pytest.approx(float(l0), rel=2e-5)
    assert r1 == pytest.approx(float(l1), rel=2e-5)


def test_lm_decode_deficit_is_zero_for_the_references_own_greedy_tokens():
    from benchmark.families import lm as family
    from benchmark.reference import lm as ref
    config = tiny("lm_tiny.json")
    _, params, _ = family.train_setup(config, {"seq": 16}, 2, seed=1)
    prompt = np.arange(3, 9, dtype=np.int32)
    seq = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(5):
            logits = ref.logits_fn(params, jnp.asarray([seq]))[0, -1]
            seq.append(int(jnp.argmax(logits)))
    worst, checked = ref.decode_deficits(params, [seq], [len(prompt)], 16)
    assert checked == 5 and worst == 0.0
    wrong = list(seq)
    wrong[-1] = (wrong[-1] + 1) % config["vocab_size"]
    worst, _ = ref.decode_deficits(params, [wrong], [len(prompt)], 16)
    assert worst > 0.0
