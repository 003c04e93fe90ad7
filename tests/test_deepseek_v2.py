"""DeepSeek-V2-Lite on the normal training path (``LMConfig.deepseek_v2_lite``):
latent attention with decoupled, YaRN-scaled rotary keys in every layer, a
leading dense layer, a share of softmax-routed experts beside two shared
ones and the sequence-wise balance loss, against the plain float32
reference ``benchmark/reference/deepseek_v2.py`` at a tiny size.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (a fused rsqrt against a divide by sqrt, one
einsum over all held experts against one expert after another, scores in
one square against blocks of queries). ``RTOL`` 1e-5 of the largest entry
holds loss, logits and EVERY gradient leaf of the three-layer model: a
blend left out, a per-batch balance loss, a renormalised gate, a lost
expert or a bfloat16 matmul misses by orders of magnitude.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.models import layers, lm
from autodist_tpu.parallel import expert
from benchmark.reference import deepseek_v2 as ref
from benchmark.tools.loss_limit import patched
from tests.test_kimi_linear import (check_recomputed_flash_blocks, close,
                                    cpu_spec, flat)

RTOL = 1e-5
TOP_K = 3
HELD = (0, 1, 2, 3)
SEQ = 48
# a window of 16 positions extended four times: sequences of 48 pass it,
# of the 8 rotary pairs the first keeps its frequency, the next two are
# blended and the rest interpolated (low 0, high 3), and the two mscales
# differ, so cos and sin carry a factor of their own
YARN = {"factor": 4, "original_max_position_embeddings": 16,
        "beta_fast": 2, "beta_slow": 0.25, "mscale": 0.5,
        "mscale_all_dim": 0.9}
# the balance loss at a hundred times the published weight, so that a
# wrong one (per batch, not per sequence) shows in the loss and the
# router's gradient at 1e-5
ALPHA = 0.1


def tiny_config(**kw):
    """The cell's pattern at d 48 and three layers (dense, MoE, MoE): 4
    latent heads (latent 24, 16 + 16 score features, values of 16), dense
    width 96, 16 experts of width 32 of which 4 are held, top-3, two shared
    experts, vocab 256."""
    sizes = dict(vocab_size=256, d_model=48, num_heads=4, mlp_dim=32,
                 kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=16,
                 v_head_dim=16, dense_dim=96, num_experts=16,
                 experts_per_token=TOP_K, experts_held=HELD,
                 router_aux_loss_coef=ALPHA,
                 rope_scaling=dict(YARN, type="yarn"))
    sizes.update(kw)
    return dataclasses.replace(
        lm.LMConfig.deepseek_v2_lite(num_layers=sizes.pop("num_layers", 3),
                                     max_seq_len=64), **sizes)


def batches(n, rows=2, vocab=256, seed=1):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, SEQ + 1)).astype(np.int32)}
            for _ in range(n)]


def reference_loss(params, batch, held=HELD, yarn=YARN):
    return ref.nll_sum(params, batch, TOP_K, held, yarn) / ref.batch_weight(
        {"tokens": np.zeros(batch["tokens"].shape)})


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    _, loss_fn, params, _, batch = tiny
    with jax.default_matmul_precision("highest"), patched(ref, "ALPHA", ALPHA):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


MLA_LEAVES = ["mla/" + n for n in (
    "q_proj/kernel", "kv_a_proj/kernel", "kv_a_norm/scale",
    "kv_b_proj/kernel", "o_proj/kernel")]
MOE_LEAVES = ["moe/" + n for n in (
    "router", "gate_proj", "up_proj", "down_proj", "shared/gate_proj/kernel",
    "shared/up_proj/kernel", "shared/down_proj/kernel")]
DENSE_LEAVES = ["mlp/%s_proj/kernel" % n for n in ("gate", "up", "down")]
NORMS = ["RMSNorm_0/scale", "RMSNorm_1/scale"]
LEAVES = sorted(
    ["embed/embedding", "final_ln/scale", "lm_head/kernel"]
    + ["layer_%d/%s" % (i, leaf) for i, names in enumerate(
        [DENSE_LEAVES, MOE_LEAVES, MOE_LEAVES])
       for leaf in MLA_LEAVES + names + NORMS])


def test_the_tiny_model_has_the_cells_layer_pattern(tiny):
    cfg, _, params, _, _ = tiny
    assert cfg.layer_types == ("mla",) * 3 and cfg.first_k_dense_replace == 1
    assert cfg.router_activation == "softmax" and cfg.seq_aux
    # a softmax router has no choice-only bias; the shared experts are ONE
    # SwiGLU of twice an expert's width; the stacks hold the share
    assert set(flat(params)) == {"params/" + leaf for leaf in LEAVES}
    moe = params["params"]["layer_1"]["moe"]
    assert moe["router"].shape == (48, 16)
    assert moe["gate_proj"].shape == (4, 48, 32)
    assert moe["shared"]["gate_proj"]["kernel"].shape == (48, 64)


def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(apply_fn(params, ids),
              ref.logits_fn(params, ids, TOP_K, HELD, YARN))


def test_loss_matches_the_reference(loss_and_grads):
    got, want, _, _ = loss_and_grads
    close(got, want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert np.abs(want["params/" + leaf]).max() > 0
    close(got["params/" + leaf], want["params/" + leaf])


def sown_balance_losses(cfg, params, ids):
    _, sown = lm.TransformerLM(cfg).apply(params, ids, mutable=["losses"])
    return [float(layer["moe"]["router_lb"][0])
            for layer in sown["losses"].values()]


def test_the_balance_loss_is_per_sequence_and_summed_over_routed_layers(tiny):
    """The two sequences of the batch route differently: the loss the
    layers sow is the reference's per-sequence one, the mean of the two
    sequences' own, and ``make_train_setup`` adds the routed layers' SUM
    times the coefficient (not their mean, OLMoE's convention) to the
    mean NLL."""
    cfg, loss_fn, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        per_layer = sown_balance_losses(cfg, params, ids)
        _, want = ref.forward(params, ids, TOP_K, HELD, YARN)
        nll = reference_loss(params, batch) - ref.ALPHA * want
        one_row = [ref.forward(params, ids[b:b + 1], TOP_K, HELD, YARN)[1]
                   for b in range(2)]
        loss = loss_fn(params, batch)
    assert len(per_layer) == 2                 # the dense layer sows none
    close(sum(per_layer), want)
    close(want, (one_row[0] + one_row[1]) / 2)
    assert abs(float(one_row[0]) - float(one_row[1])) > 1e-3
    close(loss, nll + ALPHA * sum(per_layer))


def test_a_per_batch_balance_loss_is_another_number():
    """f and P taken over all the rows at once (OLMoE's way, DeepSeek's
    ``seq_aux`` false) differ from the mean of the sequences' own."""
    r = np.random.RandomState(2)
    probs = jax.nn.softmax(jnp.asarray(r.randn(64, 8) * 2, jnp.float32))
    _, chosen = jax.lax.top_k(probs, 2)
    per_sequence = expert.sequence_balance_loss(probs, chosen, 4)
    per_batch = expert.sequence_balance_loss(probs, chosen, 1)
    close(per_sequence, ref.balance_loss(probs, chosen, 4))
    close(per_batch, ref.balance_loss(probs, chosen, 1))
    # ... which is OLMoE's L_lb over k
    counts = jnp.sum(jax.nn.one_hot(chosen, 8), axis=(0, 1))
    close(per_batch, expert.router_losses(jnp.log(probs), probs, counts)[0] / 2)
    assert abs(float(per_sequence) - float(per_batch)) > 1e-3


def test_an_even_router_reads_one():
    """Every expert chosen equally often in every sequence and uniform
    probabilities: f = 1, P = 1 / E, L = 1."""
    E, k, S, B = 8, 2, 16, 3
    probs = jnp.full((B * S, E), 1.0 / E)
    chosen = jnp.asarray([[(k * t + j) % E for j in range(k)]
                          for t in range(B * S)])
    assert float(expert.sequence_balance_loss(probs, chosen, B)) \
        == pytest.approx(1.0, abs=1e-6)
    assert float(ref.balance_loss(probs, chosen, B)) \
        == pytest.approx(1.0, abs=1e-6)
    # all tokens on the same k experts: E / k times the even router's
    same = jnp.tile(jnp.arange(k)[None], (B * S, 1))
    peaked = jnp.zeros((B * S, E)).at[:, :k].set(1.0 / k)
    assert float(expert.sequence_balance_loss(peaked, same, B)) \
        == pytest.approx(E / k, rel=1e-6)


# ------------------------------------------------------------------ YaRN


PUBLISHED = layers.YarnConfig(**{
    k: v for k, v in lm.LMConfig.deepseek_v2_lite().rope_scaling.items()
    if k != "type"})


def test_yarns_numbers_for_the_published_keys():
    """By hand: dim 64, theta 10000, factor 40 over a window of 4,096.
    cd(32) = 64 ln(4096 / 64 pi) / (2 ln 10000) = 10.47, cd(1) = 22.51: the
    ramp runs from pair 10 to pair 23; ms = 0.1 x 0.707 x ln 40 + 1."""
    inv = layers.rotary_inv_freq(64, 10000.0, PUBLISHED)
    assert inv.dtype == np.float32 and inv.shape == (32,)
    extra = lambda i: 10000.0 ** (-2 * i / 64)  # noqa: E731
    by_hand = {0: 1.0,                                 # as trained
               10: extra(10),                          # the ramp's start
               16: extra(16) * ((6 / 13) / 40 + 7 / 13),
               23: extra(23) / 40,                     # the ramp's end
               31: extra(31) / 40}                     # interpolated
    for i, want in by_hand.items():
        assert inv[i] == pytest.approx(want, rel=1e-6), i
    assert by_hand[16] == pytest.approx(0.0055002, rel=1e-4)
    np.testing.assert_array_equal(inv, ref.yarn_inv_freq(64, ref.YARN))
    ms = layers.yarn_mscale(40, 0.707)
    assert ms == pytest.approx(1.2608, abs=5e-5) == ref.ms(ref.YARN, 0.707)
    assert 192 ** -0.5 * ms ** 2 == pytest.approx(0.1147, abs=5e-5)
    assert ref.softmax_scale(192, ref.YARN) == pytest.approx(0.1147, abs=5e-5)
    assert layers.yarn_mscale(1, 0.707) == 1.0     # no extension, no factor


def test_the_ramp_lies_between_the_published_pairs():
    inv = layers.rotary_inv_freq(64, 10000.0, PUBLISHED).astype(np.float64)
    share = inv / (10000.0 ** (-np.arange(32) / 32))     # 1 .. 1 / 40
    np.testing.assert_allclose(share[:11], 1.0, rtol=1e-6)
    np.testing.assert_allclose(share[23:], 1 / 40, rtol=1e-6)
    assert np.all(np.diff(share[10:24]) < 0)


@pytest.mark.parametrize("what, yarn", [
    ("blend, and cos / sin carry ms(0.5) / ms(0.9)", YARN),
    ("equal mscales: the factor on cos / sin is 1", dict(YARN, mscale=0.9)),
    ("mscale_all_dim 0: no temperature", dict(YARN, mscale_all_dim=0.0)),
    ("no extension", dict(YARN, factor=1))])
def test_the_latent_mixer_is_the_references_past_the_window(what, yarn):
    """One latent mixer alone on sequences three windows long, output and
    every gradient, with plain RoPE (the blend left out) far away."""
    cfg = layers.MLAConfig(24, 16, 16, 16, 10000.0, layers.YarnConfig(**yarn))
    mixer = layers.LatentAttention(4, cfg, 1e-6)
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, SEQ, 48), jnp.float32)
    weight = jnp.cos(jnp.arange(x.size).reshape(x.shape))
    params = mixer.init(jax.random.PRNGKey(0), x, layers.causal_mask(SEQ),
                        jnp.arange(SEQ))

    def program(p, x):
        return jnp.sum(weight * mixer.apply(p, x, layers.causal_mask(SEQ),
                                            jnp.arange(SEQ)))

    def reference(p, x, yarn=yarn):
        return jnp.sum(weight * ref.mla(x, p["params"], yarn))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(program, argnums=(0, 1))(params, x)
        want = jax.value_and_grad(reference, argnums=(0, 1))(params, x)
        plain = reference(params, x, dict(yarn, factor=1, mscale_all_dim=0.0))
    close(got[0], want[0])
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        close(a, b)
    if yarn["factor"] > 1:
        assert abs(float(plain) - float(want[0])) > 1e-2 * abs(float(want[0]))


def test_rope_is_rotate_by_plain_frequencies():
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(2, 8, 3, 16), jnp.float32)
    pos = jnp.arange(8)
    inv = 10000.0 ** (-np.arange(8) / 8)
    close(layers.rope(x, pos, 10000.0),
          layers.rotate(x, pos, inv.astype(np.float32)))
    close(layers.rotate(x, pos, inv.astype(np.float32), 0.5),
          0.5 * layers.rope(x, pos, 10000.0))
    np.testing.assert_array_equal(layers.rotary_inv_freq(16, 10000.0, None),
                                  inv.astype(np.float32))


# ------------------------------------------------- the share of the experts


def routed_layer(rng, tokens, d, f, n_all):
    return (jnp.asarray(rng.randn(tokens, d), jnp.float32), {
        "router": jnp.asarray(rng.randn(d, n_all) / np.sqrt(d), jnp.float32),
        "gate_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "up_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "down_proj": jnp.asarray(rng.randn(n_all, f, d) / np.sqrt(f), jnp.float32),
        "shared": {n + "_proj": {"kernel": jnp.asarray(
            rng.randn(*s) / np.sqrt(s[0]), jnp.float32)}
            for n, s in (("gate", (d, 2 * f)), ("up", (d, 2 * f)),
                         ("down", (2 * f, d)))}})


def program_share(x, m, held, sequences, top_k=TOP_K):
    """The routed part one chip computes: its stacks hold ``held`` only."""
    idx = jnp.asarray(held)
    return expert.dropless_moe_ffn(
        x.reshape(sequences, -1, x.shape[-1]), m["router"],
        m["gate_proj"][idx], m["up_proj"][idx], m["down_proj"][idx], top_k,
        held=tuple(held), seq_aux=True)


def test_all_shares_the_shared_experts_once_and_the_loss_once_are_the_uncut_layer():
    """The guide's share test: 16 experts over four chips of 4, three
    sequences of 16 tokens. The routed outputs of the four shares, summed,
    plus the shared experts counted ONCE, equal the reference's whole
    layer with every expert held; every share computes the SAME balance
    loss, over all 16 outputs, and it is the uncut layer's, counted once;
    each share's output is the reference's same share."""
    x, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 16)
    shares = [(0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12, 13, 14, 15)]
    with jax.default_matmul_precision("highest"):
        parts = [program_share(x, m, held, 3) for held in shares]
        uncut, uncut_loss = ref.routed_ffn(x, m, TOP_K, 3,
                                           held=tuple(range(16)))
        shared = ref.shared_ffn(x, m)
        for held, (out, lb, z, counts) in zip(shares, parts):
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            want, loss = ref.routed_ffn(x, cut, TOP_K, 3, held, shared=False)
            close(out.reshape(48, 32), want)
            close(lb, loss)
            close(lb, uncut_loss)
            assert float(z) == 0.0 and counts.shape == (4,)
    close(sum(p[0] for p in parts).reshape(48, 32) + shared, uncut)
    # every chosen pair is held by exactly one chip
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * TOP_K


def test_a_softmax_share_and_the_sorted_form_over_all_experts_agree():
    """Every expert held, through ``_held_experts``, is the sorted dropless
    form (OLMoE's path) with the same router: output, both losses, load."""
    x, m = routed_layer(np.random.RandomState(4), 24, 32, 16, 8)
    args = (x, m["router"], m["gate_proj"], m["up_proj"], m["down_proj"], 2)
    with jax.default_matmul_precision("highest"):
        for seq_aux in (False, True):
            shape = (2, 12, 32) if seq_aux else (24, 32)
            held = expert.dropless_moe_ffn(
                args[0].reshape(shape), *args[1:], held=tuple(range(8)),
                seq_aux=seq_aux)
            whole = expert.dropless_moe_ffn(args[0].reshape(shape), *args[1:],
                                            seq_aux=seq_aux)
            for a, b in zip(held, whole):
                close(a, b)
            assert (float(whole[2]) == 0.0) is seq_aux


def test_gates_are_the_softmax_probabilities_themselves():
    r = np.random.RandomState(5)
    logits = jnp.asarray(r.randn(6, 8), jnp.float32)
    probs, gate, chosen = expert.Routing().choose(logits, 3)
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    close(probs, p)
    assert np.array_equal(np.sort(chosen, -1),
                          np.sort(np.argsort(-p, axis=-1)[:, :3], -1))
    close(gate, np.take_along_axis(p, np.asarray(chosen), -1))
    assert np.all(np.asarray(jnp.sum(gate, -1)) < 1.0)   # not renormalised
    _, renorm, _ = expert.Routing(renormalize=True, scaling_factor=2.0) \
        .choose(logits, 3)
    close(jnp.sum(renorm, -1), np.full(6, 2.0))


def test_the_layers_load_and_loss_leave_the_step_as_device_counters(tiny):
    """``routed_pairs`` = pairs that chose a HELD expert, ``chosen_pairs`` =
    all T x k, and ``moe.aux_loss`` = the routed layers' balance losses
    summed, unweighted, declared by the loss and added while it is
    traced."""
    from autodist_tpu.telemetry import device_counters
    cfg, loss_fn, params, _, batch = tiny
    assert loss_fn.device_counters == (
        "moe.max_expert_pairs", "moe.routed_pairs", "moe.chosen_pairs",
        "moe.aux_loss")
    with jax.default_matmul_precision("highest"):
        with device_counters.collect(loss_fn.device_counters) as got:
            loss_fn(params, batch)
        per_layer = sown_balance_losses(cfg, params, batch["tokens"][:, :-1])
    assert int(got["moe.chosen_pairs"]) == 2 * (2 * SEQ * TOP_K)
    assert 0 < int(got["moe.routed_pairs"]) < int(got["moe.chosen_pairs"])
    close(got["moe.aux_loss"], sum(per_layer))
    # OLMoE's and Kimi-Linear's losses declare what they did
    olmoe = lm.make_train_setup(dataclasses.replace(
        lm.LMConfig.olmoe_1b_7b(num_layers=1, max_seq_len=16), vocab_size=64,
        d_model=32, num_heads=2, num_experts=4, experts_per_token=2,
        mlp_dim=16), seq_len=16, batch_size=2)[0]
    assert olmoe.device_counters == ("moe.max_expert_pairs",
                                     "moe.routed_pairs")


# ------------------------------------------------------- through the Runner


@pytest.mark.parametrize("devices", [1, 2])
def test_two_fit_steps_follow_the_references_adam_step(tiny, devices):
    """``AutoDist(AllReduce()).build`` -> ``Runner.fit``: the loss at steps
    0 and 1 is the reference's one float32 Adam step, on one replica and
    on two (one sequence each: the balance loss is per sequence, so the
    replicas' mean is the batch's)."""
    cfg, loss_fn, params, _, _ = tiny
    pool = batches(2, seed=5)
    autodist_tpu.reset()
    try:
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                                   resource_spec=cpu_spec(devices))
        runner = ad.build(loss_fn, optax.adam(1e-3), params, pool[0])
        runner.init(params)
        with jax.default_matmul_precision("highest"):
            history = runner.fit(iter(pool), steps=2)
        counters = history[0]["counters"]
    finally:
        autodist_tpu.reset()
    with patched(ref, "ALPHA", ALPHA):
        want = ref.train_check(
            lambda p, b: ref.nll_sum(p, b, TOP_K, HELD, YARN),
            ref.batch_weight, params, pool[0], pool[1], jax.devices()[:1])
    close([float(m["loss"]) for m in history], want)
    assert set(counters) == {"moe.max_expert_pairs", "moe.routed_pairs",
                             "moe.chosen_pairs", "moe.aux_loss"}
    assert 1.0 <= float(counters["moe.aux_loss"]) / 2 < 16 / TOP_K


# ------------------------------------------------- what the model refuses


@pytest.mark.parametrize("change, says", [
    (dict(rope_scaling={"type": "linear", "factor": 4}), "rope_scaling"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(rope_scaling=dict(YARN, type="yarn", extra=1)), "rope_scaling"),
    (dict(rope_theta=None), "rope_theta"),
    (dict(layer_types=("mla", "attention", "mla")), "latent"),
    (dict(router_z_loss_coef=0.001), "z-loss"),
    (dict(router_activation="sigmoid"), "softmax router"),
    (dict(num_experts=0, experts_per_token=0, experts_held=None),
     "num_experts is 0"),
    (dict(experts_held=(0, 0)), "experts_held"),
    (dict(experts_held=(16,)), "experts_held"),
    (dict(experts_per_token=17), "experts_per_token")])
def test_an_architecture_the_model_cannot_build_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        tiny_config(**change)


@pytest.mark.parametrize("what, make", [
    ("softmax + shared + held (this model)", tiny_config),
    ("softmax + renormalised, scaled gates + shared, all held",
     lambda: tiny_config(moe_renormalize=True, routed_scaling_factor=2.5,
                         experts_held=None)),
    ("OLMoE's losses over a share", lambda: tiny_config(
        seq_aux=False, router_z_loss_coef=0.001)),
    ("sigmoid + shared + held (Kimi-Linear)",
     lambda: lm.LMConfig.kimi_linear_48b_a3b(num_layers=5,
                                             experts_held=(0, 1))),
    ("plain RoPE on the latent keys", lambda: tiny_config(rope_scaling=None)),
])
def test_the_router_and_the_share_are_independent(what, make):
    """Shared experts, renormalised or scaled gates and a share of the
    experts come with either router; the loss and its gradient are finite
    and a softmax router's losses reach the loss with a share too."""
    cfg = make()
    if cfg.d_model > 48:
        return      # (the published widths: accepted is all that is asked)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=2, seed=0)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    assert np.isfinite(float(loss))
    router = grads["params"]["layer_1"]["moe"]["router"]
    assert np.all(np.isfinite(router)) and np.abs(router).max() > 0
    bare = dataclasses.replace(cfg, router_aux_loss_coef=0.0,
                               router_z_loss_coef=0.0)
    nll = lm.make_train_setup(bare, seq_len=16, batch_size=2, seed=0)[0](
        params, batch)
    assert float(loss) > float(nll)


# ------------------------------------------ per-block recompute, the kernel


@pytest.mark.parametrize("against", ["blocks_not_recomputed", "unnamed"])
def test_a_recomputed_block_runs_no_flash_forward_kernel(tiny, against,
                                                         monkeypatch):
    """Latent attention in all three layers."""
    cfg, _, params, _, batch = tiny
    check_recomputed_flash_blocks(cfg, params, batch, against, monkeypatch,
                                  cfg.num_layers, RTOL)
