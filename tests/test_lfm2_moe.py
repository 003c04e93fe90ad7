"""LFM2-24B-A2B on the normal training path (``LMConfig.lfm2_24b_a2b``):
gated short convolutions beside grouped-query attention with a per-head
norm, two leading dense layers, a share of sigmoid-routed experts and a
tied head, against the plain float32 reference
``benchmark/reference/lfm2_moe.py`` at a tiny size.
``tests/test_lfm2_moe_cell.py`` has the same model through ``Runner.fit``
and the other five presets held to what they built before the model got a
convolution and a tied head.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (a fused rsqrt against a divide by sqrt, one
einsum over all held experts against one expert after another, K/V heads
repeated against indexed). ``RTOL`` 1e-5 of the largest entry holds logits,
loss and EVERY gradient leaf of the six-layer model: a gate left out, the
taps reversed, a head untied, a norm left out or a bfloat16 matmul misses
by orders of magnitude.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import layers, lm
from autodist_tpu.parallel import expert
from benchmark.reference import lfm2_moe as ref
from tests.test_kimi_linear import close, flat

TOP_K = 3
HELD = (0, 1, 2, 3)
SEQ = 32


def tiny_config(**kw):
    """The cell's six layers (conv + dense, conv + dense, attention + MoE,
    conv + MoE, conv + MoE, conv + MoE) at d 48: 3 taps, 4 query heads over
    2 K/V heads of 12, dense width 96, 16 experts of width 32 of which 4
    are held, top-3 renormalised, a tied table of 256 rows."""
    sizes = dict(vocab_size=256, d_model=48, num_heads=4, num_kv_heads=2,
                 mlp_dim=32, dense_dim=96, num_experts=16,
                 experts_per_token=TOP_K, experts_held=HELD)
    sizes.update(kw)
    return dataclasses.replace(
        lm.LMConfig.lfm2_24b_a2b(num_layers=sizes.pop("num_layers", 6),
                                 max_seq_len=64), **sizes)


def batches(n, rows=2, vocab=256, seed=1, seq=SEQ):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, seq + 1)).astype(np.int32)}
            for _ in range(n)]


def reference_loss(params, batch, held=HELD):
    return ref.nll_sum(params, batch, TOP_K, held) / ref.batch_weight(
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
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


CONV_LEAVES = ["conv/" + n for n in ("in_proj/kernel", "conv",
                                     "out_proj/kernel")]
ATTN_LEAVES = ["MultiHeadAttention_0/" + n for n in (
    "query/kernel", "key/kernel", "value/kernel", "out/kernel",
    "q_norm/scale", "k_norm/scale")]
MOE_LEAVES = ["moe/" + n for n in ("router", "gate_proj", "up_proj",
                                   "down_proj")]
DENSE_LEAVES = ["mlp/%s_proj/kernel" % n for n in ("gate", "up", "down")]
NORMS = ["RMSNorm_0/scale", "RMSNorm_1/scale"]
LAYER_LEAVES = [CONV_LEAVES + DENSE_LEAVES, CONV_LEAVES + DENSE_LEAVES,
                ATTN_LEAVES + MOE_LEAVES] + [CONV_LEAVES + MOE_LEAVES] * 3
# (no ``lm_head``: the head is the embedding's transpose)
LEAVES = sorted(
    ["embed/embedding", "final_ln/scale"]
    + ["layer_%d/%s" % (i, leaf) for i, names in enumerate(LAYER_LEAVES)
       for leaf in names + NORMS])


def test_the_published_pattern_gives_layers_0_to_5_the_cut_states(tiny):
    """``layer_types`` (conv conv attention conv, ten times) and
    ``num_dense_layers`` 2 as published, cut from their start: conv +
    dense, conv + dense, attention + MoE, then conv + MoE three times."""
    cfg, _, params, _, _ = tiny
    full = lm.LMConfig.lfm2_24b_a2b()
    assert full.layer_types == ("conv", "conv", "attention", "conv") * 10
    assert full.layer_types.count("attention") == 10
    assert cfg.layer_types == full.layer_types[:6]
    assert cfg.first_k_dense_replace == full.first_k_dense_replace == 2
    p = params["params"]
    assert [("conv" if "conv" in p["layer_%d" % i] else "attention",
             "mlp" if "mlp" in p["layer_%d" % i] else "moe")
            for i in range(6)] == [
        ("conv", "mlp"), ("conv", "mlp"), ("attention", "moe"),
        ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    # every leaf but the routers' choice-only bias, which has no gradient
    bias = {"params/layer_%d/moe/e_score_correction_bias" % i
            for i in range(2, 6)}
    assert set(flat(params)) - bias == {"params/" + leaf for leaf in LEAVES}
    assert all(not np.any(flat(params)[b]) for b in bias)
    conv = p["layer_0"]["conv"]
    assert conv["in_proj"]["kernel"].shape == (48, 3 * 48)
    assert conv["conv"].shape == (3, 48)          # one 3-tap filter a channel
    mixer = p["layer_2"]["MultiHeadAttention_0"]
    assert mixer["query"]["kernel"].shape == (48, 4, 12)
    assert mixer["key"]["kernel"].shape == (48, 2, 12)
    assert mixer["q_norm"]["scale"].shape == (12,)       # per head
    assert p["layer_3"]["moe"]["gate_proj"].shape == (4, 48, 32)
    assert p["layer_3"]["moe"]["router"].shape == (48, 16)


def test_the_published_preset_is_the_catalogs_row():
    cfg = lm.LMConfig.lfm2_24b_a2b()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.max_seq_len) == (
        40, 2048, 32, 8, None, 65536, 128000)
    assert (cfg.conv_size, cfg.dense_dim, cfg.mlp_dim,
            cfg.num_experts, cfg.experts_per_token) == (
        3, 11776, 1536, 64, 4)
    assert (cfg.router_activation, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.num_shared_experts) == (
        "sigmoid", True, 1.0, 0)
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-5
    assert cfg.rope_theta == 1e6 and cfg.qk_head_norm and cfg.tie_embedding
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale
                or cfg.router_aux_loss_coef)
    assert (ref.TOP_K, ref.RMS_EPS, ref.ROPE_THETA) == (4, 1e-5, 1e6)


@pytest.mark.parametrize("bad", [
    dict(conv_size=3),                                    # no conv layer
    dict(num_layers=2, layer_types=("conv", "attention")),    # no taps
    dict(tie_embedding=True)])                            # head_bias is set
def test_a_config_that_names_half_a_convolution_is_refused(bad):
    with pytest.raises(ValueError):
        lm.LMConfig(**bad)


def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(jax.jit(apply_fn)(params, ids), jax.jit(
            lambda p, i: ref.logits_fn(p, i, TOP_K, HELD))(params, ids))


def test_loss_matches_the_reference_and_is_the_nll_alone(loss_and_grads):
    got, want, grads, _ = loss_and_grads
    close(got, want)
    # the bias chooses and does nothing else: no gradient reaches it
    assert all(not np.any(g) for name, g in grads.items()
               if name.endswith("e_score_correction_bias"))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert np.abs(want["params/" + leaf]).max() > 0
    close(got["params/" + leaf], want["params/" + leaf])


# ---------------------------------------------------------- the convolution


def conv_params(rng, d=8, taps=3):
    return {"in_proj": {"kernel": jnp.asarray(rng.randn(d, 3 * d), jnp.float32)},
            "conv": jnp.asarray(rng.randn(taps, d), jnp.float32),
            "out_proj": {"kernel": jnp.asarray(rng.randn(d, d), jnp.float32)}}


def program_conv(x, p):
    return layers.ShortConv(p["conv"].shape[0]).apply({"params": p}, x)


def test_the_convolution_is_causal():
    """Change token t: nothing before t moves, and t .. t + 2 do (three
    taps reach two tokens back)."""
    r = np.random.RandomState(0)
    p = conv_params(r)
    x = jnp.asarray(r.randn(2, 16, 8), jnp.float32)
    t = 9
    other = x.at[:, t].add(1.0)
    for fn in (program_conv, ref.conv_mixer):
        a, b = np.asarray(fn(x, p)), np.asarray(fn(other, p))
        np.testing.assert_array_equal(a[:, :t], b[:, :t])
        assert all(np.abs(a[:, t + j] - b[:, t + j]).max() > 1e-3
                   for j in range(3))
        np.testing.assert_array_equal(a[:, t + 3:], b[:, t + 3:])


def test_the_convolution_is_the_recurrence_over_its_last_two_inputs():
    """Token by token with a state of the last two gated inputs
    (``conv_L_cache`` - 1 rows a channel, what a decode slot would hold),
    zeros before the sequence's start."""
    r = np.random.RandomState(1)
    p = conv_params(r)
    x = jnp.asarray(r.randn(2, 11, 8), jnp.float32)
    w = np.asarray(p["conv"])
    state = np.zeros((2, 2, 8), np.float32)               # z_{t-2}, z_{t-1}
    want = []
    with jax.default_matmul_precision("highest"):
        for t in range(x.shape[1]):
            b, c, u = np.split(np.asarray(x[:, t] @ p["in_proj"]["kernel"]),
                               3, axis=-1)
            z = b * u
            conv = w[0] * state[:, 0] + w[1] * state[:, 1] + w[2] * z
            state = np.stack([state[:, 1], z], axis=1)
            want.append(np.asarray((c * conv) @ p["out_proj"]["kernel"]))
        want = np.stack(want, axis=1)
        close(program_conv(x, p), want)
        close(ref.conv_mixer(x, p), want)


def test_a_gate_left_out_or_the_taps_reversed_is_another_mixer():
    """What the loss limit's faults plant is seen at 1e-5 here."""
    r = np.random.RandomState(2)
    p = conv_params(r)
    x = jnp.asarray(r.randn(2, 16, 8), jnp.float32)
    sound = ref.conv_mixer(x, p)
    others = [ref.conv_mixer(x, p, b_gate=False),
              ref.conv_mixer(x, p, c_gate=False),
              ref.conv_mixer(x, dict(p, conv=p["conv"][::-1]))]
    for other in others:
        assert float(jnp.max(jnp.abs(other - sound))) \
            > 1e-3 * float(jnp.max(jnp.abs(sound)))


def test_the_mixer_has_two_projections_and_a_filter_and_no_bias():
    """``conv_bias`` is false in the published config and the equations
    state none: the mixer's parameters are its two kernels and one filter
    of ``conv_L_cache`` taps a channel."""
    x = jnp.zeros((1, 8, 8), jnp.float32)
    params = layers.ShortConv(3).init(jax.random.PRNGKey(0), x)["params"]
    assert {k: v.shape for k, v in flat(params).items()} == {
        "in_proj/kernel": (8, 24), "out_proj/kernel": (8, 8),
        "conv": (3, 8)}


# ---------------------------------------------------------------- the head


def test_the_tied_tables_gradient_is_the_embeddings_plus_the_heads(
        tiny, loss_and_grads):
    """The same model with an untied head whose kernel is E^T: the tied
    table's gradient is the sum of the two uses' (the lookup's rows and
    the logits' product), every other leaf's is the same."""
    cfg, _, params, _, batch = tiny
    untied_loss, _, _, _ = lm.make_train_setup(
        dataclasses.replace(cfg, tie_embedding=False), seq_len=SEQ,
        batch_size=2, seed=0)
    table = params["params"]["embed"]["embedding"]
    untied = {"params": dict(params["params"], lm_head={"kernel": table.T})}
    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(untied_loss))(untied, batch)
    got_loss, _, got, _ = loss_and_grads
    close(value, got_loss)
    grads = flat(grads)
    lookup = grads.pop("params/embed/embedding")
    head = grads.pop("params/lm_head/kernel")
    assert np.abs(lookup).max() > 0 and np.abs(head).max() > 0
    close(got["params/embed/embedding"], lookup + head.T)
    for name in grads:
        close(got[name], grads[name])


def test_the_lean_head_takes_a_tied_table(tiny, loss_and_grads):
    """The chunked head on the embedding's transpose: the plain head's
    loss and gradients (the cell's logits are under the lean head's
    bytes, a larger batch's are not)."""
    cfg, _, params, _, batch = tiny
    lean, _, _, _ = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2,
                                        seed=0, lean_head=True)
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(lean)(params, batch))
        value, grads = jax.jit(jax.value_and_grad(lean))(params, batch)
    assert "custom_vjp_call" in text
    got_loss, _, got, _ = loss_and_grads
    close(value, got_loss)
    for name, g in flat(grads).items():
        close(g, got[name])


def test_serving_refuses_what_it_cannot_cache(tiny):
    cfg, _, params, _, batch = tiny
    with pytest.raises(NotImplementedError, match="conv"):
        lm.TransformerLM(cfg).apply(
            params, batch["tokens"][:, :8], jnp.full((2,), 8),
            method=lm.TransformerLM.prefill)


# ------------------------------------------------- the share of the experts


def routed_layer(rng, tokens, d, f, n_all):
    return (jnp.asarray(rng.randn(tokens, d), jnp.float32), {
        "router": jnp.asarray(rng.randn(d, n_all) / np.sqrt(d), jnp.float32),
        "e_score_correction_bias": jnp.asarray(0.1 * rng.randn(n_all),
                                               jnp.float32),
        "gate_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "up_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "down_proj": jnp.asarray(rng.randn(n_all, f, d) / np.sqrt(f), jnp.float32)})


def program_share(x, m, held, top_k=TOP_K):
    """The routed part one chip computes: its stacks hold ``held`` only,
    the choice by score + bias and the gates renormalised over the chosen
    of ALL the router's outputs."""
    idx = jnp.asarray(held)
    return expert.dropless_moe_ffn(
        x[None], m["router"], m["gate_proj"][idx], m["up_proj"][idx],
        m["down_proj"][idx], top_k, jnp.float32,
        expert.Routing("sigmoid", True, 1.0, m["e_score_correction_bias"]),
        held=tuple(held))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts over eight chips of 2 (experts
    0-1, 2-3, ...). The routed outputs of the eight shares, summed, equal
    the reference's whole layer with every expert held (no shared expert
    to count once); each share's output is the reference's same share;
    every chosen pair is held by exactly one chip. The bias is not zero
    here: it moves the choice and not the gates."""
    x, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 16)
    shares = [(2 * i, 2 * i + 1) for i in range(8)]
    with jax.default_matmul_precision("highest"):
        parts = [program_share(x, m, held) for held in shares]
        uncut = ref.routed_ffn(x, m, TOP_K, held=tuple(range(16)))
        unbiased = ref.routed_ffn(
            x, dict(m, e_score_correction_bias=jnp.zeros(16)), TOP_K)
        for held, (out, _, _, counts) in zip(shares, parts):
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            close(out.reshape(48, 32), ref.routed_ffn(x, cut, TOP_K, held))
            assert counts.shape == (2,)
    close(sum(p[0] for p in parts).reshape(48, 32), uncut)
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * TOP_K
    assert float(jnp.max(jnp.abs(unbiased - uncut))) > 1e-3
