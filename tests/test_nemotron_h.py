"""The Nemotron-H tower of Nemotron-Labs-TwoTower-30B-A3B on the normal
training path (``LMConfig.nemotron_twotower_30b_a3b``): Mamba-2 state-space
layers, NoPE grouped-query attention and sigmoid-routed ``relu(up x)^2``
experts beside a shared one, EVERY layer one sub-layer behind one norm,
against the plain float32 reference ``benchmark/reference/nemotron_h.py``
at a tiny size. ``tests/test_nemotron_h_cell.py`` has the same model
through ``Runner.fit`` and the cell's files.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (the chunked dual form against the recurrence
token by token, one einsum over all held experts against one expert after
another, K/V heads repeated against indexed). ``RTOL`` 1e-5 of the largest
entry holds logits, loss and EVERY gradient leaf of the eight-layer model:
a decay left out, the wrong group, the gate after the norm, ``relu`` for
``relu^2`` or a bfloat16 matmul misses by orders of magnitude.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models import layers, lm
from autodist_tpu.ops import ssd
from autodist_tpu.parallel import expert
from benchmark.reference import kimi_linear as gated_ref
from benchmark.reference import nemotron_h as ref
from tests.test_kimi_linear import close, flat

TOP_K = 3
HELD = (0, 1, 2, 3)
GROUPS = 2
SEQ = 32
PATTERN = ("mamba2", "moe", "mamba2", "moe", "mamba2", "attention", "moe",
           "mamba2")


def tiny_config(**kw):
    """The cell's eight layers (``MEMEM*EM``) at d 48: Mamba-2 of 4 heads
    of 8 over 2 groups of 16 states, 4 taps, chunks of 8; 4 query heads
    over 2 K/V heads of 12; 16 experts of width 32 of which 4 are held,
    top-3 renormalised x 2.5, a shared expert of 40; an untied head over
    256 rows."""
    sizes = dict(vocab_size=256, d_model=48, num_heads=4, head_dim=12,
                 num_kv_heads=2, mlp_dim=32, shared_expert_dim=40,
                 num_experts=16, experts_per_token=TOP_K, experts_held=HELD,
                 mamba_num_heads=4, mamba_head_dim=8, mamba_n_groups=GROUPS,
                 ssm_state_size=16, mamba_chunk=8)
    sizes.update(kw)
    return dataclasses.replace(
        lm.LMConfig.nemotron_twotower_30b_a3b(
            num_layers=sizes.pop("num_layers", 8), max_seq_len=64), **sizes)


def batches(n, rows=2, vocab=256, seed=1, seq=SEQ):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, seq + 1)).astype(np.int32)}
            for _ in range(n)]


def reference_loss(params, batch, held=HELD):
    return ref.nll_sum(params, batch, TOP_K, held, GROUPS) / ref.batch_weight(
        {"tokens": np.zeros(batch["tokens"].shape)})


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    # (the filter's bias starts at zero: give it values, or a reference
    # that left it out would agree)
    p = dict(params["params"])
    for i, name in enumerate(sorted(p)):
        if "mamba" in p[name]:
            bias = p[name]["mamba"]["conv_bias"]
            p[name] = dict(p[name], mamba=dict(
                p[name]["mamba"], conv_bias=0.1 * jax.random.normal(
                    jax.random.PRNGKey(i), bias.shape)))
    return cfg, loss_fn, {"params": p}, apply_fn, batches(1)[0]


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    _, loss_fn, params, _, batch = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


MAMBA_LEAVES = ["mamba/" + n for n in (
    "in_proj/kernel", "conv", "conv_bias", "A_log", "D", "dt_bias", "norm",
    "out_proj/kernel")]
ATTN_LEAVES = ["MultiHeadAttention_0/%s/kernel" % n
               for n in ("query", "key", "value", "out")]
MOE_LEAVES = ["moe/" + n for n in (
    "router", "up_proj", "down_proj", "shared/up_proj/kernel",
    "shared/down_proj/kernel")]
KIND_LEAVES = {"mamba2": MAMBA_LEAVES, "attention": ATTN_LEAVES,
               "moe": MOE_LEAVES}
LEAVES = sorted(
    ["embed/embedding", "final_ln/scale", "lm_head/kernel"]
    + ["layer_%d/%s" % (i, leaf) for i, kind in enumerate(PATTERN)
       for leaf in KIND_LEAVES[kind] + ["RMSNorm_0/scale"]])


def test_the_published_pattern_gives_layers_0_to_7_one_sublayer_each(tiny):
    """``hybrid_override_pattern`` as published, cut from its start:
    ``MEMEM*EM``, every layer ONE norm and ONE sub-layer; no gate matrix
    in an expert, routed or shared."""
    cfg, _, params, _, _ = tiny
    full = lm.LMConfig.nemotron_twotower_30b_a3b()
    assert len(full.layer_types) == full.num_layers == 52
    assert [full.layer_types.count(k) for k in (
        "mamba2", "moe", "attention")] == [23, 23, 6]
    assert cfg.layer_types == full.layer_types[:8] == PATTERN
    assert lm.routed_layer_indices(cfg) == (1, 3, 6)
    # every leaf but the routers' choice-only bias, which has no gradient
    bias = {"params/layer_%d/moe/e_score_correction_bias" % i
            for i in (1, 3, 6)}
    assert set(flat(params)) - bias == {"params/" + leaf for leaf in LEAVES}
    p = params["params"]
    mixer = p["layer_0"]["mamba"]
    # [z | xBC | dt]: 32 | 32 + 2 x 2 x 16 | 4
    assert mixer["in_proj"]["kernel"].shape == (48, 32 + 96 + 4)
    assert mixer["conv"].shape == (4, 96) and mixer["norm"].shape == (32,)
    assert p["layer_5"]["MultiHeadAttention_0"]["key"]["kernel"].shape \
        == (48, 2, 12)
    assert p["layer_1"]["moe"]["up_proj"].shape == (4, 48, 32)
    assert p["layer_1"]["moe"]["shared"]["up_proj"]["kernel"].shape \
        == (48, 40)


def test_the_published_preset_is_the_catalogs_row():
    cfg = lm.LMConfig.nemotron_twotower_30b_a3b()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.max_seq_len) == (
        52, 2688, 32, 2, 128, 131072, 262144)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.ssm_state_size, cfg.mamba_conv_size, cfg.mamba_chunk) == (
        64, 64, 8, 128, 4, 128)
    assert (cfg.mlp_dim, cfg.shared_expert_dim, cfg.num_experts,
            cfg.experts_per_token, cfg.num_shared_experts) == (
        1856, 3712, 128, 6, 1)
    assert (cfg.router_activation, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.expert_gated) == (
        "sigmoid", True, 2.5, False)
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-5
    assert cfg.rope_theta is None and cfg.single_sublayer
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale
                or cfg.tie_embedding or cfg.router_aux_loss_coef)
    assert (ref.TOP_K, ref.RMS_EPS, ref.SCALING, ref.N_GROUPS) == (
        6, 1e-5, 2.5, 8)


MAMBA_SIZES = dict(mamba_num_heads=4, mamba_head_dim=8, mamba_n_groups=2,
                   ssm_state_size=16, mamba_conv_size=4, mamba_chunk=8)
ROUTED = dict(num_experts=4, experts_per_token=2)


@pytest.mark.parametrize("bad", [
    # half a Mamba layer: sizes without a layer, a layer without sizes,
    # heads that do not share the groups
    dict(MAMBA_SIZES),
    dict(num_layers=1, layer_types=("mamba2",), single_sublayer=True),
    dict(MAMBA_SIZES, num_layers=1, layer_types=("mamba2",),
         single_sublayer=True, mamba_n_groups=3),
    # kinds that are single sub-layers alone
    dict(MAMBA_SIZES, num_layers=1, layer_types=("mamba2",)),
    dict(ROUTED, num_layers=2, layer_types=("attention", "moe")),
    # what single sub-layers are not built with
    dict(single_sublayer=True),
    dict(ROUTED, num_layers=2, layer_types=("attention", "moe"),
         single_sublayer=True, loop_steps=2),
    dict(ROUTED, num_layers=2, layer_types=("attention", "moe"),
         single_sublayer=True, sandwich_norm=True),
    dict(num_layers=2, layer_types=("attention",) * 2, single_sublayer=True,
         indexer_num_heads=2, indexer_head_dim=8, indexer_topk=4),
    dict(ROUTED, num_layers=2, layer_types=("attention", "moe"),
         single_sublayer=True, first_k_dense_replace=1, dense_dim=64),
    dict(num_layers=2, layer_types=("attention", "moe"),
         single_sublayer=True),
    dict(num_layers=2, layer_types=("conv", "attention"), conv_size=3,
         single_sublayer=True),
    # the experts' form and the shared expert's width belong to a routed
    # feed-forward, and the width to a shared expert
    dict(expert_gated=False),
    dict(ROUTED, shared_expert_dim=32)])
def test_a_config_that_names_what_is_not_built_is_refused(bad):
    with pytest.raises(ValueError):
        lm.LMConfig(**bad)


def test_a_dense_layer_of_the_pattern_is_refused_by_name():
    """``nemotron_h``'s pattern has a fourth letter, '-', a dense
    feed-forward alone, which this model's string does not use."""
    assert set(lm.NEMOTRON_H_LAYERS) == {"M", "*", "E"}
    with pytest.raises(ValueError, match="layer_types"):
        lm.LMConfig.nemotron_twotower_30b_a3b(
            num_layers=2, layer_types=("mamba2", "-"))


def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(jax.jit(apply_fn)(params, ids), jax.jit(
            lambda p, i: ref.logits_fn(p, i, TOP_K, HELD, GROUPS))(
            params, ids))


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


def test_two_adam_steps_follow_the_reference(tiny):
    """The driver's check at a tiny size: the losses of step 0 and of step
    1 after one Adam(1e-3) step, against ``train_check``'s."""
    _, loss_fn, params, _, _ = tiny
    b0, b1 = batches(2, seed=7)
    opt = optax.adam(1e-3)
    with jax.default_matmul_precision("highest"):
        loss0, grads = jax.jit(jax.value_and_grad(loss_fn))(params, b0)
        updates, _ = opt.update(grads, opt.init(params), params)
        loss1 = jax.jit(loss_fn)(optax.apply_updates(params, updates), b1)
        want0, want1 = ref.train_check(
            lambda p, b: ref.nll_sum(p, b, TOP_K, HELD, GROUPS),
            ref.batch_weight, params, b0, b1, jax.devices())
    close(loss0, want0)
    close(loss1, want1)
    assert float(loss1) < float(loss0) + 1.0


def test_the_lean_head_and_the_plain_head_agree(tiny, loss_and_grads):
    """The cell's logits are exactly ``LEAN_HEAD_LOGIT_BYTES`` and take the
    chunked head; the tiny model's take the plain one."""
    cfg, _, params, _, batch = tiny
    lean, _, _, _ = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2,
                                        seed=0, lean_head=True)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(lean))(params, batch)
    got_loss, _, got, _ = loss_and_grads
    close(value, got_loss)
    for name, g in flat(grads).items():
        close(g, got[name])
    assert 4 * 1 * 8192 * 16384 == lm.LEAN_HEAD_LOGIT_BYTES


def test_serving_refuses_a_mamba_layer_by_name(tiny):
    cfg, _, params, _, batch = tiny
    model = lm.TransformerLM(cfg)
    with pytest.raises(NotImplementedError, match="mamba2"):
        model.apply(params, batch["tokens"][:, :8], jnp.full((2,), 8),
                    method=lm.TransformerLM.prefill)
    cache = jnp.zeros((2, 8, 64, 4, 12))
    with pytest.raises(NotImplementedError, match="mamba2"):
        model.apply(params, batch["tokens"][:, 0], cache, cache,
                    jnp.zeros((2,), jnp.int32),
                    method=lm.TransformerLM.decode_step)


def test_a_layer_that_is_its_feed_forward_alone_has_no_kv_rows():
    block = layers.TransformerBlock(
        2, 8, 16, norm="rmsnorm", num_experts=4, experts_per_token=2,
        only="ffn")
    x = jnp.zeros((1, 4, 16))
    params = block.init(jax.random.PRNGKey(0), x)
    assert set(params["params"]) == {"RMSNorm_0", "moe"}
    with pytest.raises(NotImplementedError, match="feed-forward alone"):
        block.apply(params, x, return_kv=True, mutable=["counters", "losses"])


# --------------------------------------------------------- the recurrence


def scan_inputs(rng, S, B=2, H=4, P=3, G=2, N=5):
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa: E731
    return (f(B, S, H, P), jnp.abs(f(B, S, H)) * 0.5 + 0.01,
            -jnp.abs(f(H)) - 0.2, f(B, S, G, N), f(B, S, G, N), f(H))


@pytest.mark.parametrize("S", [8, 32, 29], ids=[
    "one_chunk", "four_chunks", "not_whole_chunks"])
def test_the_chunked_form_is_the_recurrence_token_by_token(S):
    """Outputs and the gradient of EVERY input, at G < H (two heads read
    each group's B and C), chunks of 8."""
    r = np.random.RandomState(S)
    inputs = scan_inputs(r, S)
    weight = jnp.asarray(r.randn(*inputs[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, carry = ssd.ssd_chunked(*inputs, 8)
        want = ref.recurrence(*inputs)
        close(got, want)
        assert 0.0 < float(carry) < 1.0
        grads = [jax.grad(lambda *t: jnp.sum(fn(*t) * weight),
                          argnums=tuple(range(6)))(*inputs)
                 for fn in (lambda *t: ssd.ssd_chunked(*t, 8)[0],
                            ref.recurrence)]
    for g, w in zip(*grads):
        assert np.abs(w).max() > 0
        close(g, w)


def test_a_chunk_that_decays_far_stays_finite():
    """Cumulative sums of -40 a token: a ratio of two exponentials would
    be inf / inf; the masked differences are not."""
    r = np.random.RandomState(3)
    x, dt, a, b, c, d = scan_inputs(r, 16)
    with jax.default_matmul_precision("highest"):
        got, carry = ssd.ssd_chunked(x, dt + 20.0, a - 2.0, b, c, d, 8)
        close(got, ref.recurrence(x, dt + 20.0, a - 2.0, b, c, d))
        grad = jax.grad(lambda t: jnp.sum(
            ssd.ssd_chunked(x, t, a - 2.0, b, c, d, 8)[0]))(dt + 20.0)
    assert np.all(np.isfinite(grad)) and float(carry) == 0.0


def test_the_chunk_carry_is_what_of_an_incoming_state_survives():
    x, dt, a, b, c, d = scan_inputs(np.random.RandomState(4), 16)
    _, carry = ssd.ssd_chunked(x, dt, a, b, c, d, 8)
    want = np.mean(np.exp(np.sum(
        np.asarray(dt * a).reshape(2, 2, 8, 4), axis=2)))
    np.testing.assert_allclose(float(carry), want, rtol=1e-6)


def test_heads_that_do_not_share_the_groups_are_refused():
    x, dt, a, b, c, d = scan_inputs(np.random.RandomState(5), 8, H=3, G=2)
    with pytest.raises(ValueError, match="groups"):
        ssd.ssd_chunked(x, dt, a, b, c, d, 8)


def mamba_params(rng, d=16, H=4, P=4, G=2, N=8, taps=4):
    inner, conv_dim = H * P, H * P + 2 * G * N
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa: E731
    return {"in_proj": {"kernel": f(d, inner + conv_dim + H) / np.sqrt(d)},
            "conv": f(taps, conv_dim) * 0.5, "conv_bias": f(conv_dim) * 0.1,
            "A_log": jnp.log(jnp.abs(f(H)) + 1.0), "D": f(H),
            "dt_bias": f(H) * 0.5, "norm": 1.0 + 0.1 * f(inner),
            "out_proj": {"kernel": f(inner, d) / np.sqrt(inner)}}


def program_mamba(x, p, G=2, N=8, chunk=8):
    H = p["A_log"].shape[0]
    cfg = layers.Mamba2Config(H, p["norm"].shape[0] // H, G, N,
                              p["conv"].shape[0], chunk)
    return layers.Mamba2Mixer(cfg, 1e-5).apply({"params": p}, x,
                                               mutable=["counters"])[0]


def test_the_mamba_layer_cannot_see_the_future():
    """Change token t: nothing before t moves, everything from t on does
    (the state carries it)."""
    r = np.random.RandomState(0)
    p = mamba_params(r)
    x = jnp.asarray(r.randn(2, 24, 16), jnp.float32)
    t = 9
    other = x.at[:, t].add(1.0)
    for fn in (program_mamba, lambda x, p: ref.mamba(x, p, 2)):
        a, b = np.asarray(fn(x, p)), np.asarray(fn(other, p))
        np.testing.assert_array_equal(a[:, :t], b[:, :t])
        assert all(np.abs(a[:, s] - b[:, s]).max() > 1e-6
                   for s in range(t, 24))


def test_the_mixer_is_the_references():
    r = np.random.RandomState(1)
    p = mamba_params(r)
    x = jnp.asarray(r.randn(2, 19, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        close(program_mamba(x, p), ref.mamba(x, p, 2))


@pytest.mark.parametrize("fault", [
    dict(dt_bias=False), dict(skip=False), dict(gate_first=False),
    dict(conv_bias=False), dict(group_of=lambda h, per: 0),
    dict(decay_sign=0.0)])
def test_a_planted_fault_is_another_mixer(fault):
    """What the loss limit's faults plant is seen at 1e-5 here."""
    r = np.random.RandomState(2)
    p = mamba_params(r)
    x = jnp.asarray(r.randn(2, 16, 16), jnp.float32)
    sound, other = ref.mamba(x, p, 2), ref.mamba(x, p, 2, **fault)
    assert float(jnp.max(jnp.abs(other - sound))) \
        > 1e-3 * float(jnp.max(jnp.abs(sound)))


def test_the_mixers_parameters_are_nemotron_hs():
    x = jnp.zeros((1, 8, 16), jnp.float32)
    cfg = layers.Mamba2Config(4, 4, 2, 8, 4, 8)
    params = layers.Mamba2Mixer(cfg, 1e-5).init(jax.random.PRNGKey(0), x)
    p = params["params"]
    assert {k: v.shape for k, v in flat(p).items()} == {
        "in_proj/kernel": (16, 16 + 48 + 4), "out_proj/kernel": (16, 16),
        "conv": (4, 48), "conv_bias": (48,), "A_log": (4,), "D": (4,),
        "dt_bias": (4,), "norm": (16,)}
    # A_log = log U(1, 16), D = 1, softplus(dt_bias) in [1e-3, 1e-1]
    assert np.all((np.exp(p["A_log"]) >= 1) & (np.exp(p["A_log"]) <= 16))
    assert np.all(np.asarray(p["D"]) == 1) and not np.any(p["conv_bias"])
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert np.all((step >= 1e-3 * 0.999) & (step <= 1e-1 * 1.001))


# ------------------------------------------------- the share of the experts


def routed_layer(rng, tokens, d, f, n_all, shared_f, gated):
    w = lambda *s: jnp.asarray(rng.randn(*s) / np.sqrt(s[-2]), jnp.float32)  # noqa: E731
    names = ("gate", "up", "down") if gated else ("up", "down")
    m = {"router": w(d, n_all),
         "e_score_correction_bias": jnp.asarray(0.1 * rng.randn(n_all),
                                                jnp.float32),
         "up_proj": w(n_all, d, f), "down_proj": w(n_all, f, d),
         "shared": {n + "_proj": {"kernel": w(*(
             (shared_f, d) if n == "down" else (d, shared_f)))}
             for n in names}}
    if gated:
        m["gate_proj"] = w(n_all, d, f)
    return jnp.asarray(rng.randn(tokens, d), jnp.float32), m


def program_share(x, m, held, scaling, top_k=TOP_K):
    """The routed part one chip computes: its stacks hold ``held`` only,
    the choice by score + bias and the gates renormalised over the chosen
    of ALL the router's outputs; no ``gate_proj``, no gate."""
    idx = jnp.asarray(held)
    return expert.dropless_moe_ffn(
        x[None], m["router"],
        m["gate_proj"][idx] if "gate_proj" in m else None,
        m["up_proj"][idx], m["down_proj"][idx], top_k, jnp.float32,
        expert.Routing("sigmoid", True, scaling,
                       m["e_score_correction_bias"]),
        held=tuple(held))


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_all_shares_and_the_shared_expert_once_are_the_uncut_layer(gated):
    """The guide's share test: 16 experts over 16 chips of 1 (the
    deployment's 16 ways). The routed outputs of the 16 shares, summed,
    plus the shared expert counted ONCE (at a width of its own), equal the
    reference's whole layer with every expert held; and each share is the
    reference's same share. Both forms of the ONE routed layer."""
    its_ref = gated_ref if gated else ref
    x, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 16, 24, gated)
    shares = [(e,) for e in range(16)]
    with jax.default_matmul_precision("highest"):
        parts = [program_share(x, m, held, its_ref.SCALING)
                 for held in shares]
        uncut = its_ref.routed_ffn(x, m, TOP_K, held=tuple(range(16)))
        shared = gated_ref.swiglu(x, m["shared"]) if gated else ref.relu2_mlp(
            x, m["shared"]["up_proj"]["kernel"],
            m["shared"]["down_proj"]["kernel"])
        for held, (out, lb, z, counts) in zip(shares[:4], parts):
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            close(out[0], its_ref.routed_ffn(x, cut, TOP_K, held,
                                             shared=False))
            assert float(lb) == float(z) == 0.0 and counts.shape == (1,)
    close(sum(p[0][0] for p in parts) + shared, uncut)
    # every chosen pair is held by exactly one chip
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * TOP_K


def test_every_expert_held_is_the_sorted_form_without_a_gate():
    """``held=None`` takes the grouped matmuls (OLMoE's form): two of them
    an expert, not three."""
    x, m = routed_layer(np.random.RandomState(6), 16, 32, 16, 4, 24, False)
    routing = expert.Routing("sigmoid", True, ref.SCALING,
                             m["e_score_correction_bias"])
    with jax.default_matmul_precision("highest"):
        out, _, _, counts = expert.dropless_moe_ffn(
            x[None], m["router"], None, m["up_proj"], m["down_proj"], 2,
            jnp.float32, routing)
        close(out[0], ref.routed_ffn(x, m, 2, shared=False))
    assert int(jnp.sum(counts)) == 16 * 2


def test_the_held_form_without_a_gate_names_one_product():
    """Under the policy that saves ``parallel.expert.KEPT`` a recomputed
    non-gated layer keeps ONE [T, E, f] array where a gated one keeps
    two."""
    def kept(gated):
        x, m = routed_layer(np.random.RandomState(7), 16, 32, 8, 8, 8, gated)
        fn = jax.checkpoint(
            lambda x: jnp.sum(program_share(x, m, (0, 1, 2, 3), 1.0)[0]),
            policy=jax.checkpoint_policies.save_only_these_names(expert.KEPT))
        text = str(jax.make_jaxpr(jax.grad(fn))(x))
        return text.count("name=" + expert.KEPT)
    assert (kept(False), kept(True)) == (1, 2)
    assert lm.held_expert_kept_bytes(8192, (8, 2688, 1856), 2, 1) \
        == 8192 * 8 * 1856 * 2 == 243269632
    assert lm.dense_kept_bytes(8192, 3712, 2, 1) == 8192 * 3712 * 2
