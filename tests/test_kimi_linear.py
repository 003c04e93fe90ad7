"""Kimi-Linear on the normal training path (``LMConfig.kimi_linear_48b_a3b``):
KDA layers beside NoPE latent attention, a leading dense layer, a share of
sigmoid-routed experts with a shared expert, against the plain float32
reference ``benchmark/reference/kimi_linear.py`` at a tiny size, and
lm1b's and OLMoE's models held to what they built before the model got a
per-layer pattern.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (the chunked delta rule with its triangular
solve against the token-by-token recurrence; sorted rows through a grouped
matmul against every expert on every token; a fused rsqrt against a divide
by sqrt). ``RTOL`` 1e-5 of the largest entry holds logits, loss, the
chunked op alone and every gradient leaf of ONE layer of each kind. Through
the five-layer model the gradients' float32 rounding adds up layer by
layer (measured: 6e-5 at worst, on the first KDA layers' leaves; against a
float64 run of the reference the program and the float32 reference are
equally far, 2e-6 to 5e-6 a layer), so the whole model's leaves are held
to ``DEEP_RTOL`` 1e-4: a decay left out, beta fixed at 1, a lost expert or
a bfloat16 matmul misses either by orders of magnitude.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu import telemetry
from autodist_tpu.models import layers, lm
from autodist_tpu.ops import kda as kda_op
from autodist_tpu.ops.attention import reference_attention
from autodist_tpu.ops.flash_attention import flash_attention
from autodist_tpu.parallel import expert
from benchmark.reference import kimi_linear as ref

RTOL = 1e-5
DEEP_RTOL = 1e-4
TOP_K = 4
HELD = (0, 1, 2, 3)
SEQ = 32
# the KDA mixer's element-wise kernels (tests/test_kda.py holds them)
FUSED_PASSES = ("kda_pre_fwd", "kda_pre_bwd", "kda_post_fwd", "kda_post_bwd")


def tiny_config(**kw):
    """The cell's five layers (KDA + dense, KDA + MoE, KDA + MoE, MLA +
    MoE, KDA + MoE) at d 48: 4 KDA heads of 16, 4 latent heads (latent 24,
    16 + 8 score features, values of 16), dense width 96, 16 experts of
    width 32 of which 4 are held, top-4, one shared expert, vocab 256."""
    sizes = dict(vocab_size=256, d_model=48, num_heads=4, mlp_dim=32,
                 kda_num_heads=4, kda_head_dim=16, kv_lora_rank=24,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 dense_dim=96, num_experts=16, experts_per_token=TOP_K,
                 experts_held=HELD)
    sizes.update(kw)
    layers_ = sizes.pop("num_layers", 5)
    return dataclasses.replace(
        lm.LMConfig.kimi_linear_48b_a3b(num_layers=layers_, max_seq_len=64),
        **sizes)


def tiny_olmoe():
    return dataclasses.replace(
        lm.LMConfig.olmoe_1b_7b(num_layers=1, max_seq_len=16), vocab_size=64,
        d_model=32, num_heads=2, num_experts=4, experts_per_token=2,
        mlp_dim=16)


def close(got, want, rtol=RTOL):
    """Within rtol of the reference's largest entry, elementwise."""
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * max(np.abs(want).max(), 1e-30))


def flat(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def batches(n, rows=2, vocab=256, seed=1):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, SEQ + 1)).astype(np.int32)}
            for _ in range(n)]


def reference_loss(params, batch):
    return ref.nll_sum(params, batch, TOP_K, HELD) / ref.batch_weight(
        {"tokens": np.zeros(batch["tokens"].shape)})


def both_losses_and_grads(cfg):
    loss_fn, params, _, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    batch = batches(1)[0]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


@pytest.fixture(scope="module")
def loss_and_grads():
    return both_losses_and_grads(tiny_config())


KDA_LEAVES = ["kda/" + n for n in (
    "A_log", "dt_bias", "o_norm", "q_conv", "k_conv", "v_conv",
    "q_proj/kernel", "k_proj/kernel", "v_proj/kernel", "o_proj/kernel",
    "f_a_proj/kernel", "f_b_proj/kernel", "g_a_proj/kernel",
    "g_b_proj/kernel", "b_proj/kernel")]
MLA_LEAVES = ["mla/" + n for n in (
    "q_proj/kernel", "kv_a_proj/kernel", "kv_a_norm/scale",
    "kv_b_proj/kernel", "o_proj/kernel")]
MOE_LEAVES = ["moe/" + n for n in (
    "router", "gate_proj", "up_proj", "down_proj", "shared/gate_proj/kernel",
    "shared/up_proj/kernel", "shared/down_proj/kernel")]
DENSE_LEAVES = ["mlp/%s_proj/kernel" % n for n in ("gate", "up", "down")]
NORMS = ["RMSNorm_0/scale", "RMSNorm_1/scale"]
LAYER_LEAVES = [KDA_LEAVES + DENSE_LEAVES, KDA_LEAVES + MOE_LEAVES,
                KDA_LEAVES + MOE_LEAVES, MLA_LEAVES + MOE_LEAVES,
                KDA_LEAVES + MOE_LEAVES]
LEAVES = sorted(
    ["embed/embedding", "final_ln/scale", "lm_head/kernel"]
    + ["layer_%d/%s" % (i, leaf) for i, names in enumerate(LAYER_LEAVES)
       for leaf in names + NORMS])


def test_the_tiny_model_has_the_cells_layer_pattern(tiny):
    cfg, _, params, _, _ = tiny
    assert cfg.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert cfg.first_k_dense_replace == 1
    # every leaf but the routers' choice-only bias, which has no gradient
    bias = {"params/layer_%d/moe/e_score_correction_bias" % i
            for i in range(1, 5)}
    assert set(flat(params)) - bias == {"params/" + leaf for leaf in LEAVES}
    assert all(not np.any(flat(params)[b]) for b in bias)


def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(apply_fn(params, ids),
              ref.logits_fn(params, ids, TOP_K, HELD))


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
    close(got["params/" + leaf], want["params/" + leaf], DEEP_RTOL)


@pytest.mark.parametrize("kind, dense", [("kda", 1), ("kda", 0), ("mla", 0)])
def test_one_layer_of_each_kind_matches_to_1e_5(kind, dense):
    """Without four more layers' rounding behind it, every leaf of a layer
    is within 1e-5."""
    got_loss, want_loss, got, want = both_losses_and_grads(tiny_config(
        num_layers=1, layer_types=(kind,), first_k_dense_replace=dense))
    close(got_loss, want_loss)
    assert (kind + "/o_proj/kernel" in "".join(got)) and len(got) >= 10
    for name in got:
        close(got[name], want[name])


# ------------------------------------- latent attention through the kernel


@pytest.mark.parametrize("seq", [64, 40])
def test_flash_attention_takes_values_narrower_than_the_scores(seq):
    """MLA's shape: scores over 192 features, values of 128; forward and
    the three gradients against materialised scores (the kernel
    interpreted)."""
    r = np.random.RandomState(0)
    q, k = (jnp.asarray(r.randn(1, seq, 2, 192), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.randn(1, seq, 2, 128), jnp.float32)
    mask = jnp.tril(jnp.ones((seq, seq), bool))[None, None]
    weight = jnp.cos(jnp.arange(v.size).reshape(v.shape))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: jnp.sum(
            flash_attention(*a, causal=True) * weight), argnums=(0, 1, 2))(
            q, k, v)
        want = jax.value_and_grad(lambda *a: jnp.sum(
            reference_attention(*a, mask) * weight), argnums=(0, 1, 2))(
            q, k, v)
    close(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        close(a, b)


def test_the_latent_layer_through_the_kernel_gives_the_default_paths_gradients(
        tiny):
    cfg, _, params, _, batch = tiny
    kernel = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2, seed=0,
                                 attention="flash")[0]
    default = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2, seed=0,
                                  attention="default")[0]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(kernel))(params, batch)
        want = jax.jit(jax.value_and_grad(default))(params, batch)
    close(got[0], want[0])
    got, want = flat(got[1]), flat(want[1])
    for name in got:
        close(got[name], want[name], DEEP_RTOL)


@pytest.mark.parametrize("seq, backend, flash", [
    (8192, "tpu", True), (8192, "cpu", False), (2048, "tpu", True),
    (256, "tpu", False)])
def test_auto_attention_sees_the_latent_layers_192_features(seq, backend,
                                                            flash):
    assert lm.auto_flash_attention(seq, 128 + 64, backend) is flash


# ------------------------------------------------- the share of the experts


def routed_layer(rng, tokens, d, f, n_all):
    return (jnp.asarray(rng.randn(tokens, d), jnp.float32), {
        "router": jnp.asarray(rng.randn(d, n_all) / np.sqrt(d), jnp.float32),
        "e_score_correction_bias": jnp.zeros((n_all,), jnp.float32),
        "gate_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "up_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "down_proj": jnp.asarray(rng.randn(n_all, f, d) / np.sqrt(f), jnp.float32),
        "shared": {n + "_proj": {"kernel": jnp.asarray(
            rng.randn(*s) / np.sqrt(s[0]), jnp.float32)}
            for n, s in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d)))}})


def program_share(x, m, held, top_k=TOP_K, bias=None):
    """The routed part one chip computes: its stacks hold ``held`` only."""
    idx = jnp.asarray(held)
    routing = expert.Routing(
        "sigmoid", True, ref.SCALING,
        m["e_score_correction_bias"] if bias is None else bias)
    return expert.dropless_moe_ffn(
        x, m["router"], m["gate_proj"][idx], m["up_proj"][idx],
        m["down_proj"][idx], top_k, routing=routing, held=tuple(held))


def test_all_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test: 16 experts over four chips of 4. The routed
    outputs of the four shares, summed, plus the shared expert counted
    ONCE, equal the reference's whole layer with every expert held; and
    each share is the reference's same share."""
    x, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 16)
    shares = [(0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12, 13, 14, 15)]
    with jax.default_matmul_precision("highest"):
        parts = [program_share(x, m, held) for held in shares]
        uncut = ref.routed_ffn(x, m, TOP_K, held=tuple(range(16)))
        shared = ref.swiglu(x, m["shared"])
        for held, (out, lb, z, counts) in zip(shares, parts):
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            close(out, ref.routed_ffn(x, cut, TOP_K, held, shared=False))
            assert float(lb) == float(z) == 0.0 and counts.shape == (4,)
    close(sum(p[0] for p in parts) + shared, uncut)
    # every chosen pair is held by exactly one chip
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * TOP_K


def test_a_share_in_any_order_and_every_expert_held_agree():
    x, m = routed_layer(np.random.RandomState(4), 24, 32, 16, 8)
    with jax.default_matmul_precision("highest"):
        whole = program_share(x, m, tuple(range(8)))[0]
        parts = [program_share(x, m, held)[0]
                 for held in ((7, 2, 5), (0, 1, 3, 4, 6))]
    close(parts[0] + parts[1], whole)


def test_gates_are_sigmoid_scores_renormalised_and_scaled_and_the_bias_only_chooses():
    r = np.random.RandomState(5)
    logits = jnp.asarray(r.randn(6, 8), jnp.float32)
    # a bias that lifts expert 7 into every token's choice
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 10.0], jnp.float32)
    _, gate, chosen = expert.Routing("sigmoid", True, 2.446, bias).choose(
        logits, 3)
    scores = 1 / (1 + np.exp(-np.asarray(logits)))
    by_hand = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    assert np.array_equal(np.sort(chosen, -1), np.sort(by_hand, -1))
    assert np.all(np.any(np.asarray(chosen) == 7, axis=-1))
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=-1)
    close(gate, picked / picked.sum(-1, keepdims=True) * 2.446)   # no bias in
    close(jnp.sum(gate, -1), np.full(6, 2.446))
    _, plain, _ = expert.Routing("sigmoid", False, 1.0, bias).choose(logits, 3)
    close(plain, picked)


def test_the_layers_load_equals_a_count_by_hand(tiny):
    """``routed_pairs`` = pairs that chose a HELD expert, ``chosen_pairs``
    = all T x k, ``max_expert_pairs`` = the fullest HELD expert's, per
    routed layer; the dense layer counts nothing."""
    cfg, _, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    model = lm.TransformerLM(cfg)
    _, sown = model.apply(params, ids, mutable=["counters", "intermediates"],
                          capture_intermediates=lambda m, _: isinstance(
                              m, layers.MoEFeedForward))
    assert sorted(sown["counters"]) == ["layer_%d" % i for i in range(1, 5)]
    # layer 1's input is reproducible by hand: the reference's first layer
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][ids]
        x = ref.layer(x, p["layer_0"], TOP_K, HELD, 1e-5)
        x = x + ref.kda(ref.rms(x, p["layer_1"]["RMSNorm_0"]["scale"]),
                        p["layer_1"]["kda"], 1e-5)
        h = ref.rms(x, p["layer_1"]["RMSNorm_1"]["scale"]).reshape(-1, 48)
        scores = jax.nn.sigmoid(h @ p["layer_1"]["moe"]["router"])
    chosen = np.asarray(jax.lax.top_k(scores, TOP_K)[1])
    per_expert = np.bincount(chosen.reshape(-1), minlength=16)
    got = {k: int(v[0]) for k, v in sown["counters"]["layer_1"]["moe"].items()}
    assert got == {"chosen_pairs": 2 * SEQ * TOP_K,
                   "routed_pairs": int(per_expert[list(HELD)].sum()),
                   "max_expert_pairs": int(per_expert[list(HELD)].max())}


def cpu_spec(n):
    return autodist_tpu.resource_spec.ResourceSpec.from_dict({
        "nodes": [{"address": "127.0.0.1", "chief": True,
                   "cpus": list(range(n))}]})


def fit_two_steps(tiny, devices, pool):
    cfg, loss_fn, params, _, _ = tiny
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                               resource_spec=cpu_spec(devices))
    runner = ad.build(loss_fn, optax.adam(1e-3), params, pool[0])
    runner.init(params)
    with jax.default_matmul_precision("highest"):
        history = runner.fit(iter(pool), steps=2)
    return runner, [float(m["loss"]) for m in history]


@pytest.mark.parametrize("devices", [1, 2])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """make_train_setup -> AutoDist(AllReduce()).build -> Runner.fit, as
    lm1b and OLMoE go, against ``train_check`` (block-accumulated
    gradients, one float32 Adam step)."""
    _, _, params, _, _ = tiny
    pool = batches(2, rows=2)
    try:
        _, got = fit_two_steps(tiny, devices, pool)
    finally:
        autodist_tpu.reset()
    want = ref.train_check(
        lambda p, b: ref.nll_sum(p, b, TOP_K, HELD), ref.batch_weight,
        params, pool[0], pool[1], jax.devices())
    close(got[0], want[0])
    close(got[1], want[1], DEEP_RTOL)


@pytest.mark.parametrize("tracing", [True, False])
def test_the_share_reaches_the_counters_only_under_telemetry(tiny, tracing):
    telemetry.configure("1" if tracing else None)
    try:
        telemetry.get_recorder().clear()
        runner, _ = fit_two_steps(tiny, 1, batches(2, rows=2))
        counters = telemetry.get_recorder().counters()
        gauges = telemetry.get_recorder().gauges() \
            if hasattr(telemetry.get_recorder(), "gauges") else None
    finally:
        telemetry.configure(None)
        autodist_tpu.reset()
    moe = {k: v for k, v in counters.items() if k.startswith("moe.")}
    if not tracing:
        assert not moe
        return
    assert set(moe) == {"moe.routed_pairs", "moe.chosen_pairs",
                        "moe.max_expert_pairs"}
    # two steps x four routed layers x 2 x 32 tokens x top-4
    assert moe["moe.chosen_pairs"] == 2 * 4 * 2 * SEQ * TOP_K
    assert 0 < moe["moe.routed_pairs"] < moe["moe.chosen_pairs"]
    assert gauges is None or gauges.get("model.remat_blocks") == 0


def test_olmoes_loss_declares_the_counters_it_declared_before():
    loss_fn = lm.make_train_setup(tiny_olmoe(), seq_len=8, batch_size=2)[0]
    assert loss_fn.device_counters == ("moe.max_expert_pairs",
                                       "moe.routed_pairs")
    share = lm.make_train_setup(tiny_config(num_layers=2), seq_len=8,
                                batch_size=2)[0]
    assert share.device_counters == (
        "moe.max_expert_pairs", "moe.routed_pairs", "moe.chosen_pairs")
    dense_only = lm.make_train_setup(
        tiny_config(num_layers=1), seq_len=8, batch_size=2)[0]
    assert not hasattr(dense_only, "device_counters")


# ----------------------------------------------- per-block recompute, head


def test_recomputed_blocks_give_the_same_loss_and_gradients(tiny):
    cfg, _, params, _, batch = tiny
    ids, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]

    def loss(model, p):
        logits, sown = model.apply(p, ids, mutable=["counters"])
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                     targets[..., None], axis=-1)
        return -jnp.mean(picked), sown["counters"]

    with jax.default_matmul_precision("highest"):
        (want, load), want_g = jax.jit(jax.value_and_grad(functools.partial(
            loss, lm.TransformerLM(cfg)), has_aux=True))(params)
        (got, got_load), got_g = jax.jit(jax.value_and_grad(functools.partial(
            loss, lm.TransformerLM(cfg, remat_blocks=True)), has_aux=True))(
            params)
    close(got, want)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.all(a == b)), got_load, load))
    got_g, want_g = flat(got_g), flat(want_g)
    for name in got_g:
        close(got_g[name], want_g[name], DEEP_RTOL)
    remats = [str(jax.make_jaxpr(lambda p: model.apply(
        p, ids, mutable=["counters"])[0])(params)).count("remat2[")
        for model in (lm.TransformerLM(cfg),
                      lm.TransformerLM(cfg, remat_blocks=True))]
    assert remats[1] - remats[0] == 5   # one per block, beside the op's own


@pytest.mark.parametrize("against", ["blocks_not_recomputed", "jnp_form"])
def test_a_recomputed_block_of_wide_heads_runs_the_prologue_again_and_the_core_not(
        against, monkeypatch):
    """One KDA layer with a head of 128, its block recomputed: the
    gradient's jaxpr holds, by name, 1 ``kda_fwd``, 1 ``kda_bwd``, 2
    ``kda_pre_fwd`` and 1 ``kda_pre_bwd`` (the core's output and states
    are kept by name, its operands are made again), 2 ``kda_post_fwd`` and
    1 ``kda_post_bwd``. Loss and gradients are those of the same model
    with no block recomputed, and of the ``jnp`` form around the ``lax``
    core (what every head took until the passes were fused)."""
    from tests.test_flash_attention import kernel_calls
    cfg = tiny_config(num_layers=1, layer_types=("kda",), kda_num_heads=1,
                      kda_head_dim=128)
    params = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2, seed=0)[1]
    batch = batches(1)[0]
    ids, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]

    def run(remat_blocks):
        model = lm.TransformerLM(cfg, remat_blocks=remat_blocks)

        def loss(p):
            logits = model.apply(p, ids, mutable=["counters"])[0]
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), targets[..., None], axis=-1))

        with jax.default_matmul_precision("highest"):
            traced = jax.jit(jax.value_and_grad(loss)).trace(params)
            value, grads = traced.lower().compile()(params)
        return value, flat(grads), {
            name: kernel_calls(traced.jaxpr.jaxpr, name)
            for name in ("kda_fwd", "kda_bwd") + FUSED_PASSES}

    got, got_g, calls = run(True)
    assert calls == {"kda_fwd": 1, "kda_bwd": 1, "kda_pre_fwd": 2,
                     "kda_pre_bwd": 1, "kda_post_fwd": 2, "kda_post_bwd": 1}
    if against == "jnp_form":
        monkeypatch.setattr(kda_op, "runs_as_kernels", lambda dk, dv: False)
    want, want_g, want_calls = run(False)
    assert set(want_calls.values()) == ({0} if against == "jnp_form" else {1})
    close(got, want)
    for name in got_g:
        close(got_g[name], want_g[name], DEEP_RTOL)


def check_recomputed_flash_blocks(cfg, params, batch, against, monkeypatch,
                                 flash_layers, rtol):
    """``cfg``'s model with every block recomputed and the latent layers'
    cores on the flash kernel: its gradient holds one ``flash_fwd`` a
    flash layer, the forward pass's. Against the same model without
    recomputation ("blocks_not_recomputed") loss and gradients agree to
    ``rtol``; against the kernel's residuals left without their name, as
    before the name existed ("unnamed"), the recomputed blocks run the
    kernel again and loss and every gradient leaf are equal to the last
    bit (what is kept is what the second kernel call would have written)."""
    from autodist_tpu.ops import flash_attention as fa
    from tests.test_flash_attention import kernel_calls
    ids, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    attn_fn = fa.make_flash_attn_fn(causal=True)

    def run(remat_blocks):
        model = lm.TransformerLM(cfg, attn_fn=attn_fn,
                                 remat_blocks=remat_blocks)

        def loss(p):
            logits = model.apply(p, ids, mutable=["counters"])[0]
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), targets[..., None], axis=-1))

        with jax.default_matmul_precision("highest"):
            traced = jax.jit(jax.value_and_grad(loss)).trace(params)
            value, grads = traced.lower().compile()(params)
        return value, flat(grads), kernel_calls(traced.jaxpr.jaxpr,
                                                "flash_fwd")

    got, got_g, calls = run(True)
    unnamed = against == "unnamed"
    if unnamed:
        monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    want, want_g, want_calls = run(unnamed)
    assert calls == flash_layers
    assert want_calls == (2 * flash_layers if unnamed else flash_layers)
    if unnamed:
        assert got == want
    else:
        close(got, want)
    for name in got_g:
        if unnamed:
            np.testing.assert_array_equal(got_g[name], want_g[name])
        else:
            close(got_g[name], want_g[name], rtol)


@pytest.mark.parametrize("against", ["blocks_not_recomputed", "unnamed"])
def test_a_recomputed_block_runs_no_flash_forward_kernel(tiny, against,
                                                         monkeypatch):
    """One latent layer of five."""
    cfg, _, params, _, batch = tiny
    check_recomputed_flash_blocks(cfg, params, batch, against, monkeypatch,
                                  cfg.layer_types.count("mla"), DEEP_RTOL)


def test_serving_refuses_layers_whose_state_it_cannot_cache(tiny):
    cfg, _, params, _, batch = tiny
    with pytest.raises(NotImplementedError, match="recurrent state"):
        lm.TransformerLM(cfg).apply(
            params, batch["tokens"][:, :8], jnp.full((2,), 8),
            method=lm.TransformerLM.prefill)
