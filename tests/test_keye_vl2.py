"""Keye-VL-2.0-30B-A3B's language model on the normal training path
(``LMConfig.keye_vl2_30b_a3b``): query heads over fewer K/V heads with a
per-head norm, a sparse-attention indexer that chooses ``topk`` keys for
every query, and a share of softmax-routed experts with renormalised gates,
against the plain float32 reference ``benchmark/reference/keye_vl2.py`` at
a tiny size.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (a fused rsqrt against a divide by sqrt, one
einsum over all held experts against one expert after another, K/V heads
repeated against indexed) and in HOW a query's keys are chosen (the k-th
largest score bit by bit against a stable sort). ``RTOL`` 1e-5 of the
largest entry holds loss, logits and EVERY gradient leaf of the two-layer
model: a choice left out or halved, a wrong K/V grouping, a norm left out
or a bfloat16 matmul misses by orders of magnitude.
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
from autodist_tpu.ops import dsa
from autodist_tpu.ops import flash_attention as fa
from autodist_tpu.parallel import expert
from benchmark.reference import keye_vl2 as ref
from tests.test_kimi_linear import close, cpu_spec, flat

TOP_K = 3
HELD = (0, 1, 2, 3)
SEQ = 32
INDEX_TOPK = 8          # a query past position 7 chooses
ROPE_DIM = 4            # of the indexer's 8 features


def tiny_config(**kw):
    """The cell's layer at d 48 and two layers: 4 query heads over 2 K/V
    heads of 16, an indexer of 2 heads of 8 that keeps 8 keys a query
    (scores 8 queries at a time), 16 experts of width 32 of which 4 are
    held, top-3 renormalised, vocab 256."""
    sizes = dict(vocab_size=256, d_model=48, num_heads=4, head_dim=16,
                 num_kv_heads=2, mlp_dim=32, indexer_num_heads=2,
                 indexer_head_dim=8, indexer_topk=INDEX_TOPK,
                 indexer_q_chunk=8, indexer_rope_dim=ROPE_DIM,
                 num_experts=16, experts_per_token=TOP_K, experts_held=HELD)
    sizes.update(kw)
    return dataclasses.replace(
        lm.LMConfig.keye_vl2_30b_a3b(num_layers=sizes.pop("num_layers", 2),
                                     max_seq_len=64), **sizes)


def batches(n, rows=2, vocab=256, seed=1, seq=SEQ):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, seq + 1)).astype(np.int32)}
            for _ in range(n)]


def reference_loss(params, batch, held=HELD, topk=INDEX_TOPK):
    return ref.nll_sum(params, batch, TOP_K, held, topk, ROPE_DIM) \
        / ref.batch_weight({"tokens": np.zeros(batch["tokens"].shape)})


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


MIXER = "MultiHeadAttention_0/"
INDEXER_LEAVES = [MIXER + "indexer/" + n for n in (
    "wq/kernel", "wk/kernel", "weights_proj/kernel", "k_norm/scale",
    "k_norm/bias")]
TRAINED_LEAVES = [MIXER + n for n in (
    "query/kernel", "key/kernel", "value/kernel", "out/kernel",
    "q_norm/scale", "k_norm/scale")] + [
    "moe/router", "moe/gate_proj", "moe/up_proj", "moe/down_proj",
    "RMSNorm_0/scale", "RMSNorm_1/scale"]
LEAVES = sorted(["embed/embedding", "final_ln/scale", "lm_head/kernel"]
                + ["layer_%d/%s" % (i, leaf) for i in range(2)
                   for leaf in TRAINED_LEAVES + INDEXER_LEAVES])


def test_the_tiny_model_has_the_cells_layer(tiny):
    cfg, _, params, _, _ = tiny
    assert cfg.layer_types is None and cfg.first_k_dense_replace == 0
    assert cfg.router_activation == "softmax" and cfg.moe_renormalize
    assert not cfg.router_aux_loss_coef and not cfg.num_shared_experts
    assert set(flat(params)) == {"params/" + leaf for leaf in LEAVES}
    mixer = params["params"]["layer_1"]["MultiHeadAttention_0"]
    # 4 query heads, 2 K/V heads, a head's size of its own (4 x 16 != 48)
    assert mixer["query"]["kernel"].shape == (48, 4, 16)
    assert mixer["key"]["kernel"].shape == (48, 2, 16)
    assert mixer["out"]["kernel"].shape == (4, 16, 48)
    assert mixer["q_norm"]["scale"].shape == (16,)       # per head
    assert mixer["indexer"]["wq"]["kernel"].shape == (48, 2 * 8)
    assert mixer["indexer"]["wk"]["kernel"].shape == (48, 8)  # ONE key head
    assert params["params"]["layer_1"]["moe"]["gate_proj"].shape == (4, 48, 32)


def test_the_published_preset_is_the_catalogs_row():
    cfg = lm.LMConfig.keye_vl2_30b_a3b()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (48, 2048, 32, 4, 128, 151936)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.indexer_topk,
            cfg.indexer_q_chunk) == (16, 64, 2048, 512)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.mlp_dim,
            cfg.moe_renormalize, cfg.rope_theta) == (128, 8, 768, True, 1e7)
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-6
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale)


@pytest.mark.parametrize("bad", [
    dict(indexer_num_heads=2, indexer_head_dim=0),
    dict(indexer_num_heads=2, indexer_head_dim=8, indexer_topk=0),
    dict(qk_norm=True, qk_head_norm=True)])
def test_a_config_that_names_half_an_indexer_or_two_norms_is_refused(bad):
    with pytest.raises(ValueError):
        lm.LMConfig(**bad)


def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(apply_fn(params, ids),
              ref.logits_fn(params, ids, TOP_K, HELD, INDEX_TOPK, ROPE_DIM))


def test_loss_matches_the_reference(loss_and_grads):
    got, want, _, _ = loss_and_grads
    close(got, want)


@pytest.mark.parametrize("leaf", [l for l in LEAVES if "indexer" not in l])
def test_gradient_leaf_matches_the_reference(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert np.abs(want["params/" + leaf]).max() > 0
    close(got["params/" + leaf], want["params/" + leaf])


@pytest.mark.parametrize("leaf", [l for l in LEAVES if "indexer" in l])
def test_the_nll_has_no_gradient_to_the_indexer(loss_and_grads, leaf):
    """The choice is discrete and x enters the indexer with the gradient
    stopped: exactly zero, in the program and in the reference."""
    _, _, got, want = loss_and_grads
    assert not np.any(got["params/" + leaf])
    assert not np.any(want["params/" + leaf])


# ----------------------------------------------------- the choice of keys


def program_choice(cfg, params, ids):
    """Every layer's selection [B, S, S] as the model's own modules make
    it, by flax's ``capture_intermediates``."""
    _, state = lm.TransformerLM(cfg).apply(
        params, ids, mutable=["intermediates", "counters"],
        capture_intermediates=lambda m, _: isinstance(m, layers.SparseIndexer))
    return {name: np.asarray(
        layer["MultiHeadAttention_0"]["indexer"]["__call__"][0]) != 0
        for name, layer in state["intermediates"].items()}


@pytest.mark.parametrize("topk", [64, 32, 8, 1],
                         ids=["seq_below_topk", "seq_at_topk",
                              "seq_above_topk", "one_key"])
def test_the_chosen_sets_are_the_references(tiny, topk):
    """S = 32 below, at and above ``topk``: every key a query sees, then a
    true choice; the same sets as the reference's stable sort gives, and
    each query keeps min(its keys, topk)."""
    _, _, params, _, batch = tiny
    cfg = tiny_config(indexer_topk=topk)
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        got = program_choice(cfg, params, ids)["layer_0"]
        want = np.asarray(ref.kept_in_layer_0(params, ids, topk, ROPE_DIM))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got.sum(-1), np.minimum(np.arange(SEQ) + 1, topk)[None].repeat(2, 0))
    assert not np.any(np.triu(got[0], 1))                 # causal
    if topk >= SEQ:
        np.testing.assert_array_equal(got[0], np.tril(np.ones((SEQ, SEQ))))


def test_the_counters_count_the_chosen_and_the_causal_pairs(tiny):
    cfg, _, params, _, batch = tiny
    _, state = lm.TransformerLM(cfg).apply(
        params, batch["tokens"][:, :-1], mutable=["counters"])
    for layer in state["counters"].values():
        sown = layer["MultiHeadAttention_0"]["indexer"]
        assert int(sown["causal_pairs"][0]) == 2 * SEQ * (SEQ + 1) // 2
        assert int(sown["selected_pairs"][0]) == 2 * (
            INDEX_TOPK * (INDEX_TOPK + 1) // 2 + (SEQ - INDEX_TOPK) * INDEX_TOPK)


SCORES = {
    "all_equal": np.zeros((1, 6, 6), np.float32),
    "signed_zeros": np.asarray([[[0.0, -0.0, 0.0, -0.0, 1.0, -1.0]] * 6],
                               np.float32),
    "pairs_of_ties": np.asarray([[[2, 1, 2, 1, 3, 3.0]] * 6], np.float32),
    "negative_and_inf": np.asarray(
        [[[-1, -np.inf, -3, -1, -np.inf, -2.0]] * 6], np.float32),
    "random": np.random.RandomState(5).randn(2, 6, 6).astype(np.float32),
}


@pytest.mark.parametrize("k", [1, 2, 3, 6])
@pytest.mark.parametrize("scores", sorted(SCORES))
def test_ties_go_to_the_lower_key(scores, k):
    """``ops/dsa.py:choose`` (the k-th largest bit by bit, a cumulative
    count among the ties) against the reference's stable sort, on scores
    with ties at the threshold, -0.0 beside 0.0 and -inf."""
    s, rows = jnp.asarray(SCORES[scores]), jnp.arange(6)
    canonical = jnp.where(s == 0, 0.0, s)
    got = np.asarray(dsa.choose(canonical, rows, k))
    np.testing.assert_array_equal(got, np.asarray(ref.kept(canonical, rows, k)))
    np.testing.assert_array_equal(
        got.sum(-1), np.minimum(np.arange(6) + 1, k)[None].repeat(len(s), 0))
    if scores == "all_equal":                   # the k lowest keys
        np.testing.assert_array_equal(
            got[0], np.tril(np.ones((6, 6), bool)) & (np.arange(6) < k))


def test_the_kth_largest_is_the_sorts():
    r = np.random.RandomState(7)
    x = jnp.asarray(np.concatenate(
        [r.randn(5, 40), [[0.0] * 20 + [-0.0] * 20]]).astype(np.float32))
    bits = dsa._ordered_bits(jnp.where(x == 0, 0.0, x))
    for k in (1, 7, 40):
        want = jnp.sort(bits, axis=-1)[:, -k]
        np.testing.assert_array_equal(dsa.kth_largest(bits, k), want)
    # the order of the floats is the order of their bits
    order = np.argsort(np.asarray(x[0]), kind="stable")
    assert np.all(np.diff(np.asarray(bits[0])[order].astype(np.int64)) >= 0)


# --------------------------------------------------------- grouped heads


def test_grouped_heads_are_the_same_model_with_the_kv_heads_repeated(tiny):
    """4 query heads over 2 K/V heads against 4 over 4 whose K/V kernels
    are the two, each twice: the same logits (query head h reads K/V head
    h // 2)."""
    cfg, _, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    repeated = jax.tree_util.tree_map(lambda a: a, params)
    for i in range(cfg.num_layers):
        mixer = dict(repeated["params"]["layer_%d" % i]["MultiHeadAttention_0"])
        for name in ("key", "value"):
            mixer[name] = {"kernel": jnp.repeat(mixer[name]["kernel"], 2, 1)}
        repeated["params"]["layer_%d" % i] = dict(
            repeated["params"]["layer_%d" % i], MultiHeadAttention_0=mixer)
    with jax.default_matmul_precision("highest"):
        got = lm.TransformerLM(cfg).apply(params, ids, mutable=["counters"])[0]
        want = lm.TransformerLM(dataclasses.replace(cfg, num_kv_heads=4)).apply(
            repeated, ids, mutable=["counters"])[0]
    close(got, want)


def test_a_wrong_grouping_or_no_choice_is_another_model(tiny):
    """What the loss limit's faults plant is seen at 1e-5 here: every query
    head on K/V head 0, dense causal attention, topk halved, the per-head
    norm left out."""
    _, _, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    lp = params["params"]["layer_0"]
    h = ref.rms(params["params"]["embed"]["embedding"][ids],
                lp["RMSNorm_0"]["scale"])
    a = lp["MultiHeadAttention_0"]
    with jax.default_matmul_precision("highest"):
        sound = ref.attention(h, a, INDEX_TOPK, ROPE_DIM)
        for fault in (dict(kv_head_of=lambda i, group: 0),
                      dict(choose=False), dict(head_norm=False),
                      dict(topk=INDEX_TOPK // 2)):
            kw = dict(topk=INDEX_TOPK, rope_dim=ROPE_DIM)
            kw.update(fault)
            other = ref.attention(h, a, **kw)
            assert float(jnp.max(jnp.abs(other - sound))) \
                > 1e-3 * float(jnp.max(jnp.abs(sound)))


# ------------------------------------- the flash kernels and recomputation


def flash_run(cfg, params, batch, remat_blocks):
    ids, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    model = lm.TransformerLM(cfg, attn_fn=fa.make_flash_attn_fn(causal=True),
                             remat_blocks=remat_blocks)

    def loss(p):
        logits = model.apply(p, ids, mutable=["counters"])[0]
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), targets[..., None], axis=-1))

    with jax.default_matmul_precision("highest"):
        traced = jax.jit(jax.value_and_grad(loss)).trace(params)
        value, grads = traced.lower().compile()(params)
    return value, flat(grads), traced.jaxpr.jaxpr


def count(jaxpr, wanted):
    """Equations of a jaxpr, inner jaxprs included, that ``wanted`` takes."""
    return sum(bool(wanted(eqn)) + sum(
        count(inner, wanted) for inner in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


def choices(jaxpr):
    """How many times a jaxpr makes a layer's choice of keys: the
    cumulative count among the ties of ``ops/dsa.py:choose`` is the
    model's only one, once in each run of query blocks (SEQ / 8 = 4
    blocks go in ``dsa.SEGMENTS`` runs)."""
    made = count(jaxpr, lambda eqn: eqn.primitive.name == "cumsum")
    assert made % dsa.SEGMENTS == 0
    return made // dsa.SEGMENTS


def flash_forwards(jaxpr):
    return count(jaxpr, lambda eqn: eqn.primitive.name == "pallas_call"
                 and eqn.params["name"] == "flash_fwd")


def test_the_model_on_the_flash_kernels_is_the_model_on_xlas_scores(
        tiny, loss_and_grads):
    """The kernels with the choice as a selection and the K/V heads
    indexed, not repeated: loss and every gradient leaf of the XLA path."""
    cfg, _, params, _, batch = tiny
    value, grads, _ = flash_run(cfg, params, batch, False)
    loss, _, want, _ = loss_and_grads
    close(value, loss)
    for name in grads:
        close(grads[name], want[name], 2e-5)


@pytest.mark.parametrize("against", ["blocks_not_recomputed", "unnamed"])
def test_a_recomputed_block_makes_no_second_choice(tiny, against, monkeypatch):
    """Every block recomputed in the backward pass: the gradient's jaxpr
    holds ONE choice of keys and one ``flash_fwd`` a layer, the forward
    pass's, because the selection and the kernel's results are kept by
    name. Without the names the recomputed blocks make both again, and
    loss and gradients are equal to the last bit."""
    cfg, _, params, _, batch = tiny
    got, got_g, jaxpr = flash_run(cfg, params, batch, True)
    assert choices(jaxpr) == cfg.num_layers
    assert flash_forwards(jaxpr) == cfg.num_layers
    unnamed = against == "unnamed"
    if unnamed:
        monkeypatch.setattr(dsa, "checkpoint_name", lambda x, name: x)
        monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    want, want_g, other = flash_run(cfg, params, batch, unnamed)
    twice = 2 if unnamed else 1
    assert choices(other) == twice * cfg.num_layers
    assert flash_forwards(other) == twice * cfg.num_layers
    for name in got_g:
        if unnamed:
            np.testing.assert_array_equal(got_g[name], want_g[name])
        else:
            close(got_g[name], want_g[name], 2e-5)
    assert (got == want) if unnamed else abs(float(got - want)) < 1e-5


def test_serving_refuses_what_it_cannot_cache(tiny):
    cfg, _, params, _, batch = tiny
    with pytest.raises(NotImplementedError, match="K/V"):
        lm.TransformerLM(cfg).apply(
            params, batch["tokens"][:, :8], jnp.full((2,), 8),
            method=lm.TransformerLM.prefill)


# ------------------------------------------------- the share of the experts


def routed_layer(rng, tokens, d, f, n_all):
    return (jnp.asarray(rng.randn(tokens, d), jnp.float32), {
        "router": jnp.asarray(rng.randn(d, n_all) / np.sqrt(d), jnp.float32),
        "gate_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "up_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "down_proj": jnp.asarray(rng.randn(n_all, f, d) / np.sqrt(f), jnp.float32)})


def program_share(x, m, held, top_k=TOP_K):
    """The routed part one chip computes: its stacks hold ``held`` only,
    the gates renormalised over the chosen of ALL the router's outputs."""
    idx = jnp.asarray(held)
    return expert.dropless_moe_ffn(
        x[None], m["router"], m["gate_proj"][idx], m["up_proj"][idx],
        m["down_proj"][idx], top_k, jnp.float32,
        expert.Routing("softmax", True, 1.0, None), held=tuple(held))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts over eight chips of 2. The routed
    outputs of the eight shares, summed, equal the reference's whole layer
    with every expert held (no shared expert to count once); each share's
    output is the reference's same share; every chosen pair is held by
    exactly one chip."""
    x, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 16)
    shares = [(2 * i, 2 * i + 1) for i in range(8)]
    with jax.default_matmul_precision("highest"):
        parts = [program_share(x, m, held) for held in shares]
        uncut = ref.routed_ffn(x, m, TOP_K, held=tuple(range(16)))
        for held, (out, _, _, counts) in zip(shares, parts):
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            close(out.reshape(48, 32), ref.routed_ffn(x, cut, TOP_K, held))
            assert counts.shape == (2,)
    close(sum(p[0] for p in parts).reshape(48, 32), uncut)
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * TOP_K


# ---------------------------------------------------- the normal path, fit


@pytest.mark.parametrize("devices", [1, 2])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """make_train_setup -> AutoDist(AllReduce()).build -> Runner.fit, as
    the other four configurations go, against ``train_check``
    (block-accumulated gradients, one float32 Adam step): the indexer's
    weights are in the state and do not move."""
    cfg, loss_fn, params, _, _ = tiny
    pool = batches(2, rows=2)
    autodist_tpu.reset()
    try:
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                                   resource_spec=cpu_spec(devices))
        runner = ad.build(loss_fn, optax.adam(1e-3), params, pool[0])
        runner.init(params)
        with jax.default_matmul_precision("highest"):
            got = [float(m["loss"]) for m in runner.fit(iter(pool), steps=2)]
            want = ref.train_check(
                lambda p, b: ref.nll_sum(p, b, TOP_K, HELD, INDEX_TOPK,
                                         ROPE_DIM),
                ref.batch_weight, params, pool[0], pool[1], jax.devices()[:1])
        after = flat(runner.gather_params())
    finally:
        autodist_tpu.reset()
    close(np.asarray(got), np.asarray(want))
    before = flat(params)
    for name in before:
        moved = np.any(np.asarray(after[name]) != np.asarray(before[name]))
        assert moved == ("indexer" not in name), name
