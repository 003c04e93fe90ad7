"""The held experts' gate and up products kept by name across a recomputed
block (``parallel/expert.py:KEPT``): what a saving policy takes out of the
differentiated layer, that the values kept are the forward's to the last
bit, and the rule that says how many routed layers keep them
(``models/lm.py:auto_kept_layers``, whose other counts are
``tests/test_dense_products_kept.py``'s)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import lm
from autodist_tpu.parallel import expert

HELD = (1, 6)          # 2 of 8
T, D, F, E_ALL, TOP_K = 24, 16, 12, 8, 3


def count(jaxpr, match, rematted=False, inside=False):
    """Equations ``match`` accepts in a jaxpr and its inner ones; with
    ``rematted`` only those inside a checkpoint's recomputed part."""
    total = 0
    for eqn in jaxpr.eqns:
        within = inside or eqn.primitive.name in ("checkpoint", "remat2")
        total += match(eqn) and (within or not rematted)
        total += sum(count(inner, match, rematted, within)
                     for inner in jax.core.jaxprs_in_params(eqn.params))
    return total


def dots(jaxpr, rematted=False):
    return count(jaxpr, lambda e: e.primitive.name == "dot_general", rematted)


def names_kept(jaxpr, name=expert.KEPT):
    """``name`` equations carrying ``name``: (in all, inside a checkpoint,
    whose policy is there to save them)."""
    match = lambda e: (e.primitive.name == "name"  # noqa: E731
                       and e.params["name"] == name)
    return count(jaxpr, match), count(jaxpr, match, rematted=True)


def layer_inputs(activation):
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randn(T, D), jnp.float32)
    logits = jnp.asarray(rng.randn(T, E_ALL), jnp.float32)
    routing = expert.Routing(activation, renormalize=activation == "sigmoid")
    _, gate, chosen = routing.choose(logits, TOP_K)
    stacks = [jnp.asarray(rng.randn(*shape) / 4, jnp.float32) for shape in (
        (len(HELD), D, F), (len(HELD), D, F), (len(HELD), F, D))]
    cot = jnp.asarray(rng.randn(T, D), jnp.float32)

    def f(tokens, gate, w_gate, w_up, w_down):
        out, _ = expert._held_experts(tokens, gate, chosen, w_gate, w_up,
                                      w_down, HELD)
        return jnp.sum(out * cot)
    return f, (tokens, gate, *stacks)


@pytest.mark.parametrize("activation", ["softmax", "sigmoid"])
def test_a_checkpoint_that_keeps_the_name_makes_no_product_twice(activation):
    """The differentiated held experts under ``jax.checkpoint``: 3 products
    forward and 6 backward; a policy that saves ``expert.KEPT`` leaves
    those 9, the 6 backward ones the only ones in the recomputed part;
    without it the gate's and the up projection's are made again there
    (11; the down projection's second output is dead code). Loss and every
    gradient are the unwrapped function's to the last bit either way."""
    f, args = layer_inputs(activation)
    wrt = tuple(range(len(args)))
    keep = jax.checkpoint_policies.save_only_these_names(expert.KEPT)
    other = jax.checkpoint_policies.save_only_these_names("another_name")
    how = {"plain": f, "kept": jax.checkpoint(f, policy=keep),
           "other_name": jax.checkpoint(f, policy=other),
           "recomputed": jax.checkpoint(f)}
    grads = {k: jax.value_and_grad(g, wrt) for k, g in how.items()}
    jaxprs = {k: jax.make_jaxpr(g)(*args).jaxpr for k, g in grads.items()}
    assert {k: (dots(j), dots(j, rematted=True))
            for k, j in jaxprs.items()} == {
        "plain": (9, 0), "kept": (9, 6), "other_name": (11, 8),
        "recomputed": (11, 8)}
    want, want_g = jax.jit(grads["plain"])(*args)
    assert all(float(jnp.max(jnp.abs(g))) > 1e-3 for g in want_g)
    for k in ("kept", "other_name", "recomputed"):
        got, got_g = jax.jit(grads[k])(*args)
        assert float(got).hex() == float(want).hex()
        for a, b in zip(got_g, want_g):
            np.testing.assert_array_equal(a, b)


def test_the_name_is_the_identity_outside_a_saving_policy():
    f, args = layer_inputs("softmax")
    text = str(jax.make_jaxpr(f)(*args))
    assert text.count("name=" + expert.KEPT) == 2


# ----------------------------------------- the rule: how many layers keep


def cell(params, routed, held, width, tokens=8192, d=2048, hbm=16e9,
         remat=True):
    return dict(remat_blocks=remat, param_count=params, routed_layers=routed,
                tokens=tokens, held_stack=held and (held, d, width),
                hbm_bytes=hbm)


# (parameters as built: benchmark/configs/*.json ``parameters_as_built``)
DEEPSEEK = cell(635466752, 5, 8, 1408)
KIMI = cell(602434432, 4, 8, 1024, d=2304)
KEYE = cell(562290560, 5, 16, 768)
LFM2 = cell(558424448, 4, 8, 1536)


@pytest.mark.parametrize("what, inputs, layers", [
    ("lfm2_24b_a2b_train_1chip: all", LFM2, 4),
    ("keye_vl2_train_1chip: all", KEYE, 5),
    ("deepseek_v2_lite_train_1chip: as the chip loaded", DEEPSEEK, 5),
    ("kimi_linear_train_1chip: as the chip loaded", KIMI, 4),
    ("a chip twice as large: all", dict(DEEPSEEK, hbm_bytes=32e9), 5),
    ("a state that leaves no room", dict(DEEPSEEK, param_count=1020e6), 0),
    ("a partial count in between", dict(DEEPSEEK, param_count=938e6), 2),
    ("float32 products are twice the bytes",
     dict(DEEPSEEK, param_count=938e6, itemsize=4), 1),
    ("blocks not recomputed", dict(DEEPSEEK, remat_blocks=False), 0),
    ("no share held", dict(DEEPSEEK, held_stack=None), 0),
    ("no TPU", dict(DEEPSEEK, hbm_bytes=None), 0)])
def test_as_many_routed_layers_keep_their_products_as_fit(
        what, inputs, layers):
    assert lm.auto_kept_layers(**inputs) == lm.KeptLayers(layers)
    if layers:
        a_layer = lm.held_expert_kept_bytes(
            inputs["tokens"], inputs["held_stack"], inputs.get("itemsize", 2))
        assert 12 * inputs["param_count"] + layers * a_layer <= (
            1 - lm.KEPT_EXPERTS_HBM_LEFT) * inputs["hbm_bytes"]


def test_a_layer_keeps_four_bytes_a_token_and_hidden_feature():
    assert lm.held_expert_kept_bytes(8192, (8, 2048, 1408)) \
        == 4 * 8192 * 8 * 1408 == 369098752


# ------------------------------------- the model: which blocks keep the name


def tiny_share():
    """Three layers of softmax attention, the first with a dense
    feed-forward, two routed over 8 experts of which 2 are held, beside a
    shared expert."""
    cfg = lm.LMConfig(
        vocab_size=64, d_model=32, num_layers=3, num_heads=4, mlp_dim=16,
        max_seq_len=32, norm="rmsnorm", rope_theta=10000.0,
        attention_bias=False, head_bias=False, embed_scale=False,
        first_k_dense_replace=1, dense_dim=48, num_experts=8,
        experts_per_token=2, num_shared_experts=1, experts_held=HELD)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (3, 17)))
    params = lm.TransformerLM(cfg).init(jax.random.PRNGKey(0), ids[:, :-1])
    return cfg, {"params": params["params"]}, ids


@pytest.mark.parametrize("kept", [0, 1, 2])
def test_the_last_routed_layers_of_a_recomputed_model_keep_the_products(
        kept):
    """``TransformerLM(cfg, remat_blocks=True, kept_expert_layers=n)``: the
    forward's jaxpr holds the name twice a routed layer, every one inside a
    recomputed block, two of them a kept layer under a policy that saves
    it; the gradient makes two products fewer for every layer that keeps
    them. Against the model that keeps nothing and the model whose blocks
    are not recomputed the loss is equal bit for bit and every gradient to
    float32 rounding: the values kept are the forward's own (the layer
    alone, above, is equal to the last bit), but XLA fuses and orders the
    sums of each whole program its own way (1e-7 apart at most here, as
    ``tests/test_kimi_linear.py`` finds its recomputed blocks)."""
    cfg, params, ids = tiny_share()

    def loss_of(model):
        def loss(p):
            logits = model.apply(p, ids[:, :-1],
                                 mutable=["losses", "counters"])[0]
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), ids[:, 1:, None], axis=-1))
        return loss

    model = lm.TransformerLM(cfg, remat_blocks=True, kept_expert_layers=kept)
    forward = jax.make_jaxpr(loss_of(model))(params).jaxpr
    assert names_kept(forward) == (4, 4)

    grad = jax.value_and_grad(loss_of(model))
    none_kept = jax.value_and_grad(loss_of(
        lm.TransformerLM(cfg, remat_blocks=True)))
    assert dots(jax.make_jaxpr(none_kept)(params).jaxpr) \
        - dots(jax.make_jaxpr(grad)(params).jaxpr) == 2 * kept
    got, got_g = jax.jit(grad)(params)
    for other in (none_kept, jax.value_and_grad(loss_of(
            lm.TransformerLM(cfg)))):
        want, want_g = jax.jit(other)(params)
        assert float(got).hex() == float(want).hex()
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=0, atol=2e-6 * float(jnp.max(jnp.abs(b)))),
            got_g, want_g)


def test_a_model_whose_blocks_are_not_recomputed_keeps_nothing():
    cfg, params, ids = tiny_share()
    forward = jax.make_jaxpr(lambda p: lm.TransformerLM(
        cfg, kept_expert_layers=2).apply(
        p, ids[:, :-1], mutable=["losses", "counters"])[0])(params).jaxpr
    assert names_kept(forward) == (4, 0)
