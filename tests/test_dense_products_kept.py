"""The dense feed-forward's gate and up products kept by name across a
recomputed block (``models/layers.py:DENSE_FFN_KEPT``): the rule that books
their room beside the held experts' (``models/lm.py:auto_kept_layers``) at
each cell's own numbers, which blocks' policies save the name, what that
takes out of the differentiated model, and that the values kept are the
forward's own."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import layers, lm
from tests.test_held_experts_kept import dots, names_kept, tiny_share

# ------------------------------------------ the rule: one room, two tenants


def cell(params, tokens, dense=0, width=0, routed=0, held=None, passes=1,
         cores=0, hbm=16e9, remat=True):
    return dict(remat_blocks=remat, param_count=params, hbm_bytes=hbm,
                tokens=tokens, routed_layers=routed, held_stack=held,
                dense_layers=dense, dense_width=width, loop_steps=passes,
                core_bytes=cores)


def cores(tokens, applications, heads, qk, v):
    return applications * lm.flash_kept_bytes(tokens, heads, qk, v, 2)


# (parameters as built: benchmark/configs/*.json ``parameters_as_built``;
# the flash layers and their heads: the presets of ``LMConfig``)
OURO = cell(509661185, 4096, dense=6, width=5632, passes=4,
            cores=cores(4096, 24, 16, 128, 128))
LFM2 = cell(558424448, 8192, dense=2, width=11776, routed=4,
            held=(8, 2048, 1536), cores=cores(8192, 1, 32, 64, 64))
DEEPSEEK = cell(635466752, 8192, dense=1, width=10944, routed=5,
                held=(8, 2048, 1408), cores=cores(8192, 6, 16, 192, 128))
KEYE = cell(562290560, 8192, routed=5, held=(16, 2048, 768),
            cores=cores(8192, 5, 32, 128, 128))
KIMI = cell(602434432, 8192, dense=1, width=9216, routed=4,
            held=(8, 2304, 1024), cores=cores(8192, 1, 32, 192, 128))


@pytest.mark.parametrize("what, inputs, experts, dense", [
    ("ouro_2_6b_train_1chip: all six, four passes each", OURO, 0, 6),
    ("lfm2_24b_a2b_train_1chip: both", LFM2, 4, 2),
    ("deepseek_v2_lite_train_1chip: the experts took the room",
     DEEPSEEK, 5, 0),
    ("keye_vl2_train_1chip: no dense layer", KEYE, 5, 0),
    ("kimi_linear_train_1chip: as the chip loaded", KIMI, 4, 1),
    ("a chip twice as large: all", dict(DEEPSEEK, hbm_bytes=32e9), 5, 1),
    ("a looped model's layer costs every pass: five of six",
     dict(OURO, param_count=580e6), 0, 5),
    ("... and one pass of it fits all six",
     dict(OURO, param_count=580e6, loop_steps=1), 0, 6),
    ("the cores are charged before the dense products",
     dict(OURO, param_count=580e6, core_bytes=0), 0, 6),
    ("float32 products are twice the bytes",
     dict(LFM2, itemsize=4), 4, 0),
    ("a state that leaves no room", dict(OURO, param_count=800e6), 0, 0),
    ("blocks not recomputed", dict(LFM2, remat_blocks=False), 0, 0),
    ("no TPU", dict(LFM2, hbm_bytes=None), 0, 0)])
def test_the_dense_products_take_what_the_held_experts_leave(
        what, inputs, experts, dense):
    assert lm.auto_kept_layers(**inputs) == (experts, dense)
    if not dense:
        return
    itemsize = inputs.get("itemsize", 2)
    booked = 16 * inputs["param_count"] + inputs["core_bytes"] \
        + dense * inputs["loop_steps"] * lm.dense_kept_bytes(
            inputs["tokens"], inputs["dense_width"], itemsize)
    if experts:
        booked += experts * lm.held_expert_kept_bytes(
            inputs["tokens"], inputs["held_stack"], itemsize)
    assert booked <= (1 - lm.KEPT_EXPERTS_HBM_LEFT) * inputs["hbm_bytes"]


@pytest.mark.parametrize("inputs", [LFM2, DEEPSEEK, KEYE, KIMI])
def test_the_experts_count_is_what_it_was_without_a_second_tenant(inputs):
    alone = dict(inputs, dense_layers=0, core_bytes=0)
    assert lm.auto_kept_layers(**inputs)[0] \
        == lm.auto_kept_layers(**alone)[0] == inputs["routed_layers"]


def test_an_application_keeps_four_bytes_a_token_and_hidden_feature():
    assert lm.dense_kept_bytes(4096, 5632) == 4 * 4096 * 5632 == 92274688
    assert 24 * lm.dense_kept_bytes(4096, 5632) == 2214592512
    assert lm.dense_kept_bytes(8192, 11776) == 385875968


@pytest.mark.parametrize("first_k, width, layers, dense", [
    (0, 0, 4, 0), (1, 48, 4, 1), (2, 48, 4, 2), (6, 48, 4, 4), (2, 0, 4, 0)])
def test_the_leading_layers_with_a_width_are_the_dense_ones(
        first_k, width, layers, dense):
    cfg = dataclasses.replace(lm.LMConfig.tiny(), num_layers=layers,
                              first_k_dense_replace=first_k, dense_dim=width)
    assert lm.num_dense_layers(cfg) == dense


# ------------------------------------- the model: which blocks keep the name


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(inner)


def saving(jaxpr, name):
    """Block by block in the model's order: does the recomputed block's
    policy save ``name``? (The policy is asked as ``jax.checkpoint`` asks
    it, about a ``name`` equation's primitive.)"""
    name_p = next(e.primitive for e in equations(jaxpr)
                  if e.primitive.name == "name")
    return [bool(e.params["policy"](name_p, name=name))
            for e in equations(jaxpr)
            if e.primitive.name in ("checkpoint", "remat2")]


def tiny_dense(passes=1, dense=3):
    """Three layers of softmax attention under a dense SwiGLU each, looped
    ``passes`` times."""
    cfg = lm.LMConfig(
        vocab_size=64, d_model=32, num_layers=3, num_heads=4, mlp_dim=16,
        max_seq_len=32, norm="rmsnorm", rope_theta=10000.0,
        attention_bias=False, head_bias=False, embed_scale=False,
        first_k_dense_replace=dense, dense_dim=48, loop_steps=passes,
        sandwich_norm=passes > 1)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (3, 17)))
    params = lm.TransformerLM(cfg).init(jax.random.PRNGKey(0), ids[:, :-1])
    return cfg, {"params": params["params"]}, ids


def loss_of(model, ids):
    def loss(p):
        logits = model.apply(p, ids[:, :-1],
                             mutable=["losses", "counters"])[0]
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), ids[:, 1:, None], axis=-1))
    return loss


@pytest.mark.parametrize("passes", [1, 2])
@pytest.mark.parametrize("kept", [0, 1, 3])
def test_the_last_dense_layers_of_a_recomputed_model_keep_the_products(
        kept, passes):
    """``TransformerLM(cfg, remat_blocks=True, kept_dense_layers=n)``: the
    forward holds the name twice a dense layer APPLICATION, every one
    inside a recomputed block; the gradient makes the gate and the up
    product of a kept layer's application once and of every other's twice.
    Against the model that keeps nothing the loss and every gradient are
    equal to the last bit on the CPU, run equation by equation: the
    products are left as JAX writes them, kept or made again. (A looped
    model's passes are one ``scan`` equation, which XLA compiles whole and
    fuses its own way around what is kept: there every gradient is equal to
    float32 rounding, as ``tests/test_held_experts_kept.py`` finds a whole
    jitted model.)"""
    cfg, params, ids = tiny_dense(passes)
    model = lm.TransformerLM(cfg, remat_blocks=True, kept_dense_layers=kept)
    forward = jax.make_jaxpr(loss_of(model, ids))(params).jaxpr
    # (a looped model's passes are ONE traced body)
    assert names_kept(forward, layers.DENSE_FFN_KEPT) == (2 * 3, 2 * 3)
    assert saving(forward, layers.DENSE_FFN_KEPT) \
        == [False] * (3 - kept) + [True] * kept

    grad = jax.value_and_grad(loss_of(model, ids))
    none_kept = jax.value_and_grad(loss_of(
        lm.TransformerLM(cfg, remat_blocks=True), ids))
    assert dots(jax.make_jaxpr(none_kept)(params).jaxpr) \
        - dots(jax.make_jaxpr(grad)(params).jaxpr) == 2 * kept
    got, got_g = grad(params)
    want, want_g = none_kept(params)
    assert float(got).hex() == float(want).hex()
    rounding = 2e-6 if passes > 1 else 0.0
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=0, atol=rounding * float(jnp.max(jnp.abs(b)))),
        got_g, want_g)


def test_the_kept_layers_are_the_last_of_the_dense_ones():
    """Two dense layers of three and one kept: layer 1's policy saves the
    name, layer 0's and the GELU layer 2's do not."""
    cfg, params, ids = tiny_dense(dense=2)
    model = lm.TransformerLM(cfg, remat_blocks=True, kept_dense_layers=1)
    forward = jax.make_jaxpr(loss_of(model, ids))(params).jaxpr
    assert saving(forward, layers.DENSE_FFN_KEPT) == [False, True, False]
    assert names_kept(forward, layers.DENSE_FFN_KEPT) == (4, 4)


def test_the_shared_experts_keep_nothing_and_the_experts_keep_theirs():
    """The model of ``tests/test_held_experts_kept.py``: one dense layer,
    two routed ones with a shared expert. Only the dense layer's two
    products carry the dense name, whatever is kept, and each tenant's
    count reaches its own blocks' policies only."""
    from autodist_tpu.parallel import expert
    cfg, params, ids = tiny_share()
    model = lm.TransformerLM(cfg, remat_blocks=True, kept_expert_layers=2,
                             kept_dense_layers=1)
    forward = jax.make_jaxpr(loss_of(model, ids))(params).jaxpr
    assert names_kept(forward, layers.DENSE_FFN_KEPT) == (2, 2)
    assert names_kept(forward) == (4, 4)
    assert saving(forward, layers.DENSE_FFN_KEPT) == [True, False, False]
    assert saving(forward, expert.KEPT) == [False, True, True]
    grad = jax.value_and_grad(loss_of(model, ids))
    experts_only = jax.value_and_grad(loss_of(lm.TransformerLM(
        cfg, remat_blocks=True, kept_expert_layers=2), ids))
    assert dots(jax.make_jaxpr(experts_only)(params).jaxpr) \
        - dots(jax.make_jaxpr(grad)(params).jaxpr) == 2


def test_a_model_whose_blocks_are_not_recomputed_keeps_nothing():
    cfg, params, ids = tiny_dense()
    forward = jax.make_jaxpr(loss_of(lm.TransformerLM(
        cfg, kept_dense_layers=3), ids))(params).jaxpr
    assert names_kept(forward, layers.DENSE_FFN_KEPT) == (6, 0)


def test_a_swiglu_without_the_field_carries_no_name():
    x = jnp.ones((4, 8))
    for kept, names in ((None, 0), (layers.DENSE_FFN_KEPT, 2)):
        swiglu = layers.SwiGLU(16, kept=kept)
        params = swiglu.init(jax.random.PRNGKey(0), x)
        text = str(jax.make_jaxpr(swiglu.apply)(params, x))
        assert text.count("name=" + layers.DENSE_FFN_KEPT) == names


# ------------------------------------------------------------ the two gauges


@pytest.mark.parametrize("passes", [1, 2])
@pytest.mark.parametrize("layers_that_fit", [0, 1, 3])
def test_the_kept_dense_layers_are_gauges_of_the_traced_loss(
        monkeypatch, layers_that_fit, passes):
    """``model.kept_dense_layers`` / ``model.kept_dense_bytes`` beside the
    experts' pair, set as the loss is traced: a tiny dense model on a chip
    made so small that its blocks are recomputed and so many layers'
    products fit, a looped one's every pass counted."""
    cfg, params, _ = tiny_dense(passes)
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    an_application = lm.dense_kept_bytes(4 * 16, 48, itemsize=4)
    assert an_application == 2 * 4 * 64 * 48
    hbm = (16 * n_params + (layers_that_fit + 0.5) * passes * an_application
           ) / (1 - lm.KEPT_EXPERTS_HBM_LEFT)
    monkeypatch.setattr(lm, "_chip_hbm_bytes", lambda: hbm)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=4)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.remat_blocks"] == 3
    assert gauges["model.kept_core_bytes"] == 0       # XLA's attention
    assert gauges["model.kept_expert_layers"] == 0
    assert gauges["model.kept_dense_layers"] == layers_that_fit
    assert gauges["model.kept_dense_bytes"] \
        == layers_that_fit * passes * an_application
