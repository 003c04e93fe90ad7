"""The dense feed-forward's gate and up products kept by name across a
recomputed block (``models/layers.py:DENSE_FFN_KEPT``), the shared experts'
under the same name in a routed block, and what a sandwich-normed block's
two output norms read (``SUBLAYER_OUT_KEPT``): the rule that books their
room beside the held experts' (``models/lm.py:auto_kept_layers``) at each
cell's own numbers, which blocks' policies save the names, what that takes
out of the differentiated model, and that the values kept are the forward's
own."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import layers, lm
from tests.test_held_experts_kept import dots, names_kept, tiny_share

# ----------------------------------------- the rule: one room, four tenants


def cell(params, tokens, dense=0, width=0, routed=0, held=None, passes=1,
         cores=0, hbm=16e9, remat=True, sandwich=0, d=0, shared=0):
    return dict(remat_blocks=remat, param_count=params, hbm_bytes=hbm,
                tokens=tokens, routed_layers=routed, held_stack=held,
                dense_layers=dense, dense_width=width, loop_steps=passes,
                core_bytes=cores, sandwich_layers=sandwich, d_model=d,
                shared_width=shared)


def cores(tokens, applications, heads, qk, v):
    return applications * lm.flash_kept_bytes(tokens, heads, qk, v, 2)


# (parameters as built: benchmark/configs/*.json ``parameters_as_built``;
# the flash layers and their heads: the presets of ``LMConfig``)
OURO = cell(509661185, 4096, dense=6, width=5632, passes=4,
            cores=cores(4096, 24, 16, 128, 128), sandwich=6, d=2048)
LFM2 = cell(558424448, 8192, dense=2, width=11776, routed=4,
            held=(8, 2048, 1536), cores=cores(8192, 1, 32, 64, 64), d=2048)
DEEPSEEK = cell(635466752, 8192, dense=1, width=10944, routed=5,
                held=(8, 2048, 1408), cores=cores(8192, 6, 16, 192, 128),
                d=2048, shared=2 * 1408)
KEYE = cell(562290560, 8192, routed=5, held=(16, 2048, 768),
            cores=cores(8192, 5, 32, 128, 128), d=2048)
KIMI = cell(602434432, 8192, dense=1, width=9216, routed=4,
            held=(8, 2304, 1024), cores=cores(8192, 1, 32, 192, 128),
            d=2304, shared=1024)
# (experts without a gate: ONE kept product; the four scans' y and entering
# states are cores too, ``lm.ssd_kept_bytes``)
NEMOTRON = dict(cell(566838016, 8192, routed=3, held=(8, 2688, 1856),
                     cores=cores(8192, 1, 32, 128, 128)
                     + 4 * lm.ssd_kept_bytes(1, 8192, 64, 64, 128, 128),
                     d=2688, shared=3712), expert_products=1)
# (their blocks are not recomputed: 16 B a parameter x 2 is under the chip)
OLMOE = cell(625741824, 8192, routed=1, cores=0, d=2048, remat=False)
LM1B = cell(304209775, 16384, d=1024, remat=False)


@pytest.mark.parametrize("what, inputs, kept", [
    ("ouro_2_6b_train_1chip: all six, four passes each, and both output "
     "norms' inputs", OURO, (0, 6, 6, 0)),
    ("lfm2_24b_a2b_train_1chip: both, no shared expert", LFM2, (4, 2, 0, 0)),
    ("deepseek_v2_lite_train_1chip: the dense layer too, since the state "
     "is 12 B a parameter, and the shared experts", DEEPSEEK, (5, 1, 0, 5)),
    ("keye_vl2_train_1chip: no dense layer, no shared expert",
     KEYE, (5, 0, 0, 0)),
    ("kimi_linear_train_1chip: its shared expert too", KIMI, (4, 1, 0, 4)),
    ("nemotron_twotower_train_1chip: the scans' 0.8 GB beside the flash "
     "core's, and still all three of both", NEMOTRON, (3, 0, 0, 3)),
    ("... a fuller chip pays for the scans with shared experts' layers",
     dict(NEMOTRON, param_count=865.8e6), (3, 0, 0, 1)),
    ("... which the flash core alone would have left",
     dict(NEMOTRON, param_count=865.8e6,
          core_bytes=cores(8192, 1, 32, 128, 128)), (3, 0, 0, 3)),
    ("olmoe_train_1chip: nothing is recomputed", OLMOE, (0, 0, 0, 0)),
    ("lm1b_train_1chip: nothing is recomputed", LM1B, (0, 0, 0, 0)),
    ("lm1b_train_4chip_ar: nothing is recomputed",
     dict(LM1B, tokens=4 * 16384), (0, 0, 0, 0)),
    ("a chip twice as large: all",
     dict(DEEPSEEK, hbm_bytes=32e9), (5, 1, 0, 5)),
    ("the state at the 16 B it was: the experts took DeepSeek-V2-Lite's room",
     dict(DEEPSEEK, param_count=635466752 * 16 // 12), (5, 0, 0, 0)),
    ("a looped model's layer costs every pass: five of six, and one "
     "layer's norms", dict(OURO, param_count=770e6), (0, 5, 1, 0)),
    ("... and one pass of it fits all six of both",
     dict(OURO, param_count=770e6, loop_steps=1), (0, 6, 6, 0)),
    ("the cores are charged before the dense products",
     dict(OURO, param_count=770e6, core_bytes=0), (0, 6, 5, 0)),
    ("the shared experts come last: three layers of five",
     dict(DEEPSEEK, param_count=762e6), (5, 1, 0, 3)),
    ("float32 products are twice the bytes",
     dict(LFM2, param_count=700e6, itemsize=4), (4, 0, 0, 0)),
    ("... where bfloat16 ones fit",
     dict(LFM2, param_count=700e6), (4, 2, 0, 0)),
    ("a state that leaves no room",
     dict(OURO, param_count=1020e6), (0, 0, 0, 0)),
    ("blocks not recomputed", dict(LFM2, remat_blocks=False), (0, 0, 0, 0)),
    ("no TPU", dict(OURO, hbm_bytes=None), (0, 0, 0, 0))])
def test_each_tenant_takes_what_those_before_it_leave(what, inputs, kept):
    got = lm.auto_kept_layers(**inputs)
    assert got == lm.KeptLayers(*kept) and got.mixer_in == 0
    if not any(kept):
        return
    itemsize, tokens = inputs.get("itemsize", 2), inputs["tokens"]
    products = inputs.get("expert_products", 2)
    # (the cores are charged against what follows the experts only)
    booked = 12 * inputs["param_count"] \
        + any(got[1:]) * inputs["core_bytes"] + inputs["loop_steps"] * (
            got.dense * lm.dense_kept_bytes(
                tokens, inputs["dense_width"], itemsize)
            + got.sublayer_outs * lm.sublayer_out_kept_bytes(
                tokens, inputs["d_model"], itemsize)
            + got.shared * lm.dense_kept_bytes(
                tokens, inputs["shared_width"], itemsize, products))
    if got.experts:
        booked += got.experts * lm.held_expert_kept_bytes(
            tokens, inputs["held_stack"], itemsize, products)
    assert booked <= (1 - lm.KEPT_EXPERTS_HBM_LEFT) * inputs["hbm_bytes"]
    # ... and never more than the 4 B a parameter that left the chip over
    # what the 16 B line had booked
    before = lm.auto_kept_layers(**dict(
        inputs, param_count=inputs["param_count"] * 16 / 12))
    assert all(a >= b for a, b in zip(got, before))


@pytest.mark.parametrize("inputs", [LFM2, DEEPSEEK, KEYE, KIMI])
def test_the_experts_count_is_what_it_was_without_another_tenant(inputs):
    alone = dict(inputs, dense_layers=0, core_bytes=0, shared_width=0)
    assert lm.auto_kept_layers(**inputs).experts \
        == lm.auto_kept_layers(**alone).experts == inputs["routed_layers"]


@pytest.mark.parametrize("params, layers, passes, recomputed", [
    (635466752, 6, 1, True), (509661185, 6, 4, True),
    (602434432, 5, 1, True), (562290560, 5, 1, True),
    (558424448, 6, 1, True), (625741824, 1, 1, False),
    (304209775, 8, 1, False)])
def test_the_recompute_rule_stays_at_16_bytes_a_parameter(
        params, layers, passes, recomputed):
    """``auto_remat_blocks`` decides as before in every cell: at 12 B
    DeepSeek-V2-Lite's 635 M parameters would read 15.25e9 under the
    chip's 16e9 and stop recomputing, which does not fit."""
    assert lm.auto_remat_blocks(params, layers, 16e9, passes) == recomputed
    assert 12.0 * 635466752 * 2 < 16e9 < 16.0 * 635466752 * 2


def test_an_application_keeps_four_bytes_a_token_and_hidden_feature():
    assert lm.dense_kept_bytes(4096, 5632) == 4 * 4096 * 5632 == 92274688
    assert 24 * lm.dense_kept_bytes(4096, 5632) == 2214592512
    assert lm.dense_kept_bytes(8192, 11776) == 385875968
    # the shared experts: two of 1,408 (DeepSeek-V2-Lite), one of 1,024
    assert lm.dense_kept_bytes(8192, 2816) == 92274688
    assert lm.dense_kept_bytes(8192, 1024) == 33554432
    # both output norms' inputs, [T, d] each
    assert lm.sublayer_out_kept_bytes(4096, 2048) == 2 * 2 * 4096 * 2048
    assert 24 * lm.sublayer_out_kept_bytes(4096, 2048) == 805306368


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
    it, about a ``name`` equation's primitive; the checkpoints without a
    policy that the ``lax`` form of the delta rule holds INSIDE a block are
    no block's.)"""
    name_p = next(e.primitive for e in equations(jaxpr)
                  if e.primitive.name == "name")
    return [bool(e.params["policy"](name_p, name=name))
            for e in equations(jaxpr)
            if e.primitive.name in ("checkpoint", "remat2")
            and e.params["policy"] is not None]


def tiny_dense(passes=1, dense=3, sandwich=None):
    """Three layers of softmax attention under a dense SwiGLU each, looped
    ``passes`` times (a looped model's blocks norm their sub-layers'
    outputs too, as Ouro's)."""
    cfg = lm.LMConfig(
        vocab_size=64, d_model=32, num_layers=3, num_heads=4, mlp_dim=16,
        max_seq_len=32, norm="rmsnorm", rope_theta=10000.0,
        attention_bias=False, head_bias=False, embed_scale=False,
        first_k_dense_replace=dense, dense_dim=48, loop_steps=passes,
        sandwich_norm=passes > 1 if sandwich is None else sandwich)
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


@pytest.mark.parametrize("dense, shared, saves", [
    (1, 0, [True, False, False]), (0, 1, [False, False, True]),
    (1, 2, [True, True, True])])
def test_each_tenants_count_reaches_its_own_blocks_policies(
        dense, shared, saves):
    """The model of ``tests/test_held_experts_kept.py``: one dense layer,
    two routed ones with a shared expert. The dense layer's two products
    and each routed layer's shared expert's two carry the dense name (a
    block is dense or routed, so the name says which products and the
    block whose), the held experts' their own; the dense count reaches the
    dense block's policy, the shared count the LAST routed blocks', the
    experts' count theirs, and the gradient makes two products fewer for
    every SwiGLU that keeps its pair. Loss and gradients are the model's
    that keeps nothing, the loss bit for bit and the gradients to float32
    rounding (a jitted whole, as ``tests/test_held_experts_kept.py``)."""
    from autodist_tpu.parallel import expert
    cfg, params, ids = tiny_share()
    model = lm.TransformerLM(cfg, remat_blocks=True, kept_expert_layers=2,
                             kept_dense_layers=dense,
                             kept_shared_layers=shared)
    forward = jax.make_jaxpr(loss_of(model, ids))(params).jaxpr
    assert names_kept(forward, layers.DENSE_FFN_KEPT) == (6, 6)
    assert names_kept(forward) == (4, 4)
    assert saving(forward, layers.DENSE_FFN_KEPT) == saves
    assert saving(forward, expert.KEPT) == [False, True, True]
    grad = jax.value_and_grad(loss_of(model, ids))
    experts_only = jax.value_and_grad(loss_of(lm.TransformerLM(
        cfg, remat_blocks=True, kept_expert_layers=2), ids))
    assert dots(jax.make_jaxpr(experts_only)(params).jaxpr) \
        - dots(jax.make_jaxpr(grad)(params).jaxpr) == 2 * (dense + shared)
    if dense + shared < 3:
        return
    got, got_g = jax.jit(grad)(params)
    want, want_g = jax.jit(experts_only)(params)
    assert float(got).hex() == float(want).hex()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=0, atol=2e-6 * float(jnp.max(jnp.abs(b)))),
        got_g, want_g)


def forward_products(jaxpr, kernel_shape):
    """Products of a Dense's FORWARD form inside recomputed blocks: a
    ``dot_general`` whose right operand has the kernel's shape and is
    contracted over all but its last axis (a backward product contracts
    the kernel's last axis, or has the cotangent in its place)."""
    from tests.test_held_experts_kept import count
    lead = tuple(range(len(kernel_shape) - 1))
    return count(jaxpr, lambda e: (
        e.primitive.name == "dot_general"
        and e.invars[1].aval.shape == kernel_shape
        and tuple(e.params["dimension_numbers"][0][1]) == lead),
        rematted=True)


@pytest.mark.parametrize("kept, passes", [(1, 1), (3, 2)])
def test_the_last_sandwich_normed_layers_keep_what_their_output_norms_read(
        kept, passes):
    """``TransformerLM(cfg, remat_blocks=True, kept_sublayer_out_layers=n)``
    on Ouro's block (four norms a layer): the name is carried twice a layer
    application, by the attention's output product and by the
    feed-forward's down projection, where the two output norms read them.
    A recomputed block that keeps the name makes NEITHER product in its
    backward pass (the norms' backward reads their input; nothing else
    does), every other block both: the differentiated model holds two
    products fewer a kept layer, and none of the two kernels' forward
    forms. Loss and every gradient are the model's that keeps nothing: the
    values kept are the forward's own."""
    cfg, params, ids = tiny_dense(passes, sandwich=True)
    model = lm.TransformerLM(cfg, remat_blocks=True,
                             kept_sublayer_out_layers=kept)
    forward = jax.make_jaxpr(loss_of(model, ids))(params).jaxpr
    assert names_kept(forward, layers.SUBLAYER_OUT_KEPT) == (2 * 3, 2 * 3)
    assert saving(forward, layers.SUBLAYER_OUT_KEPT) \
        == [False] * (3 - kept) + [True] * kept
    assert saving(forward, layers.DENSE_FFN_KEPT) == [False] * 3

    grad = jax.value_and_grad(loss_of(model, ids))
    none_kept = jax.value_and_grad(loss_of(
        lm.TransformerLM(cfg, remat_blocks=True), ids))
    backward = jax.make_jaxpr(grad)(params).jaxpr
    assert dots(jax.make_jaxpr(none_kept)(params).jaxpr) - dots(backward) \
        == 2 * kept
    down_proj, out = (48, 32), (4, 8, 32)
    assert forward_products(backward, down_proj) == 3 - kept
    assert forward_products(backward, out) == 3 - kept
    got, got_g = grad(params)
    want, want_g = none_kept(params)
    assert float(got).hex() == float(want).hex()
    rounding = 2e-6 if passes > 1 else 0.0
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=0, atol=rounding * float(jnp.max(jnp.abs(b)))),
        got_g, want_g)


def test_a_block_without_output_norms_carries_no_such_name():
    cfg, params, ids = tiny_dense(sandwich=False)
    forward = jax.make_jaxpr(loss_of(lm.TransformerLM(
        cfg, remat_blocks=True, kept_sublayer_out_layers=3), ids))(
        params).jaxpr
    assert names_kept(forward, layers.SUBLAYER_OUT_KEPT) == (0, 0)


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


# -------------------------------------------------------------- the gauges


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
    hbm = (12 * n_params + (layers_that_fit + 0.5) * passes * an_application
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
    # (half an application's room is left: not a layer of anything)
    assert gauges["model.kept_sublayer_out_layers"] == 0
    assert gauges["model.kept_shared_layers"] == 0


@pytest.mark.parametrize("what, gauge, layers, a_layer", [
    ("a looped model's output norms", "sublayer_out", 3,
     2 * lm.sublayer_out_kept_bytes(4 * 16, 32, itemsize=4)),
    ("the shared experts", "shared", 2,
     lm.dense_kept_bytes(4 * 16, 16, itemsize=4))])
def test_the_sublayer_outputs_and_the_shared_experts_are_gauges_too(
        monkeypatch, what, gauge, layers, a_layer):
    """``model.kept_sublayer_out_layers`` / ``_bytes`` and
    ``model.kept_shared_layers`` / ``_bytes``: a chip with room for
    everything the model could keep, and still so small that its blocks
    are recomputed."""
    cfg, params, _ = tiny_dense(2) if gauge == "sublayer_out" \
        else tiny_share()
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    monkeypatch.setattr(lm, "_chip_hbm_bytes",
                        lambda: (12 * n_params + 300e3) / (
                            1 - lm.KEPT_EXPERTS_HBM_LEFT))
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=4)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.remat_blocks"] == 3
    assert gauges["model.kept_%s_layers" % gauge] == layers
    assert gauges["model.kept_%s_bytes" % gauge] == layers * a_layer
    other = "shared" if gauge == "sublayer_out" else "sublayer_out"
    assert gauges["model.kept_%s_layers" % other] == 0
    assert gauges["model.kept_%s_bytes" % other] == 0
