"""What a sequence mixer's input projections made, kept by name across a
recomputed block (``models/layers.py:MIXER_IN_KEPT``: Mamba-2's ``in_proj``,
KDA's q / k / v and the narrow halves of its low-rank pairs, the gated
convolution's ``in_proj`` with its gated product): the fifth tenant of
``models/lm.py:auto_kept_layers``' one room at each cell's own numbers, which
blocks' policies save the name, what that takes out of the differentiated
model, and that the values kept are the forward's own."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import layers, lm
from tests import test_kimi_linear, test_lfm2_moe, test_nemotron_h
from tests.test_dense_products_kept import (DEEPSEEK, KEYE, KIMI, LFM2, LM1B,
                                            NEMOTRON, OLMOE, OURO, equations,
                                            forward_products, loss_of)
from tests.test_dense_products_kept import saving as policy_saves
from tests.test_held_experts_kept import names_kept
from tests.test_keye_vl2_cell import bench_json

# ------------------------------------------ the rule: one room, five tenants

# (its blocks are not recomputed either: 16 B x 370.5 M x 2 is under the
# chip, ``tests/test_smallthinker_cell.py``)
SMALLTHINKER = dict(remat_blocks=False, param_count=370.5e6, hbm_bytes=16e9,
                    tokens=16384, routed_layers=4, held_stack=(8, 2560, 768))


def with_mixers(inputs, cfg):
    """The rule's inputs with the tenant's two, read off the preset."""
    return dict(inputs, mixer_layers=len(lm.mixer_in_layer_indices(cfg)),
                mixer_width=lm.mixer_in_width(cfg))


PRESETS = {
    "nemotron": lm.LMConfig.nemotron_twotower_30b_a3b(num_layers=8),
    "kimi": lm.LMConfig.kimi_linear_48b_a3b(num_layers=5),
    "lfm2": lm.LMConfig.lfm2_24b_a2b(num_layers=6),
    "ouro": lm.LMConfig.ouro_2_6b(num_layers=6),
    "deepseek": lm.LMConfig.deepseek_v2_lite(num_layers=6),
    "keye": lm.LMConfig.keye_vl2_30b_a3b(num_layers=5),
    "olmoe": lm.LMConfig.olmoe_1b_7b(num_layers=1),
    "lm1b": lm.LMConfig.lm1b(),
    "smallthinker": lm.LMConfig.smallthinker_21b_a3b(num_layers=4),
}


@pytest.mark.parametrize("preset, layers_, width", [
    ("nemotron", (0, 2, 4, 7), 2 * 4096 + 2 * 8 * 128 + 64),
    ("kimi", (0, 1, 2, 4), 3 * 4096 + 2 * 128 + 32),
    ("lfm2", (0, 1, 3, 4, 5), 4 * 2048),
    ("ouro", (), 0), ("deepseek", (), 0), ("keye", (), 0), ("olmoe", (), 0),
    ("lm1b", (), 0), ("smallthinker", (), 0)])
def test_the_tenants_layers_and_width_are_read_off_the_shapes(
        preset, layers_, width):
    cfg = PRESETS[preset]
    assert lm.mixer_in_layer_indices(cfg) == layers_
    assert lm.mixer_in_width(cfg) == width


def test_a_layer_keeps_two_bytes_a_token_and_feature():
    a_layer = lambda w: lm.kept_layer_bytes(  # noqa: E731
        8192, 2, None, 0, 0, 0, mixer_width=w).mixer_in
    assert a_layer(10304) == 168820736          # Mamba-2's [z | xBC | dt]
    assert a_layer(12576) == 206045184          # KDA's q, k, v and the rest
    assert a_layer(8192) == 134217728   # the convolution's [B|C|u] and y
    # a looped model's layer keeps it every pass, float32 twice the bytes
    assert lm.kept_layer_bytes(8192, 4, None, 0, 0, 0, loop_steps=3,
                               mixer_width=6144).mixer_in \
        == 3 * 2 * 100663296


@pytest.mark.parametrize("cell, inputs, preset, parents, mixer_in", [
    ("nemotron_twotower_train_1chip", NEMOTRON, "nemotron", (3, 0, 0, 3), 4),
    ("kimi_linear_train_1chip", KIMI, "kimi", (4, 1, 0, 4), 4),
    ("lfm2_24b_a2b_train_1chip", LFM2, "lfm2", (4, 2, 0, 0), 5),
    ("ouro_2_6b_train_1chip", OURO, "ouro", (0, 6, 6, 0), 0),
    ("deepseek_v2_lite_train_1chip", DEEPSEEK, "deepseek", (5, 1, 0, 5), 0),
    ("keye_vl2_train_1chip", KEYE, "keye", (5, 0, 0, 0), 0),
    ("olmoe_train_1chip", OLMOE, "olmoe", (0, 0, 0, 0), 0),
    ("lm1b_train_1chip", LM1B, "lm1b", (0, 0, 0, 0), 0),
    ("lm1b_train_4chip_ar", dict(LM1B, tokens=4 * 16384), "lm1b",
     (0, 0, 0, 0), 0),
    ("smallthinker_train_1chip", SMALLTHINKER, "smallthinker",
     (0, 0, 0, 0), 0)])
def test_the_mixers_inputs_are_booked_last_in_every_cell(
        cell, inputs, preset, parents, mixer_in):
    """All four, four and five layers in the three cells that have such a
    mixer, 0 layers of 0 bytes in the other seven; the four tenants before
    it count what they counted without it (it is booked LAST), and the
    whole booking stays inside the one room."""
    with_it = with_mixers(inputs, PRESETS[preset])
    got = lm.auto_kept_layers(**with_it)
    assert got == lm.KeptLayers(*parents, mixer_in)
    assert got[:4] == lm.auto_kept_layers(**inputs)[:4] == parents
    a_layer = lm.kept_layer_bytes(
        inputs["tokens"], 2, inputs.get("held_stack"),
        inputs.get("dense_width", 0), inputs.get("d_model", 0),
        inputs.get("shared_width", 0), inputs.get("loop_steps", 1),
        inputs.get("expert_products", 2), with_it["mixer_width"])
    if not mixer_in:
        assert a_layer.mixer_in == 0 or not inputs["remat_blocks"]
        return
    booked = 12 * inputs["param_count"] + inputs["core_bytes"] + sum(
        n * nbytes for n, nbytes in zip(got, a_layer))
    assert booked <= (1 - lm.KEPT_EXPERTS_HBM_LEFT) * inputs["hbm_bytes"]
    # what the tenant adds to the step's scratch, as the issue reckons it
    assert mixer_in * a_layer.mixer_in == {
        "nemotron": 675282944, "kimi": 824180736, "lfm2": 671088640}[preset]


@pytest.mark.parametrize("what, change, mixer_in", [
    ("a room one byte short of one layer", -1, 0),
    ("a room of one layer", 0, 1),
    ("a room one byte short of all four", 3 * 168820736 - 1, 3),
    ("a room of all four", 3 * 168820736, 4)])
def test_as_many_layers_of_the_tenant_as_the_room_holds(what, change,
                                                        mixer_in):
    """Nemotron-H's numbers on a chip that leaves, after the four tenants
    before it, exactly so much."""
    inputs = with_mixers(NEMOTRON, PRESETS["nemotron"])
    others = inputs["core_bytes"] + 3 * (243269632 + 60817408)
    hbm = (12 * inputs["param_count"] + others + 168820736 + change) / (
        1 - lm.KEPT_EXPERTS_HBM_LEFT)
    got = lm.auto_kept_layers(**dict(inputs, hbm_bytes=hbm))
    assert got == lm.KeptLayers(3, 0, 0, 3, mixer_in)


@pytest.mark.parametrize("what, change", [
    ("blocks not recomputed", dict(remat_blocks=False)),
    ("no TPU", dict(hbm_bytes=None)),
    ("a state that leaves no room", dict(param_count=1020e6)),
    ("no such layer", dict(mixer_layers=0)),
    ("a layer of no width", dict(mixer_width=0))])
def test_the_tenant_books_nothing(what, change):
    inputs = dict(with_mixers(KIMI, PRESETS["kimi"]), **change)
    assert lm.auto_kept_layers(**inputs).mixer_in == 0


# ----------------------------- the model: which blocks keep it, and of what

SEQ = 32


def shapes_of(cfg, ids):
    """The model's parameters as shapes: enough for a trace."""
    return {"params": jax.eval_shape(
        lm.TransformerLM(cfg).init, jax.random.PRNGKey(0),
        ids[:, :-1])["params"]}


def saving(jaxpr):
    return policy_saves(jaxpr, layers.MIXER_IN_KEPT)


def two_blocks(kind, values=False, **sizes):
    """Two layers of one mixer at its family's tiny widths: Mamba-2 alone in
    its block (``nemotron_h``'s single sub-layers), KDA or the gated
    convolution over a dense SwiGLU. -> (config,
    parameters (with ``values`` initialised, else their shapes), token ids,
    ``{projection: its kernel's shape}``, the kernels whose products a kept
    block still makes again to read the kept arrays)."""
    if kind == "mamba2":
        cfg = test_nemotron_h.tiny_config(
            num_layers=2, layer_types=("mamba2", "mamba2"), **sizes)
        kernels = {"in_proj": (48, lm.mixer_in_width(cfg))}
        again = {}
    elif kind == "kda":
        cfg = test_kimi_linear.tiny_config(
            num_layers=2, first_k_dense_replace=2, **sizes)
        hd, d = cfg.kda_num_heads * cfg.kda_head_dim, cfg.kda_head_dim
        # (q, k and v share a shape: three products a layer; the two narrow
        # halves another)
        kernels = {"q_proj, k_proj, v_proj": (48, hd),
                   "f_a_proj, g_a_proj": (48, d),
                   "b_proj": (48, cfg.kda_num_heads)}
        again = {"f_b_proj, g_b_proj": (d, hd)}
    else:
        cfg = test_lfm2_moe.tiny_config(num_layers=2, **sizes)
        # (the feed-forward's backward reads the mid-block residual, so
        # every block makes ``out_proj``'s product again, kept or not)
        kernels, again = {"in_proj": (48, 144)}, {"out_proj": (48, 48)}
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, SEQ + 1)))
    if not values:
        return cfg, shapes_of(cfg, ids), ids, kernels, again
    params = jax.jit(lm.TransformerLM(cfg).init)(
        jax.random.PRNGKey(0), ids[:, :-1])
    return cfg, {"params": params["params"]}, ids, kernels, again


PER_LAYER = {"in_proj": 1, "q_proj, k_proj, v_proj": 3,
             "f_a_proj, g_a_proj": 2, "b_proj": 1, "f_b_proj, g_b_proj": 2,
             "out_proj": 1}
# the name's carriers a layer: one array; q, k, v, f_a, g_a and b; the
# convolution's in-projection and its gated product
CARRIERS = {"mamba2": 1, "kda": 6, "conv": 2}


@pytest.mark.parametrize("kind", ["mamba2", "kda", "conv"])
@pytest.mark.parametrize("kept", [0, 1, 2])
def test_a_kept_blocks_backward_makes_no_input_projection_again(kind, kept):
    """``TransformerLM(cfg, remat_blocks=True, kept_mixer_in_layers=n)``:
    every layer's input projections carry the name inside its recomputed
    block, the LAST n blocks' policies save it, and the differentiated
    model holds a projection's forward product in the recomputed part of
    exactly the blocks that do not: a name no op of the block carried would
    keep nothing, and this is the guard. The wide halves of KDA's low-rank
    pairs, which read what is kept, and the convolution mixer's ``out_proj``
    are still made again in every block."""
    cfg, params, ids, kernels, again = two_blocks(kind)
    model = lm.TransformerLM(cfg, remat_blocks=True,
                             kept_mixer_in_layers=kept)
    forward = jax.make_jaxpr(loss_of(model, ids))(params).jaxpr
    assert names_kept(forward, layers.MIXER_IN_KEPT) \
        == (2 * CARRIERS[kind],) * 2
    assert saving(forward) == [False] * (2 - kept) + [True] * kept
    backward = jax.make_jaxpr(jax.grad(loss_of(model, ids)))(params).jaxpr
    for name, shape in kernels.items():
        assert forward_products(backward, shape) \
            == (2 - kept) * PER_LAYER[name], name
    for name, shape in again.items():
        assert forward_products(backward, shape) == 2 * PER_LAYER[name], name


@pytest.mark.parametrize("kind", ["mamba2", "kda", "conv"])
def test_loss_and_gradients_are_the_models_that_keeps_nothing(kind):
    """Kept, not kept and not recomputed at all: the loss and every
    gradient leaf equal to the last bit on the CPU, run equation by
    equation (a policy changes what is stored, not what is computed; a
    recomputed part compiled as one program would round its fusions its own
    way, so nothing is compiled whole here)."""
    cfg, params, ids, _, _ = two_blocks(kind, values=True)
    ids = ids[:1, :17]
    with jax.disable_jit():
        results = [jax.value_and_grad(loss_of(model, ids))(params)
                   for model in (
            lm.TransformerLM(cfg, remat_blocks=True, kept_mixer_in_layers=2),
            lm.TransformerLM(cfg, remat_blocks=True),
            lm.TransformerLM(cfg))]
    (want, want_g) = results[-1]
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(want_g))
    for got, got_g in results[:-1]:
        assert float(got).hex() == float(want).hex()
        jax.tree_util.tree_map(np.testing.assert_array_equal, got_g, want_g)


@pytest.mark.parametrize("kind, sizes, passes", [
    ("mamba2", dict(mamba_num_heads=64, mamba_head_dim=64, mamba_n_groups=8,
                    ssm_state_size=128, mamba_chunk=128),
     {"mamba_pre_fwd", "mamba_pre_bwd"}),
    ("kda", dict(kda_num_heads=2, kda_head_dim=128),
     {"kda_pre_fwd", "kda_pre_bwd"})])
def test_the_fused_passes_read_the_kept_arrays(kind, sizes, passes):
    """At the published head shapes the mixers' element-wise work runs as
    pallas passes (traced here, not run): the name is on what the pass
    READS (Mamba-2's ``in_proj`` output tokens last, after the transpose),
    a kept block's backward runs the pass's forward again from it and no
    input projection."""
    cfg, params, ids, kernels, _ = two_blocks(kind, **sizes)
    model = lambda k: lm.TransformerLM(  # noqa: E731
        cfg, remat_blocks=True, kept_mixer_in_layers=k)
    backward = {k: jax.make_jaxpr(jax.grad(loss_of(model(k), ids)))(params)
                for k in (0, 2)}
    assert passes <= {n for n in passes if n in str(backward[2])}
    for name, shape in kernels.items():
        assert forward_products(backward[0].jaxpr, shape) \
            == 2 * PER_LAYER[name], name
        assert forward_products(backward[2].jaxpr, shape) == 0, name
    if kind != "mamba2":
        return
    named = [e for e in equations(backward[2].jaxpr)
             if e.primitive.name == "name"
             and e.params["name"] == layers.MIXER_IN_KEPT]
    assert named and all(
        e.outvars[0].aval.shape == (2, lm.mixer_in_width(cfg), SEQ)
        for e in named)


def test_a_model_whose_blocks_are_not_recomputed_keeps_nothing():
    cfg, params, ids, _, _ = two_blocks("mamba2")
    forward = jax.make_jaxpr(loss_of(lm.TransformerLM(
        cfg, kept_mixer_in_layers=2), ids))(params).jaxpr
    assert names_kept(forward, layers.MIXER_IN_KEPT) == (2, 0)


def test_the_gated_convolution_keeps_its_in_projection_and_its_product():
    """``ShortConv`` carries the name twice: on ``in_proj``'s ``[B | C | u]``
    and on the core's gated product ``C * conv(B * u)``, which
    ``out_proj``'s weight gradient reads (with the first alone XLA made the
    product again in that matmul's prologue and the v5e's step LOST 0.35 %,
    with both it won 2.56 %: PERF.md section 6, PR 52). A kept block's
    backward then holds no multiply of the core's forward."""
    cfg, params, ids, _, _ = two_blocks("conv")
    assert lm.mixer_in_width(cfg) == 4 * cfg.d_model
    named = {}
    for kept in (0, 2):
        model = lm.TransformerLM(cfg, remat_blocks=True,
                                 kept_mixer_in_layers=kept)
        backward = jax.make_jaxpr(jax.grad(loss_of(model, ids)))(params)
        named[kept] = sorted(
            e.outvars[0].aval.shape for e in equations(backward.jaxpr)
            if e.primitive.name == "name"
            and e.params["name"] == layers.MIXER_IN_KEPT)
    # (the forward's two a layer; a block that keeps nothing names them
    # again in its recomputed part)
    assert named[2] == [(2, SEQ, 48)] * 2 + [(2, SEQ, 144)] * 2
    assert named[0] == [(2, SEQ, 48)] * 4 + [(2, SEQ, 144)] * 4


def test_the_kept_layers_are_the_last_that_have_such_a_mixer():
    """Kimi-Linear's five layers, the fourth a latent attention: with two
    kept it is layers 2 and 4 whose policies save the name, not 3 and 4."""
    cfg = test_kimi_linear.tiny_config()
    assert lm.mixer_in_layer_indices(cfg) == (0, 1, 2, 4)
    ids = jnp.zeros((1, 9), jnp.int32)
    forward = jax.make_jaxpr(loss_of(lm.TransformerLM(
        cfg, remat_blocks=True, kept_mixer_in_layers=2), ids))(
        shapes_of(cfg, ids)).jaxpr
    assert saving(forward) == [False, False, True, False, True]


# -------------------------------------------------------------- the gauges


@pytest.mark.parametrize("kind, layers_that_fit", [
    ("mamba2", 1), ("kda", 2), ("conv", 0), ("conv", 1), ("conv", 2)])
def test_the_kept_mixer_inputs_are_gauges_of_the_traced_loss(
        monkeypatch, kind, layers_that_fit):
    """``model.kept_mixer_in_layers`` / ``model.kept_mixer_in_bytes`` beside
    the four pairs before them, set as the loss is traced: a chip made so
    small that the blocks are recomputed and, after everything booked
    before, so many layers of the tenant fit."""
    cfg, params, _, _, _ = two_blocks(kind)
    cfg = dataclasses.replace(cfg, experts_held=None)
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    tokens = 2 * 16
    a_layer = 4 * tokens * lm.mixer_in_width(cfg)
    before = lm.kept_layer_bytes(tokens, 4, None, cfg.dense_dim, 0, 0)
    others = lm.num_dense_layers(cfg) * before.dense
    hbm = (12 * n_params + others + (layers_that_fit + 0.5) * a_layer) / (
        1 - lm.KEPT_EXPERTS_HBM_LEFT)
    monkeypatch.setattr(lm, "_chip_hbm_bytes", lambda: hbm)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=2)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.remat_blocks"] == 2
    assert gauges["model.kept_dense_layers"] == lm.num_dense_layers(cfg)
    assert gauges["model.kept_mixer_in_layers"] == layers_that_fit
    assert gauges["model.kept_mixer_in_bytes"] == layers_that_fit * a_layer


def test_a_model_without_such_a_mixer_reads_zero():
    cfg = lm.LMConfig.tiny()
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=2)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.kept_mixer_in_layers"] == 0
    assert gauges["model.kept_mixer_in_bytes"] == 0


# ---------------------- the ten steps as compiled for a described v5e (record)


@pytest.mark.parametrize("cell, booked", [
    ("nemotron_twotower_train_1chip", 675282944),
    ("kimi_linear_train_1chip", 824180736),
    ("lfm2_24b_a2b_train_1chip", 671088640), ("ouro_2_6b_train_1chip", 0),
    ("deepseek_v2_lite_train_1chip", 0),
    ("keye_vl2_train_1chip", 0), ("olmoe_train_1chip", 0),
    ("lm1b_train_1chip", 0), ("lm1b_train_4chip_ar", 0),
    ("smallthinker_train_1chip", 0)])
def test_the_steps_compiled_without_a_chip_hold_what_was_booked(cell, booked):
    """``benchmark/records/pr52_aot_memory.json``, every cell's whole step
    compiled for a described v5e at the parent commit and with the tenant:
    the seven cells without such a mixer compile the parent's step to the
    byte (scratch, code, every copy and Mosaic call); the three with one
    hold more scratch, by about what was booked (the recomputed arrays stood
    at the peak once before), less code, not one copy or transpose more,
    the same kernels as often, and state + scratch under the 15.35 GB a
    step has been seen to load with."""
    record = bench_json("records", "pr52_aot_memory.json")["cells"][cell]
    parent, change = (record[side]["train_step"]
                      for side in ("parent", "change"))
    grew = change["temp_size_in_bytes"] - parent["temp_size_in_bytes"]
    assert record["the_parents_step_to_the_byte"] == (booked == 0)
    for fact in ("copies_by_result", "transposes_by_result",
                 "tpu_custom_calls", "argument_size_in_bytes"):
        assert change[fact] == parent[fact], fact
    if not booked:
        assert grew == 0 and change["generated_code_size_in_bytes"] \
            == parent["generated_code_size_in_bytes"]
        return
    assert 0.6 * booked < grew < 1.25 * booked
    assert change["generated_code_size_in_bytes"] \
        < parent["generated_code_size_in_bytes"]
    assert change["argument_size_in_bytes"] + change["temp_size_in_bytes"] \
        < 14.0e9
