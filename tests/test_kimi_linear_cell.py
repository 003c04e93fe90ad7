"""Kimi-Linear's configuration and cell (``tests/test_kimi_linear.py`` holds
the model to its reference): the preset against the catalog row, the cell's
configuration file against the tree it builds, what the model refuses, the
shape rules of ``make_train_setup``, OLMoE's parameter tree held to what it
was before the model got a per-layer pattern (every preset's step:
``tests/test_lm_pins.py``), and what the cell's ``loss_rtol`` refuses (``benchmark/tools/loss_limit_kimi_linear.py``)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu.models import lm
from autodist_tpu.ops.flash_attention import (flash_attention,
                                              make_flash_attn_fn)

HERE = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-5
TOP_K = 4
HELD = (0, 1, 2, 3)
SEQ = 32


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



@pytest.mark.parametrize("what, params, layers_, hbm, remat", [
    ("kimi_linear_train_1chip", 602434432, 5, 16e9, True),
    ("lm1b_train_1chip", 304e6, 8, 16e9, False),
    ("olmoe_train_1chip: one block", 625.7e6, 1, 16e9, False),
    ("the same model on a 32 GB chip", 602434432, 5, 32e9, False),
    ("no TPU", 602434432, 5, None, False)])
def test_blocks_are_recomputed_where_the_state_takes_half_the_chip(
        what, params, layers_, hbm, remat):
    assert lm.auto_remat_blocks(params, layers_, hbm) is remat


@pytest.mark.parametrize("vocab, rows, seq, lean", [
    (20480, 1, 8192, True),      # kimi_linear_train_1chip: 0.67 GB of logits
    (20480, 1, 2048, False),
    (99183, 64, 256, True),      # lm1b, as before
    (50304, 4, 2048, True),      # OLMoE, as before
    (32000, 32, 128, False),     # the default config at 32 rows of 128
    (128, 4, 16, False)])
def test_the_lean_head_engages_on_the_logits_bytes_too(vocab, rows, seq, lean,
                                                      monkeypatch):
    from autodist_tpu.ops import xent
    calls = []
    real = xent.chunked_softmax_xent
    monkeypatch.setattr(xent, "chunked_softmax_xent",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    cfg = dataclasses.replace(lm.LMConfig.tiny(), vocab_size=vocab,
                              max_seq_len=seq, d_model=8, num_heads=1,
                              mlp_dim=8, num_layers=1)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=seq, batch_size=rows)
    jax.eval_shape(loss_fn, params, batch)
    assert bool(calls) is lean


# ------------------------------------------------- the config, the preset


def bench_json(*parts):
    with open(os.path.join(HERE, "..", "benchmark", *parts)) as f:
        return json.load(f)


def test_the_published_preset_is_the_catalog_row():
    cfg = lm.LMConfig.kimi_linear_48b_a3b()
    pub = bench_json("configs", "kimi_linear_48b_a3b.json")["published"]
    kda = pub["linear_attn_config"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size,
            cfg.norm_eps, cfg.kda_num_heads, cfg.kda_head_dim,
            cfg.kda_conv_size, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.first_k_dense_replace,
            cfg.dense_dim, cfg.mlp_dim, cfg.num_experts,
            cfg.experts_per_token, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.num_shared_experts) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["vocab_size"], pub["rms_norm_eps"],
        kda["num_heads"], kda["head_dim"], kda["short_conv_kernel_size"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["v_head_dim"], pub["first_k_dense_replace"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_experts"], pub["num_experts_per_token"],
        pub["moe_renormalize"], pub["routed_scaling_factor"],
        pub["num_shared_experts"])
    assert pub["moe_router_activation_func"] == cfg.router_activation
    assert [i + 1 for i, t in enumerate(cfg.layer_types) if t == "mla"] \
        == kda["full_attn_layers"]
    assert [i + 1 for i, t in enumerate(cfg.layer_types) if t == "kda"] \
        == kda["kda_layers"]
    assert pub["mla_use_nope"] and cfg.rope_theta is None
    assert cfg.experts_held is None     # the published model holds them all
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale)


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds, no
    width differs from the source, and ``reduced`` names every key that
    does."""
    from benchmark.families import kimi_linear as family
    config = bench_json("configs", "kimi_linear_48b_a3b.json")
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert sorted(differs) == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert config["router_num_experts"] == config["published"]["num_experts"]
    assert config["experts_held"] == list(range(config["num_experts"]))
    cfg = family.model_config(config, 8192)
    assert cfg.layer_types == ("kda", "kda", "kda", "mla", "kda")
    want = dataclasses.replace(
        lm.LMConfig.kimi_linear_48b_a3b(num_layers=5), dtype=cfg.dtype,
        vocab_size=20480, experts_held=tuple(range(8)))
    assert cfg == want
    shapes = jax.eval_shape(lambda: lm.TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    built = config["parameters_as_built"]
    count = lambda tree: sum(  # noqa: E731
        a.size for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == built["total"] == 602434432
    assert count(shapes["layer_0"]["kda"]) == built["kda_mixer"]
    assert count(shapes["layer_3"]["mla"]) == built["mla_mixer"]
    assert count(shapes["layer_0"]["mlp"]) == built["dense_ffn"]
    assert count(shapes["layer_1"]["moe"]["shared"]) == built["shared_expert"]
    assert count(shapes["layer_1"]) == built["layer_kda_moe"]
    assert count(shapes["layer_3"]) == built["layer_mla_moe"]
    assert family.active_matmul_params(config) == \
        built["active_matmul_per_token"]


@pytest.mark.parametrize("change, says", [
    (dict(layer_types=("kda",)), "layer_types"),
    (dict(layer_types=("kda", "kda", "kda", "mla", "lstm")), "layer_types"),
    (dict(experts_held=(0, 0)), "experts_held"),
    (dict(experts_held=(16,)), "experts_held"),
    (dict(experts_held=()), "experts_held"),
    (dict(router_activation="tanh"), "router_activation"),
    # (a softmax router with renormalised, scaled gates, a shared expert
    # and a share is a model since PR 31; a sigmoid router's loss is not)
    (dict(router_aux_loss_coef=0.01), "softmax router")])
def test_an_architecture_the_model_cannot_build_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        tiny_config(**change)


# ------------------------------------- OLMoE's tree is what it was


def tree_of(cfg):
    shapes = jax.eval_shape(lambda: lm.TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    return [["/".join(str(k.key) for k in path), list(a.shape), str(a.dtype)]
            for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]
            if path[0].key == "params"]


def test_olmoe_parameter_tree_is_unchanged():
    with open(os.path.join(HERE, "data", "lm_pins.json")) as f:
        assert tree_of(lm.LMConfig.olmoe_1b_7b(num_layers=1)) \
            == json.load(f)["olmoe_tree"]


def test_the_kernel_with_equal_widths_is_the_kernel_it_was():
    """q, k and v of one width lower to the same pallas calls (block shapes
    and scratch) as before the value width became its own: the forward
    kernel and, since PR 34, ONE backward kernel where there were two."""
    x = jnp.zeros((1, 64, 2, 16), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True)), argnums=(0, 1, 2)))(x, x, x))
    assert text.count("pallas_call") == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    assert "192" not in text and text.count("(64, 16)") > 0
    fn = make_flash_attn_fn(causal=True)
    assert fn(x, x, x).shape == x.shape


# ---- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_kimi_linear.py)


def bench_lines(*parts):
    with open(os.path.join(HERE, "..", "benchmark", *parts)) as f:
        return [json.loads(line) for line in f]


CELL = bench_json("workloads", "kimi_linear_train_1chip.json")
PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at the rehearsal's
    tiny size, read as the benchmark's driver reads a run."""
    from benchmark.tools import loss_limit_kimi_linear as tool
    config = bench_json("tests", "configs", "kimi_linear_tiny.json")
    traffic = dict(bench_json("traffic", "train_b1_s8192_every16.json"),
                   batch_per_chip=2, seq=SEQ)
    rows = tool.readings(config, traffic, 7, CELL["loss_rtol"])
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    from benchmark.tools import loss_limit_kimi_linear as tool
    assert PLANTED == sorted(tool.faults())
    assert not set(CELL["loss_rtol_refuses"]) & set(
        CELL["loss_rtol_lets_through"])
    # the configuration's own precision is never refused
    assert set(tool.WITHIN) <= set(CELL["loss_rtol_lets_through"])


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_moves_what_the_driver_reads(tiny_readings, fault):
    """The faults are really planted: each moves the reading by far more
    than the 1e-5 the float32 program and reference differ by."""
    assert tiny_readings["sound"] == 0.0
    if fault == "computed_in_bfloat16":
        assert RTOL < tiny_readings[fault] < tiny_readings[
            "computed_in_float8_e4m3fn"]
    else:
        assert tiny_readings[fault] > 10 * RTOL


def limit_record(fault):
    return [r["reading"] for r in bench_lines("records",
                                              "pr29_loss_limit.jsonl")
            if r.get("fault") == fault]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len(sound) >= 6
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr29_loss_limit.jsonl): a fault is
    REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])


# ------------------------- the cell's step, compiled for a described v5e


def test_the_cells_whole_step_compiles_for_a_v5e_and_fits_it(monkeypatch):
    """``kimi_linear_train_1chip``'s step as the benchmark builds it ON THE
    CHIP (``benchmark/tools/aot_compile_as_on_the_chip.py``: the backend
    probe answers "tpu", the chip's memory is the v5e's, the kernels lower
    as Mosaic calls), at the published widths and 1 x 8,192 tokens: the
    two flash kernels at 192 / 128 (``flash_fwd`` and, since PR 34, the one
    backward kernel ``flash_bwd``) and the delta rule's two kernels per
    KDA layer, each ONCE (a recomputed block keeps by name what their
    backward kernels read of the forward kernels' results), the mixer's
    fused element-wise passes around them (PR 42: ``kda_pre_fwd`` and
    ``kda_post_fwd`` twice a layer, the recomputed block's included,
    ``kda_pre_bwd`` and ``kda_post_bwd`` once, under ``kda`` and outside
    ``kda_scan``), no float32 [8192, 4096] layout copy between them, no
    triangular solve and no loop of the core left to XLA, every held
    expert on every token in four routed layers, every block recomputed,
    and state + scratch inside 16 GB with the 2.4 GB of initial parameters
    ``ModelItem`` keeps beside them."""
    import sys
    import autodist_tpu
    path = list(sys.path)
    from benchmark.tools import aot_compile, aot_compile_as_on_the_chip
    sys.path[:] = path      # (the tools re-point sys.path)
    compiled = []
    mem = aot_compile.mem
    monkeypatch.setattr(aot_compile, "mem",
                        lambda c: (compiled.append(c), mem(c))[1])
    try:
        with aot_compile_as_on_the_chip.as_on_the_chip() as devices:
            out = aot_compile.compile_cell("kimi_linear_train_1chip", devices)
    finally:
        autodist_tpu.reset()
    text = compiled[0].as_text()
    assert all(name in text for name in ("flash_fwd", "flash_bwd"))
    assert not any(name in text for name in ("flash_dq", "flash_dkdv"))
    # the two flash kernels, four KDA layers' forward and backward
    # kernels; a share of the experts runs no grouped-matmul kernel
    # (expert.py:_held_experts)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 + 4 + 4 + 3 * (4 + 4)
    launches = {name: [line for line in calls
                       if line.split(" = ")[0].split("%")[-1].rsplit(
                           ".", 1)[0] == name]
                for name in ("flash_fwd", "flash_bwd", "kda_fwd", "kda_bwd",
                             "kda_pre_fwd", "kda_pre_bwd", "kda_post_fwd",
                             "kda_post_bwd")}
    assert {name: len(lines) for name, lines in launches.items()} == {
        "flash_fwd": 1, "flash_bwd": 1, "kda_fwd": 4, "kda_bwd": 4,
        "kda_pre_fwd": 8, "kda_pre_bwd": 4, "kda_post_fwd": 8,
        "kda_post_bwd": 4}
    for name, lines in launches.items():
        if name.startswith("kda_"):
            assert all("/kda/" in line for line in lines)
            assert all(("kda_scan" in line) is (name in ("kda_fwd", "kda_bwd"))
                       for line in lines)
    # nothing re-lays a float32 [8192, 4096] between the projections and
    # the kernels (24 copies f32[1024, 8, 32, 128] a step until PR 42)
    assert not [line for line in text.splitlines()
                if " copy(" in line and "f32[1024,8,32,128]" in line]
    assert "triangular-solve" not in text
    # (the lean head's two scans are the step's only loops)
    assert not [line for line in text.splitlines()
                if " while(" in line and "kda" in line]
    assert "rematted_computation" in text
    step = out["train_step"]
    # float32 master weights and Adam's two moments: 12 B a parameter
    assert abs(step["argument_size_in_bytes"] - 12 * 602434432) < 1 << 20
    # 5.79 GB since PR 52 keeps what the four KDA mixers' input projections
    # made (q, k, v, the narrow halves of the low-rank pairs and b: 0.82 GB
    # by the closed form, 0.54 of scratch, since the recomputed arrays stood
    # at the peak before); 5.25 GB since PR 46 keeps the four shared
    # experts' gate and up products (0.13 GB by the closed form, 0.10 of
    # scratch) and nothing but the state is at rest beside it;
    # 5.16 GB since PR 45 keeps the dense layer's gate and up products
    # (0.30 GB, the closed form's 4 x 8,192 x 9,216 B to a megabyte); 4.85
    # GB since PR 42's fused passes keep no float32 intermediate of
    # the KDA mixers (5.45 since PR 41 keeps the held experts' gate and up
    # products in all four routed layers, 0.80 GB of the closed form's
    # 4 x 268 MB; 4.65 with the flash kernel's output and q kept, PR 32;
    # 4.47, PR 30; 4.58, PR 29); the chip loaded it, cold and from the
    # cache (PERF.md section 6)
    assert 5.5e9 < step["temp_size_in_bytes"] < 6.0e9
    assert step["live_bytes_estimate"] < 13.3e9
    # 9 expert products a routed layer, none made a second time
    products = [line for line in text.splitlines()
                if " convolution(" in line and "moe_experts" in line]
    assert len(products) == 9 * 4
    assert not [line for line in products if "rematted_computation" in line]
    # ... nor the dense layer's gate and up products (PR 45: the rule finds
    # their 0.30 GB of room beside the experts' 1.07 and the cores' 0.17)
    dense = [line for line in text.splitlines() if " convolution(" in line
             and ("mlp/gate_proj" in line or "mlp/up_proj" in line)]
    assert dense
    assert not [line for line in dense if "rematted_computation" in line]
    # ... nor a KDA mixer's q, k, v, f_a, g_a and b projections (PR 52); the
    # wide halves of the low-rank pairs read what is kept and are made again
    made_again = {name: [line for line in text.splitlines()
                         if " convolution(" in line
                         and "/kda/%s/" % name in line
                         and "rematted_computation" in line]
                  for name in ("q_proj", "k_proj", "v_proj", "f_a_proj",
                               "g_a_proj", "b_proj", "f_b_proj", "g_b_proj")}
    assert {name for name, lines in made_again.items() if lines} \
        == {"f_b_proj", "g_b_proj"}
