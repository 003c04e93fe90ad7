"""DeepSeek-V2-Lite's configuration and cell (``tests/test_deepseek_v2.py``
holds the model to its reference): the preset against the catalog row, the
cell's configuration file against the tree it builds, the shape rules of
``make_train_setup`` for this cell, the cell's whole step compiled for a
described v5e, and what the cell's ``loss_rtol`` refuses
(``benchmark/tools/loss_limit_deepseek_v2.py``)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import lm

HERE = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-5
SEQ = 32


def bench_json(*parts):
    with open(os.path.join(HERE, "..", "benchmark", *parts)) as f:
        return json.load(f)


def bench_lines(*parts):
    with open(os.path.join(HERE, "..", "benchmark", *parts)) as f:
        return [json.loads(line) for line in f]


CONFIG = bench_json("configs", "deepseek_v2_lite.json")
CELL = bench_json("workloads", "deepseek_v2_lite_train_1chip.json")


# ------------------------------------------------- the config, the preset


def test_the_published_preset_is_the_catalog_row():
    cfg = lm.LMConfig.deepseek_v2_lite()
    pub = CONFIG["published"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size,
            cfg.norm_eps, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.first_k_dense_replace,
            cfg.dense_dim, cfg.mlp_dim, cfg.num_experts,
            cfg.experts_per_token, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.num_shared_experts, cfg.rope_theta,
            cfg.rope_scaling, cfg.seq_aux, cfg.max_seq_len) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["vocab_size"], pub["rms_norm_eps"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["v_head_dim"], pub["first_k_dense_replace"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["norm_topk_prob"], pub["routed_scaling_factor"],
        pub["n_shared_experts"], pub["rope_theta"], pub["rope_scaling"],
        pub["seq_aux"], pub["max_position_embeddings"])
    assert pub["scoring_func"] == cfg.router_activation == "softmax"
    assert pub["q_lora_rank"] is None and pub["topk_method"] == "greedy"
    assert cfg.layer_types == ("mla",) * 27
    assert cfg.experts_held is None     # the published model holds them all
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale
                or pub["attention_bias"] or pub["tie_word_embeddings"])
    from benchmark.reference import deepseek_v2 as ref
    assert cfg.router_aux_loss_coef == ref.ALPHA \
        == CONFIG["assumed"]["aux_loss_alpha"] == 0.001
    assert dict(ref.YARN, type="yarn") == pub["rope_scaling"]
    assert (ref.TOP_K, ref.RMS_EPS, ref.ROPE_THETA) == (
        pub["num_experts_per_tok"], pub["rms_norm_eps"], pub["rope_theta"])


def test_the_catalogs_row_is_the_files_published_block():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "DeepSeek-V2-Lite"][0]
    assert row["config"] == CONFIG["published"]
    assert row["source_url"] == CONFIG["source"]
    entry = [c for c in bench_json("..", "BENCHMARK.json")["configs"]
             if c["name"] == "deepseek_v2_lite"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds, no
    width differs from the source, and ``reduced`` names every key that
    does."""
    from benchmark.families import deepseek_v2 as family
    config = CONFIG
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert sorted(differs) == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(config["reduced_why"]) == sorted(config["reduced"])
    assert config["router_num_experts"] == \
        config["published"]["n_routed_experts"]
    assert config["experts_held"] == list(range(config["n_routed_experts"]))
    cfg = family.model_config(config, 8192)
    want = dataclasses.replace(
        lm.LMConfig.deepseek_v2_lite(num_layers=6), dtype=cfg.dtype,
        vocab_size=12800, experts_held=tuple(range(8)))
    assert cfg == want
    shapes = jax.eval_shape(lambda: lm.TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    built = config["parameters_as_built"]
    count = lambda tree: sum(  # noqa: E731
        a.size for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == built["total"] == 635466752
    assert count(shapes["layer_0"]["mla"]) == built["mla_mixer"]
    assert count(shapes["layer_0"]["mlp"]) == built["dense_ffn"]
    assert count(shapes["layer_0"]) == built["layer_0_mla_dense"]
    moe = shapes["layer_1"]["moe"]
    assert count(moe["shared"]) == built["shared_experts"]
    assert moe["router"].size == built["router"]
    assert 3 * moe["gate_proj"].size == built["held_experts_per_layer"] \
        == 8 * built["one_expert"]
    assert count(shapes["layer_1"]) == built["layer_mla_moe"]
    assert shapes["embed"]["embedding"].size == built["embedding"]
    assert count(shapes["lm_head"]) == built["head"]
    assert family.active_matmul_params(config) == \
        built["active_matmul_per_token"]
    # no width is cut: the tree's shapes are the published widths, and the
    # file's sentence names each of them
    mla = shapes["layer_3"]["mla"]
    assert {k: v["kernel"].shape for k, v in mla.items() if "proj" in k} == {
        "q_proj": (2048, 16 * (128 + 64)), "kv_a_proj": (2048, 512 + 64),
        "kv_b_proj": (512, 16 * (128 + 128)), "o_proj": (16 * 128, 2048)}
    assert shapes["layer_0"]["mlp"]["gate_proj"]["kernel"].shape \
        == (2048, 10944)
    assert moe["gate_proj"].shape == (8, 2048, 1408)
    assert moe["router"].shape == (2048, 64)
    assert moe["shared"]["down_proj"]["kernel"].shape == (2 * 1408, 2048)
    for number in ("2048", "16 latent", "512", "128 + 64", "values of 128",
                   "factor 40", "4,096", "0.707", "10944", "1408",
                   "64 outputs", "6 experts a token", "2 shared"):
        assert number in config["no_width_is_cut"], number
    assert "1.33" in config["deployment"]


def test_the_closed_forms_at_the_published_sizes():
    from benchmark.families import deepseek_v2 as family
    traffic = bench_json("traffic", "train_b1_s8192_every16.json")
    d, h, seq = 2048, 16, 8192
    mla = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    moe = d * 64 + 3 * d * 1408 * (2 + 6 * 8 / 64)
    active = 6 * mla + 5 * moe + 3 * d * 10944 + d * 12800
    assert family.active_matmul_params(CONFIG) == active
    assert round(active / 1e6, 1) == 295.6
    scores = 3 * 2 * (192 + 128) * h * seq * (seq + 1) / 2 * 6
    assert family.mla_attn_flops_per_step(CONFIG, 1, seq) == scores
    assert round(scores / 1e12, 2) == 6.19
    assert family.train_flops_per_token(CONFIG, traffic) == \
        6 * active + scores / seq
    assert round(family.train_flops_per_token(CONFIG, traffic) / 1e9, 2) \
        == 2.53
    # every held expert on every token, five routed layers
    assert family.expert_flops_per_step(CONFIG, seq) == \
        18 * d * 1408 * seq * 8 * 5
    assert round(family.expert_flops_per_step(CONFIG, seq) / 1e12, 1) == 17.0


# ------------------------- the family: its batches, its reference's numbers


def test_step_1_is_read_on_the_batch_step_0_trained_on():
    """``drivers/train_fit.py`` compares the loss of step 0 on ``pool[0]``
    and of step 1 on ``pool[1]``: the family's second batch is the first
    once more, so the second number is the step's own effect; every other
    batch is ``families/lm.py``'s."""
    from benchmark.families import deepseek_v2 as family
    from benchmark.families import lm as lm_family
    traffic = {"seq": 16}
    pool = family.host_batches(CONFIG, traffic, 2, 3100000601, 8)
    plain = lm_family.host_batches(CONFIG, traffic, 2, 3100000601, 8)
    assert len(pool) == 8 and pool[1] is pool[0]
    for i in (0, 2, 3, 4, 5, 6, 7):
        assert (pool[i]["tokens"] == plain[i]["tokens"]).all()
    assert pool[0]["tokens"].max() < CONFIG["vocab_size"]
    assert len({b["tokens"].tobytes() for b in pool}) == 7
    assert len(family.host_batches(CONFIG, traffic, 2, 5, 1)) == 1


@pytest.mark.parametrize("key, other", [
    ("num_experts_per_tok", 4), ("rms_norm_eps", 1e-5),
    ("rope_theta", 500000.0), ("aux_loss_alpha", 0.01),
    ("rope_scaling", {"type": "yarn", "factor": 8})])
def test_the_family_refuses_numbers_its_reference_does_not_state(key, other):
    """The driver calls the reference with the constants it states and
    hands it no configuration: a file that differs is refused by name,
    not compared with another model."""
    from benchmark.families import deepseek_v2 as family
    tiny = bench_json("tests", "configs", "deepseek_v2_lite_tiny.json")
    for config in (CONFIG, tiny):
        family.held_to_the_reference(config)
        if key == "aux_loss_alpha":
            config = dict(config, assumed={"aux_loss_alpha": other})
        else:
            config = dict(config, **{key: other})
        with pytest.raises(ValueError, match=key):
            family.train_setup(config, {"seq": 16}, 1, 0)


# ----------------------------- the shape rules, as they decide for the cell


def test_the_programs_own_rules_decide_this_cells_step():
    """Blocks recomputed (16 B x 635 M is over half a v5e), the flash
    kernel at seq 8,192 over 192 score features, the PLAIN head (the
    logits are under the lean head's bytes and the slice under its
    rows)."""
    total = CONFIG["parameters_as_built"]["total"]
    assert lm.auto_remat_blocks(total, 6, 16e9)
    assert not lm.auto_remat_blocks(total, 6, 32e9)
    assert lm.auto_flash_attention(8192, 128 + 64, "tpu")
    assert 4 * 8192 * 12800 < lm.LEAN_HEAD_LOGIT_BYTES and 12800 < 32768


def test_the_gauge_counts_every_latent_layer_on_the_kernel():
    from tests.test_deepseek_v2 import tiny_config
    loss_fn, params, batch, _ = lm.make_train_setup(
        tiny_config(), seq_len=16, batch_size=1, seed=0, attention="flash")
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["attention.flash_layers"] == 3
    assert gauges["attention.kda_kernel_layers"] == 0


# ---- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_deepseek_v2.py)


PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at the rehearsal's
    tiny size, read as the benchmark's driver reads a run."""
    from benchmark.tools import loss_limit_deepseek_v2 as tool
    config = bench_json("tests", "configs", "deepseek_v2_lite_tiny.json")
    traffic = dict(bench_json("traffic", "train_b1_s8192_every16.json"),
                   batch_per_chip=2, seq=SEQ)
    rows = tool.readings(config, traffic, 7, CELL["loss_rtol"])
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    from benchmark.tools import loss_limit_deepseek_v2 as tool
    assert PLANTED == sorted(tool.faults())
    assert not set(CELL["loss_rtol_refuses"]) & set(
        CELL["loss_rtol_lets_through"])
    # the configuration's own precision is never refused
    assert set(tool.WITHIN) <= set(CELL["loss_rtol_lets_through"])
    # the nearest precision under the configuration's, a state left
    # unchanged and another learning rate are refused (REVIEW 31), and of
    # what this PR brought to the mixer the blend past the window and the
    # temperature (the rotation left out altogether reads just over the
    # limit, not by the noise: the file says so)
    assert {"computed_in_float8_e4m3fn", "no_step", "adam_lr_doubled",
            "yarn_blend_left_out", "yarn_temperature_left_out",
            "latent_norm_left_out", "shared_experts_half_as_wide"} <= set(
        CELL["loss_rtol_refuses"])


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


def limit_record(fault, round=2):
    """Round 2's readings (the family's second batch repeats the first:
    what the cell runs); round 1's were taken on eight distinct batches."""
    return [r["reading"] for r in bench_lines("records",
                                              "pr31_loss_limit.jsonl")
            if r.get("fault") == fault and r["round"] == round]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len(sound) >= 20
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


@pytest.mark.parametrize("fault, times", [
    ("computed_in_float8_e4m3fn", 30), ("no_step", 30),
    ("adam_lr_doubled", 8)])
def test_the_limit_lies_between_its_two_readings_with_room(fault, times):
    """Three times over the worst sound run, and the nearest precision
    under bfloat16, a state left unchanged and a doubled rate each many
    times over the limit at both planted seeds. On eight distinct batches
    (round 1) the first two read within 1.1 of the limit of that round."""
    assert len(limit_record(fault)) >= 2
    assert min(limit_record(fault)) > times * CELL["loss_rtol"]
    assert max(limit_record("computed_in_bfloat16")) < CELL["loss_rtol"] / 3
    if fault != "adam_lr_doubled":
        assert min(limit_record(fault, round=1)) < 1.1 * 8e-4


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr31_loss_limit.jsonl): a fault is
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
    """``deepseek_v2_lite_train_1chip``'s step as the benchmark builds it ON
    THE CHIP (``benchmark/tools/aot_compile_as_on_the_chip.py``), at the
    published widths and 1 x 8,192 tokens: the two flash kernels at
    192 / 128 (``flash_fwd`` and, since PR 34, the one backward kernel
    ``flash_bwd``) ONCE in each of six layers (a recomputed block keeps the
    forward kernel's output and log-sum-exp by name: until PR 32 it ran
    the forward once more), no grouped-matmul kernel (a share of the
    experts runs every held expert on every token), every block
    recomputed, and state + scratch inside 16 GB (since PR 46 nothing
    else is at rest: set-up lets go of the initial parameters)."""
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
            out = aot_compile.compile_cell("deepseek_v2_lite_train_1chip",
                                           devices)
    finally:
        autodist_tpu.reset()
    text = compiled[0].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 6 * 2
    for kernel in ("flash_fwd", "flash_bwd"):
        assert sum(kernel in line for line in calls) == 6
    assert all("mla_core" in line for line in calls)
    # (no kernel of the twelve is the grouped matmul's; the module's TEXT may
    # name megablox's file among its source files where an earlier test of
    # the same process traced a cached ``jnp`` function from inside it)
    assert not any("gmm" in line for line in calls)
    assert "rematted_computation" in text
    step = out["train_step"]
    total = CONFIG["parameters_as_built"]["total"]
    # float32 master weights and Adam's two moments: 12 B a parameter
    assert abs(step["argument_size_in_bytes"] - 12 * total) < 1 << 20
    # 5.82 GB since PR 46 keeps the dense layer's and the five shared
    # experts' gate and up products too (359 MB + 5 x 92 MB; 5.00 GB since
    # PR 41 keeps the held experts' in all five routed layers, the closed
    # form's 5 x 369 MB; 3.15 GB since PR 32 keeps the forward kernel's
    # output and q in six layers; 2.21 GB, PR 31); the chip loaded it, cold
    # and from the cache (PERF.md section 6: 13.6 GB with what is at rest,
    # where it stood at 15.35 with the initial parameters kept)
    assert step["temp_size_in_bytes"] < 5.9e9
    assert step["live_bytes_estimate"] < 13.6e9
    # 9 expert products a routed layer, none made a second time
    products = [line for line in text.splitlines()
                if " convolution(" in line and "moe_experts" in line]
    assert len(products) == 9 * 5
    assert not [line for line in products if "rematted_computation" in line]
    record = bench_json("records", "pr46_aot_memory.json")["cells"][
        "deepseek_v2_lite_train_1chip"]["change"]["train_step"]
    assert abs(record["temp_size_in_bytes"] - step["temp_size_in_bytes"]) \
        < 0.05 * step["temp_size_in_bytes"]
