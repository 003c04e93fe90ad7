"""LFM2-24B-A2B's configuration and cell (``tests/test_lfm2_moe.py`` holds
the model to its reference): the configuration file against the catalog's
row key by key and against the tree it builds, the closed-form FLOPs and
bytes against the program's own products at a tiny size, the shape rules of
``make_train_setup`` for this cell, the grouped core at heads of 64
compiled for a described v5e, the model through ``Runner.fit``, and what
the cell's ``loss_rtol`` refuses
(``benchmark/tools/loss_limit_lfm2_moe.py``)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu import telemetry
from autodist_tpu.models import lm
from benchmark.reference import lfm2_moe as ref
from tests.test_keye_vl2_cell import bench_json, bench_lines, dot_flops
from tests.test_kimi_linear import close, cpu_spec, flat
from tests.test_lfm2_moe import HELD, SEQ, TOP_K, batches, tiny_config

HERE = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-5
CONFIG = bench_json("configs", "lfm2_24b_a2b.json")
CELL = bench_json("workloads", "lfm2_24b_a2b_train_1chip.json")
TINY_FILE = bench_json("tests", "configs", "lfm2_moe_tiny.json")
REDUCED = ["num_experts", "num_hidden_layers", "vocab_size"]


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


# ------------------------------------------------- the config, the preset


def test_the_published_preset_is_the_files_published_block():
    cfg = lm.LMConfig.lfm2_24b_a2b()
    pub = CONFIG["published"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.vocab_size, cfg.norm_eps, cfg.rope_theta, cfg.conv_size,
            cfg.dense_dim, cfg.mlp_dim, cfg.num_experts,
            cfg.experts_per_token, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.first_k_dense_replace,
            cfg.max_seq_len) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["vocab_size"], pub["norm_eps"],
        pub["rope_parameters"]["rope_theta"], pub["conv_L_cache"],
        pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["norm_topk_prob"],
        pub["routed_scaling_factor"], pub["num_dense_layers"],
        pub["max_position_embeddings"])
    from benchmark.families import lfm2_moe as family
    assert cfg.layer_types == family.layer_types(pub)
    assert cfg.head_dim is None and 2048 // 32 == 64
    assert cfg.router_activation == "sigmoid" and pub["use_expert_bias"]
    assert cfg.qk_head_norm and cfg.tie_embedding     # assumed: no key
    assert cfg.experts_held is None     # the published model holds them all
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale
                or cfg.router_aux_loss_coef or cfg.num_shared_experts)
    assert (ref.TOP_K, ref.RMS_EPS, ref.ROPE_THETA) == (
        pub["num_experts_per_tok"], pub["norm_eps"],
        pub["rope_parameters"]["rope_theta"])


def test_the_catalogs_row_is_the_files_published_block_key_by_key():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "LFM2-24B-A2B"][0]
    assert row["config"] == CONFIG["published"]
    assert row["source_url"] == CONFIG["source"]
    # every key of the row is in the file as it is run, under the same
    # name, and differs only where ``reduced`` says (``layer_types`` and
    # ``rope_parameters`` are copied whole)
    assert sorted(k for k, v in row["config"].items()
                  if CONFIG[k] != v) == REDUCED
    entry = [c for c in bench_json("..", "BENCHMARK.json")["configs"]
             if c["name"] == "lfm2_24b_a2b"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds, no
    width differs from the source, and ``reduced`` names every key that
    does."""
    from benchmark.families import lfm2_moe as family
    config = CONFIG
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert sorted(differs) == sorted(config["reduced"]) == REDUCED
    assert sorted(config["reduced_why"]) == REDUCED
    assert config["router_num_experts"] == config["published"]["num_experts"]
    assert config["experts_held"] == list(range(config["num_experts"]))
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 8, 65536 // 8)
    assert family.layer_types(config) == (
        "conv", "conv", "attention", "conv", "conv", "conv")
    for key in ("tie_embedding", "qk_head_norm", "expert_bias",
                "router_loss", "optimizer", "weights"):
        assert key in config["assumed"], key
    for word in ("1e-6", "Serving is not built", "last two gated inputs"):
        assert word in config["departures"], word
    cfg = family.model_config(config, 8192)
    want = dataclasses.replace(
        lm.LMConfig.lfm2_24b_a2b(num_layers=6), dtype=cfg.dtype,
        vocab_size=8192, experts_held=tuple(range(8)))
    assert cfg == want
    shapes = jax.eval_shape(lambda: lm.TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    built = config["parameters_as_built"]
    count = lambda tree: sum(  # noqa: E731
        a.size for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == built["total"] == 558424448
    assert "lm_head" not in shapes and built["head"] == 0     # tied
    assert count(shapes["layer_0"]["conv"]) == built["conv_mixer"]
    assert count(shapes["layer_2"]["MultiHeadAttention_0"]) \
        == built["attention"]
    assert count(shapes["layer_0"]["mlp"]) == built["dense_ffn"]
    moe = shapes["layer_3"]["moe"]
    assert moe["router"].size == built["router"]
    assert 3 * moe["gate_proj"].size == built["held_experts_per_layer"] \
        == 8 * built["one_expert"]
    assert (count(shapes["layer_1"]), count(shapes["layer_2"]),
            count(shapes["layer_5"])) == (
        built["dense_conv_layer"], built["attention_moe_layer"],
        built["conv_moe_layer"])
    assert shapes["embed"]["embedding"].size == built["embedding"]
    assert family.active_matmul_params(config) == \
        built["active_matmul_per_token"]
    for bytes_ in ("6.70 GB", "8.93 GB", "13.40 GB"):
        assert bytes_ in built["bytes"]
    assert round(12 * built["total"] / 1e9, 2) == 6.70
    assert round(24 * built["total"] / 1e9, 2) == 13.40
    # a seventh layer would not fit (ISSUE 40: 15.5 GB)
    assert 24 * (built["total"] + built["conv_moe_layer"]) > 15.5e9
    # no width is cut: the tree's shapes are the published widths, and the
    # file's sentence names each of them
    assert shapes["layer_0"]["conv"]["in_proj"]["kernel"].shape == (2048, 6144)
    assert shapes["layer_0"]["conv"]["conv"].shape == (3, 2048)
    mixer = shapes["layer_2"]["MultiHeadAttention_0"]
    assert {k: v["kernel"].shape for k, v in mixer.items()
            if "kernel" in v} == {
        "query": (2048, 32, 64), "key": (2048, 8, 64),
        "value": (2048, 8, 64), "out": (32, 64, 2048)}
    assert mixer["q_norm"]["scale"].shape == (64,)
    assert shapes["layer_0"]["mlp"]["gate_proj"]["kernel"].shape \
        == (2048, 11776)
    assert moe["gate_proj"].shape == (8, 2048, 1536)
    assert moe["router"].shape == (2048, 64)
    for number in ("2048", "3 taps", "6144", "32 query heads over 8",
                   "of 64", "1e6", "11776", "1536", "64 outputs",
                   "4 experts a token"):
        assert number in config["no_width_is_cut"], number
    assert "2.0 x" in config["deployment"]


def test_the_closed_forms_at_the_published_sizes():
    from benchmark.families import lfm2_moe as family
    traffic = bench_json("traffic", "train_b1_s8192_every16.json")
    d, seq = 2048, 8192
    conv, attn = 4 * d * d, 2 * d * 64 * (32 + 8)
    moe = d * 64 + 3 * d * 1536 * (4 * 8 / 64)
    active = 5 * conv + attn + 2 * 3 * d * 11776 + 4 * moe + d * 8192
    assert family.active_matmul_params(CONFIG) == active
    assert round(active / 1e6, 1) == 275.3
    core = 3 * 2 * (64 + 64) * 32 * seq * (seq + 1) / 2
    assert family.dsa_core_flops_per_step(CONFIG, 1, seq) == core
    assert round(core / 1e12, 3) == 0.825
    assert family.train_flops_per_token(CONFIG, traffic) == \
        6 * active + core / seq
    assert round(family.train_flops_per_token(CONFIG, traffic) / 1e9, 2) \
        == 1.75
    # the conv mixers' projections, 4 d^2 weights a layer, once forward
    # and twice backward (the recomputed forward is not model work)
    assert family.conv_mix_flops_per_step(CONFIG, seq) == \
        3 * 2 * conv * seq * 5
    assert round(family.conv_mix_flops_per_step(CONFIG, seq) / 1e12, 2) \
        == 4.12
    # every held expert on every token, four routed layers: 16 times the
    # model's (8 held where an even router sends half an expert a token)
    assert family.expert_flops_per_step(CONFIG, seq) == \
        18 * d * 1536 * seq * 8 * 4
    assert round(family.expert_flops_per_step(CONFIG, seq) / 1e12, 2) == 14.84


def test_the_closed_forms_count_the_programs_own_products():
    """The forward pass of the tiny model, traced: the FLOPs of its
    ``dot_general``s are the closed forms' pieces, each by the ratio the
    family states. Projections, router, dense layers and the tied head 2 a
    parameter and token; EVERY held expert on every token
    (``expert_flops_per_step`` / 3); XLA's scores over the whole square
    where the closed form counts the causal pairs. The conv cores hold no
    product at all."""
    from benchmark.families import lfm2_moe as family
    config = TINY_FILE
    rows = 2
    cfg = family.model_config(config, SEQ)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=rows, seed=0)
    counted = dot_flops(jax.make_jaxpr(loss_fn)(params, batch).jaxpr)
    tokens = rows * SEQ
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    here = 4 * 4 / 16
    proj = 2 * tokens * (family.active_matmul_params(config)
                         - 4 * 3 * d * f * here)
    experts = family.expert_flops_per_step(config, tokens) / 3
    core = family.dsa_core_flops_per_step(config, rows, SEQ) / 3 \
        * SEQ * SEQ / (SEQ * (SEQ + 1) / 2)
    assert counted == proj + experts + core


# ------------------------- the family: its batches, its reference's numbers


def test_step_1_is_read_on_the_batch_step_0_trained_on():
    from benchmark.families import lfm2_moe as family
    from benchmark.families import lm as lm_family
    traffic = {"seq": 16}
    pool = family.host_batches(CONFIG, traffic, 2, 4000000601, 8)
    plain = lm_family.host_batches(CONFIG, traffic, 2, 4000000601, 8)
    assert len(pool) == 8 and pool[1] is pool[0]
    for i in (0, 2, 3, 4, 5, 6, 7):
        assert (pool[i]["tokens"] == plain[i]["tokens"]).all()
    assert pool[0]["tokens"].max() < CONFIG["vocab_size"]
    assert len({b["tokens"].tobytes() for b in pool}) == 7
    assert len(family.host_batches(CONFIG, traffic, 2, 5, 1)) == 1


@pytest.mark.parametrize("key, other", [
    ("num_experts_per_tok", 8), ("norm_eps", 1e-6), ("rope_theta", 10000.0),
    ("norm_topk_prob", False), ("routed_scaling_factor", 2.5),
    ("use_expert_bias", False), ("conv_bias", True)])
def test_the_family_refuses_numbers_its_reference_does_not_state(key, other):
    """The driver calls the reference with the constants it states and
    hands it no configuration: a file that differs is refused by name,
    not compared with another model."""
    from benchmark.families import lfm2_moe as family
    for config in (CONFIG, TINY_FILE):
        family.held_to_the_reference(config)
        if key == "rope_theta":
            config = dict(config, rope_parameters={"rope_theta": other})
        else:
            config = dict(config, **{key: other})
        with pytest.raises(ValueError, match=key):
            family.train_setup(config, {"seq": 16}, 1, 0)


# ----------------------------- the shape rules, as they decide for the cell


def test_the_programs_own_rules_decide_this_cells_step():
    """Blocks recomputed (16 B x 558 M is over half a v5e), the flash
    kernels at seq 8,192 over heads of 64, the PLAIN head (the logits are
    under the lean head's bytes and the slice under its rows)."""
    total = CONFIG["parameters_as_built"]["total"]
    assert lm.auto_remat_blocks(total, 6, 16e9)
    assert not lm.auto_remat_blocks(total, 6, 32e9)
    assert lm.auto_flash_attention(8192, 64, "tpu")
    assert not lm.auto_flash_attention(4096, 64, "tpu")
    assert 4 * 8192 * 8192 < lm.LEAN_HEAD_LOGIT_BYTES and 8192 < 32768


def test_the_gauge_counts_the_attention_layer_alone_on_the_kernel():
    """Five conv layers run no attention function: ``attention.flash_layers``
    is 1 of the six."""
    loss_fn, params, batch, _ = lm.make_train_setup(
        tiny_config(), seq_len=16, batch_size=1, seed=0, attention="flash")
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["attention.flash_layers"] == 1
    assert gauges["attention.kda_kernel_layers"] == 0
    assert loss_fn.device_counters == (
        "moe.max_expert_pairs", "moe.routed_pairs", "moe.chosen_pairs")


# --------- the grouped core at heads of 64, compiled for a described v5e


def test_the_grouped_core_at_heads_of_64_compiles_for_a_v5e():
    """The cell's core, 32 query heads over 8 K/V heads of 64 (half a lane
    tile a head) at seq 8,192 in bfloat16, forward and the ONE backward
    kernel, through XLA:TPU and Mosaic for a described chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from autodist_tpu.ops import flash_attention as fa, pallas_mode
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to test
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    chip = SingleDeviceSharding(topo.devices[0])
    q, kv = (jax.ShapeDtypeStruct((1, 8192, heads, 64), jnp.bfloat16,
                                  sharding=chip) for heads in (32, 8))

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True).astype(jnp.float32))
    with pallas_mode.compiling_for_tpu():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd" in text


# ---------------------------------------------------- the normal path, fit


@pytest.mark.parametrize("devices", [1, 2])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """make_train_setup -> AutoDist(AllReduce()).build -> Runner.fit, as
    the other five configurations go, against ``train_check``
    (block-accumulated gradients, one float32 Adam step on the tied table's
    summed gradient): the routers' bias is in the state and does not move."""
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
                lambda p, b: ref.nll_sum(p, b, TOP_K, HELD),
                ref.batch_weight, params, pool[0], pool[1], jax.devices()[:1])
        after = flat(runner.gather_params())
    finally:
        autodist_tpu.reset()
    close(np.asarray(got), np.asarray(want))
    before = flat(params)
    for name in before:
        moved = np.any(np.asarray(after[name]) != np.asarray(before[name]))
        assert moved == ("e_score_correction_bias" not in name), name


# ---- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_lfm2_moe.py)


PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at a tiny size, read
    as the benchmark's driver reads a run."""
    from benchmark.tools import loss_limit_lfm2_moe as tool
    traffic = dict(bench_json("traffic", "train_b1_s8192_every16.json"),
                   batch_per_chip=2, seq=SEQ)
    rows = tool.readings(TINY_FILE, traffic, 7, CELL["loss_rtol"])
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    from benchmark.tools import loss_limit_lfm2_moe as tool
    assert PLANTED == sorted(tool.faults())
    assert not set(CELL["loss_rtol_refuses"]) & set(
        CELL["loss_rtol_lets_through"])
    # the configuration's own precision is never refused; the nearest
    # precision under it and a state left unchanged are
    assert set(tool.WITHIN) <= set(CELL["loss_rtol_lets_through"])
    assert {"computed_in_float8_e4m3fn", "no_step"} <= set(
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


def limit_record(fault):
    return [r["reading"] for r in bench_lines("records",
                                              "pr40_loss_limit.jsonl")
            if r.get("fault") == fault]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len(sound) >= 10
    assert len({r["seed"] for r in bench_lines(
        "records", "pr40_loss_limit.jsonl")
        if r.get("fault") == "sound_on_the_chip"}) >= 10
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


@pytest.mark.parametrize("fault", ["computed_in_float8_e4m3fn", "no_step"])
def test_the_limit_lies_between_its_two_readings_with_room(fault):
    """Three times over the worst sound run on the chip, and the nearest
    precision under bfloat16 and a state left unchanged each several times
    over it; the configuration's own precision under it."""
    assert limit_record(fault)
    assert min(limit_record(fault)) > 3 * CELL["loss_rtol"]
    assert max(limit_record("computed_in_bfloat16")) < CELL["loss_rtol"]


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr40_loss_limit.jsonl): a fault is
    REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])
