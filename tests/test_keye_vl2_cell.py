"""Keye-VL-2.0's configuration and cell (``tests/test_keye_vl2.py`` holds
the model to its reference): the configuration file against the catalog's
row key by key and against the tree it builds, the closed-form FLOPs
against the program's own products at a tiny size, the shape rules of
``make_train_setup`` for this cell, the selected core compiled for a
described v5e, and what the cell's ``loss_rtol`` refuses
(``benchmark/tools/loss_limit_keye_vl2.py``)."""
import dataclasses
import json
import math
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


CONFIG = bench_json("configs", "keye_vl2_30b_a3b.json")
CELL = bench_json("workloads", "keye_vl2_train_1chip.json")
REDUCED = ["num_experts", "num_hidden_layers", "vocab_size"]


def tiny_file(**sa):
    """The rehearsal's tiny configuration with a choice that bites at
    SEQ = 32 (``topk`` 8 of an indexer of 2 heads of 8, 4 rotated)."""
    config = bench_json("tests", "configs", "keye_vl2_tiny.json")
    config["sa_config"] = dict(config["sa_config"], topk=8,
                               indexer_head_dim=8, **sa)
    config["assumed"] = {"indexer_rope_dim": 4}
    config["num_experts_per_tok"] = 3
    return config


# ------------------------------------------------- the config, the preset


def test_the_published_preset_is_the_files_published_block():
    cfg = lm.LMConfig.keye_vl2_30b_a3b()
    pub = CONFIG["published"]
    sa = pub["sa_config"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.norm_eps, cfg.rope_theta,
            cfg.mlp_dim, cfg.num_experts, cfg.experts_per_token,
            cfg.moe_renormalize, cfg.max_seq_len, cfg.indexer_num_heads,
            cfg.indexer_head_dim, cfg.indexer_topk, cfg.indexer_q_chunk) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"], pub["vocab_size"], pub["rms_norm_eps"],
        pub["rope_theta"], pub["moe_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["norm_topk_prob"],
        pub["max_position_embeddings"], sa["indexer_num_heads"],
        sa["indexer_head_dim"], sa["topk"], sa["q_chunk_size"])
    assert sa["indexer_num_kv_heads"] == 1 and sa["kv_chunk_size"] == 512
    assert cfg.router_activation == "softmax" and cfg.qk_head_norm
    assert cfg.experts_held is None     # the published model holds them all
    assert cfg.indexer_rope_dim == CONFIG["assumed"]["indexer_rope_dim"] == 32
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale
                or cfg.router_aux_loss_coef or cfg.num_shared_experts
                or pub["attention_bias"] or pub["tie_word_embeddings"])
    assert pub["decoder_sparse_step"] == 1 and pub["mlp_only_layers"] == []
    from benchmark.reference import keye_vl2 as ref
    assert (ref.TOP_K, ref.RMS_EPS, ref.ROPE_THETA, ref.INDEX_TOPK,
            ref.INDEX_ROPE_DIM) == (
        pub["num_experts_per_tok"], pub["rms_norm_eps"], pub["rope_theta"],
        sa["topk"], CONFIG["assumed"]["indexer_rope_dim"])


def test_the_catalogs_row_is_the_files_published_block_key_by_key():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "Keye-VL-2.0-30B-A3B"][0]
    assert row["config"] == CONFIG["published"]
    assert row["source_url"] == CONFIG["source"]
    # every key of the row is in the file as it is run, under the same
    # name, and differs only where ``reduced`` says
    assert [k for k, v in row["config"].items() if CONFIG[k] != v] \
        and sorted(k for k, v in row["config"].items()
                   if CONFIG[k] != v) == REDUCED
    entry = [c for c in bench_json("..", "BENCHMARK.json")["configs"]
             if c["name"] == "keye_vl2_30b_a3b"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds, no
    width differs from the source, and ``reduced`` names every key that
    does."""
    from benchmark.families import keye_vl2 as family
    config = CONFIG
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert sorted(differs) == sorted(config["reduced"]) == REDUCED
    assert sorted(config["reduced_why"]) == REDUCED
    assert config["router_num_experts"] == config["published"]["num_experts"]
    assert config["experts_held"] == list(range(config["num_experts"]))
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 151936 // 8)
    for key in ("qk_head_norm", "indexer_k_norm", "indexer_rope_dim",
                "chunks", "router_loss", "optimizer"):
        assert key in config["assumed"], key
    for word in ("alignment loss", "vision tower", "Hadamard"):
        assert word in config["departures"], word
    cfg = family.model_config(config, 8192)
    want = dataclasses.replace(
        lm.LMConfig.keye_vl2_30b_a3b(num_layers=5), dtype=cfg.dtype,
        vocab_size=18992, experts_held=tuple(range(16)))
    assert cfg == want
    shapes = jax.eval_shape(lambda: lm.TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    built = config["parameters_as_built"]
    count = lambda tree: sum(  # noqa: E731
        a.size for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == built["total"] == 562290560
    mixer = shapes["layer_0"]["MultiHeadAttention_0"]
    assert count(mixer["indexer"]) == built["indexer"]
    assert count(mixer) - count(mixer["indexer"]) == built["attention"]
    moe = shapes["layer_4"]["moe"]
    assert moe["router"].size == built["router"]
    assert 3 * moe["gate_proj"].size == built["held_experts_per_layer"] \
        == 16 * built["one_expert"]
    assert count(shapes["layer_2"]) == built["layer"]
    assert shapes["embed"]["embedding"].size == built["embedding"]
    assert count(shapes["lm_head"]) == built["head"]
    assert family.active_matmul_params(config) == \
        built["active_matmul_per_token"]
    for bytes_ in ("6.75 GB", "9.00 GB", "13.49 GB"):
        assert bytes_ in built["bytes"]
    assert round(12 * built["total"] / 1e9, 2) == 6.75
    assert round(24 * built["total"] / 1e9, 2) == 13.49
    # no width is cut: the tree's shapes are the published widths, and the
    # file's sentence names each of them
    assert {k: v["kernel"].shape for k, v in mixer.items()
            if "kernel" in v} == {
        "query": (2048, 32, 128), "key": (2048, 4, 128),
        "value": (2048, 4, 128), "out": (32, 128, 2048)}
    assert {k: v["kernel"].shape for k, v in mixer["indexer"].items()
            if "kernel" in v} == {
        "wq": (2048, 16 * 64), "wk": (2048, 64), "weights_proj": (2048, 16)}
    assert moe["gate_proj"].shape == (16, 2048, 768)
    assert moe["router"].shape == (2048, 128)
    for number in ("2048", "32 query heads over 4", "128", "1e7",
                   "16 heads of 64", "topk 2048", "768", "128 outputs",
                   "8 experts a token"):
        assert number in config["no_width_is_cut"], number
    assert "2.0 x" in config["deployment"]


def test_the_closed_forms_at_the_published_sizes():
    from benchmark.families import keye_vl2 as family
    traffic = bench_json("traffic", "train_b1_s8192_every16.json")
    d, seq = 2048, 8192
    attn = 2 * d * 128 * (32 + 4)
    moe = d * 128 + 3 * d * 768 * (8 * 16 / 128)
    active = 5 * (attn + moe) + d * 18992
    assert family.active_matmul_params(CONFIG) == active
    assert round(active / 1e6, 1) == 158.2
    # every key a query sees while there are no more than 2,048, then 2,048
    pairs = 2048 * 2049 // 2 + (seq - 2048) * 2048
    assert family.chosen_pairs(CONFIG, seq) == pairs == 14681088
    assert pairs / (seq * (seq + 1) // 2) == pytest.approx(0.4375, abs=5e-5)
    assert family.chosen_pairs(CONFIG, 1024) == 1024 * 1025 // 2
    core = 3 * 2 * (128 + 128) * 32 * pairs * 5
    assert family.dsa_core_flops_per_step(CONFIG, 1, seq) == core
    assert round(core / 1e12, 2) == 3.61
    index = (2 * 16 * 64 * seq * (seq + 1) / 2
             + 2 * d * (16 * 64 + 64 + 16) * seq) * 5
    assert family.dsa_index_flops_per_step(CONFIG, 1, seq) == index
    assert round(index / 1e12, 3) == 0.529
    assert family.train_flops_per_token(CONFIG, traffic) == \
        6 * active + (core + index) / seq
    assert round(family.train_flops_per_token(CONFIG, traffic) / 1e9, 2) \
        == 1.45
    # every held expert on every token, five layers: 16 times the model's
    assert family.expert_flops_per_step(CONFIG, seq) == \
        18 * d * 768 * seq * 16 * 5
    assert round(family.expert_flops_per_step(CONFIG, seq) / 1e12, 2) == 18.55


def dot_flops(jaxpr, times=1):
    """2 x multiply-adds of every ``dot_general`` of a jaxpr, a scan's
    body as often as it runs."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * times * math.prod(eqn.outvars[0].aval.shape) \
                * math.prod(lhs[i] for i in contract)
        inner_times = times * (eqn.params["length"]
                               if eqn.primitive.name == "scan" else 1)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            total += dot_flops(inner, inner_times)
    return total


def test_the_closed_forms_count_the_programs_own_products():
    """The forward pass of the tiny model, traced: the FLOPs of its
    ``dot_general``s are the closed forms' pieces, each by the ratio the
    family states. Projections, router and head 2 a parameter and token;
    EVERY held expert on every token (``expert_flops_per_step`` / 3); the
    index scores over 10 of the square's 16 quarters (``ops/dsa.py``: four
    runs of query blocks, each against the keys up to its last query)
    where the closed form counts the causal half; XLA's scores over the
    whole square where the closed form counts the chosen pairs."""
    from benchmark.families import keye_vl2 as family
    config = tiny_file()
    rows = 2
    cfg = family.model_config(config, SEQ)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=rows, seed=0)
    counted = dot_flops(jax.make_jaxpr(loss_fn)(params, batch).jaxpr)
    tokens = rows * SEQ
    d, f, layers = 48, 32, config["num_hidden_layers"]
    here = 3 * 4 / 16
    proj = 2 * tokens * (family.active_matmul_params(config)
                         - layers * 3 * d * f * here)
    experts = family.expert_flops_per_step(config, tokens) / 3
    pairs = family.chosen_pairs(config, SEQ)
    assert pairs == 8 * 9 // 2 + (SEQ - 8) * 8
    core = family.dsa_core_flops_per_step(config, rows, SEQ) / 3 \
        * SEQ * SEQ / pairs
    index_proj = 2 * family.indexer_params(config) * tokens * layers
    index_scores = family.dsa_index_flops_per_step(config, rows, SEQ) \
        - index_proj
    from autodist_tpu.ops import dsa
    runs = dsa.SEGMENTS
    assert (runs + 1) / (2 * runs) == 10 / 16
    index = index_proj + index_scores * SEQ * (runs + 1) / (runs * (SEQ + 1))
    assert counted == proj + experts + core + index


# ------------------------- the family: its batches, its reference's numbers


def test_step_1_is_read_on_the_batch_step_0_trained_on():
    from benchmark.families import keye_vl2 as family
    from benchmark.families import lm as lm_family
    traffic = {"seq": 16}
    pool = family.host_batches(CONFIG, traffic, 2, 3700000601, 8)
    plain = lm_family.host_batches(CONFIG, traffic, 2, 3700000601, 8)
    assert len(pool) == 8 and pool[1] is pool[0]
    for i in (0, 2, 3, 4, 5, 6, 7):
        assert (pool[i]["tokens"] == plain[i]["tokens"]).all()
    assert pool[0]["tokens"].max() < CONFIG["vocab_size"]
    assert len({b["tokens"].tobytes() for b in pool}) == 7
    assert len(family.host_batches(CONFIG, traffic, 2, 5, 1)) == 1


@pytest.mark.parametrize("key, other", [
    ("num_experts_per_tok", 4), ("rms_norm_eps", 1e-5),
    ("rope_theta", 10000.0), ("norm_topk_prob", False),
    ("topk", 1024), ("indexer_rope_dim", 64)])
def test_the_family_refuses_numbers_its_reference_does_not_state(key, other):
    """The driver calls the reference with the constants it states and
    hands it no configuration: a file that differs is refused by name,
    not compared with another model."""
    from benchmark.families import keye_vl2 as family
    tiny = bench_json("tests", "configs", "keye_vl2_tiny.json")
    for config in (CONFIG, tiny):
        family.held_to_the_reference(config)
        if key == "topk":
            config = dict(config, sa_config=dict(config["sa_config"],
                                                 topk=other))
        elif key == "indexer_rope_dim":
            config = dict(config, assumed={"indexer_rope_dim": other})
        else:
            config = dict(config, **{key: other})
        with pytest.raises(ValueError, match=key):
            family.train_setup(config, {"seq": 16}, 1, 0)


# ----------------------------- the shape rules, as they decide for the cell


def test_the_programs_own_rules_decide_this_cells_step():
    """Blocks recomputed (16 B x 562 M is over half a v5e), the flash
    kernels at seq 8,192 over heads of 128, the LEAN head (the logits are
    over its bytes though the slice is under its rows)."""
    total = CONFIG["parameters_as_built"]["total"]
    assert lm.auto_remat_blocks(total, 5, 16e9)
    assert not lm.auto_remat_blocks(total, 5, 32e9)
    assert lm.auto_flash_attention(8192, 128, "tpu")
    assert 4 * 8192 * 18992 >= lm.LEAN_HEAD_LOGIT_BYTES and 18992 < 32768


def test_the_gauge_counts_every_layer_on_the_kernel_and_the_counters_exist():
    from tests.test_keye_vl2 import tiny_config
    loss_fn, params, batch, _ = lm.make_train_setup(
        tiny_config(), seq_len=16, batch_size=1, seed=0, attention="flash")
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["attention.flash_layers"] == 2
    assert loss_fn.device_counters == (
        "moe.max_expert_pairs", "moe.routed_pairs", "moe.chosen_pairs",
        "dsa.selected_pairs", "dsa.causal_pairs")


# ------------------ the selected core, compiled for a described v5e chip


@pytest.mark.parametrize("selected", [True, False])
def test_the_grouped_core_compiles_for_a_v5e_with_and_without_a_choice(
        selected):
    """The cell's core, 32 query heads over 4 K/V heads of 128 at seq
    8,192 in bfloat16, forward and the ONE backward kernel, through XLA:TPU
    and Mosaic for a described chip (the int8 selection tile and the
    per-query-head dk / dv blocks fit VMEM beside dq's accumulator), with
    the [1, 8192, 8192] selection and without one."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from autodist_tpu.ops import flash_attention as fa, pallas_mode
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to test
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    chip = SingleDeviceSharding(topo.devices[0])
    S = 8192
    q, kv, sel = (jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                  for shape, dtype in (((1, S, 32, 128), jnp.bfloat16),
                                       ((1, S, 4, 128), jnp.bfloat16),
                                       ((1, S, S), jnp.int8)))

    def loss(q, k, v, sel):
        return jnp.sum(fa.flash_attention(q, k, v, True, select=sel)
                       .astype(jnp.float32))
    with pallas_mode.compiling_for_tpu():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv, sel if selected else None).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    # the choice reaches the kernels as it is, int8; without one the
    # program holds no such operand
    assert ("s8[1,8192,8192]" in text) == selected


# ---- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_keye_vl2.py)


PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at a tiny size with
    a choice that bites (``topk`` 8 at seq 32), read as the benchmark's
    driver reads a run."""
    from benchmark.tools import loss_limit_keye_vl2 as tool
    traffic = dict(bench_json("traffic", "train_b1_s8192_every16.json"),
                   batch_per_chip=2, seq=SEQ)
    rows = tool.readings(tiny_file(), traffic, 7, CELL["loss_rtol"])
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    from benchmark.tools import loss_limit_keye_vl2 as tool
    assert PLANTED == sorted(tool.faults())
    assert not set(CELL["loss_rtol_refuses"]) & set(
        CELL["loss_rtol_lets_through"])
    # the configuration's own precision is never refused
    assert set(tool.WITHIN) <= set(CELL["loss_rtol_lets_through"])
    # the nearest precision under the configuration's, a state left
    # unchanged and the choice left out are refused. ISSUE 37 also asked
    # for topk halved, a wrong K/V grouping and the per-head norm left
    # out: each reads over the limit at one seed and not by the noise at
    # both, and the file says so (tests/test_keye_vl2.py holds them)
    assert {"computed_in_float8_e4m3fn", "no_step",
            "choice_left_out"} <= set(CELL["loss_rtol_refuses"])
    assert {"topk_halved", "every_query_head_on_kv_head_0",
            "head_norm_left_out"} <= set(CELL["loss_rtol_lets_through"])


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_moves_what_the_driver_reads(tiny_readings, fault):
    """The faults are really planted: each moves the reading by far more
    than the 1e-5 the float32 program and reference differ by (the indexer
    alone in bfloat16 moves a handful of choices: by more than nothing)."""
    assert tiny_readings["sound"] == 0.0
    if fault == "computed_in_bfloat16":
        assert RTOL < tiny_readings[fault] < tiny_readings[
            "computed_in_float8_e4m3fn"]
    elif fault == "indexer_in_bfloat16":
        assert tiny_readings[fault] > 0
    else:
        assert tiny_readings[fault] > 10 * RTOL


def limit_record(fault):
    return [r["reading"] for r in bench_lines("records",
                                              "pr37_loss_limit.jsonl")
            if r.get("fault") == fault]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len(sound) >= 11
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


@pytest.mark.parametrize("fault, times", [
    ("computed_in_float8_e4m3fn", 30), ("no_step", 8)])
def test_the_limit_lies_between_its_two_readings_with_room(fault, times):
    """Three times over the worst sound run on the chip, and the nearest
    precision under bfloat16 and a state left unchanged each many times
    over it at both planted seeds; the configuration's own precision
    under a third of it."""
    assert len(limit_record(fault)) >= 2
    assert min(limit_record(fault)) > times * CELL["loss_rtol"]
    assert max(limit_record("computed_in_bfloat16")) < CELL["loss_rtol"] / 3


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr37_loss_limit.jsonl): a fault is
    REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])


def test_the_layer_0_choice_agrees_with_the_references_on_the_chip():
    """The program's bfloat16 embedding rows and bfloat16 RMSNorm feed a
    float32 indexer; the reference is float32 throughout: on the chip, at
    the published widths and 1 x 8,192, they make the same choice on
    0.9987 of layer 0's causal pairs (ISSUE 37 expected 0.999: recorded as
    measured), every query keeping min(its keys, topk) on both sides; the
    reference with its indexer alone in bfloat16 agrees with itself on
    fewer, where the record has that reading."""
    rows = [r for r in bench_lines("records", "pr37_loss_limit.jsonl")
            if r.get("check") == "layer_0_choice_agreement"]
    assert rows
    for r in rows:
        assert 0.998 <= r["agree"] < 1
        assert r["program_chose"] == r["reference_chose"] == 14681088
        assert r["causal_pairs"] == 8192 * 8193 // 2
        assert r.get("a_bfloat16_indexer_would_agree", 0) < r["agree"]


def test_the_agreement_of_a_float32_program_is_whole():
    """``tools/loss_limit_keye_vl2.py --agreement`` at a tiny size in
    float32 on the CPU: program and reference choose the same keys, and
    the counts are the closed form's."""
    from benchmark.families import keye_vl2 as family
    from benchmark.tools import loss_limit_keye_vl2 as tool
    traffic = dict(bench_json("traffic", "train_b1_s8192_every16.json"),
                   batch_per_chip=2, seq=SEQ)
    got = tool.agreement(tiny_file(), traffic, 7)
    assert got["agree"] == got["chosen_by_both_over_either"] == 1.0
    assert got["program_chose"] == got["reference_chose"] \
        == 2 * family.chosen_pairs(tiny_file(), SEQ)
    assert got["causal_pairs"] == 2 * SEQ * (SEQ + 1) // 2
