"""Ouro-2.6B's configuration and cell (``tests/test_ouro.py`` holds the model
to its reference): the configuration file against the catalog's row key by
key and against the tree it builds, the closed-form FLOPs against the
program's own products at a tiny size (every pass counted), the shape rules
of ``make_train_setup`` for this cell, the model through ``Runner.fit``,
and what the cell's ``loss_rtol`` refuses
(``benchmark/tools/loss_limit_ouro.py``)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.models import lm
from benchmark.reference import ouro as ref
from tests.test_keye_vl2_cell import bench_json, bench_lines, dot_flops
from tests.test_kimi_linear import close, cpu_spec, flat
from tests.test_ouro import LAYERS, PASSES, SEQ, batches, tiny_config

RTOL = 1e-5
NAME = "ouro_2_6b_train_1chip"
CONFIG = bench_json("configs", "ouro_2_6b.json")
CELL = bench_json("workloads", NAME + ".json")
TRAFFIC = bench_json("traffic", "train_b1_s4096_every16.json")
TINY_FILE = bench_json("tests", "configs", "ouro_tiny.json")
RECORD = "pr44_loss_limit.jsonl"


# ------------------------------------------------- the config, the preset


def test_the_published_preset_is_the_files_published_block():
    cfg = lm.LMConfig.ouro_2_6b()
    pub = CONFIG["published"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.head_dim,
            cfg.vocab_size, cfg.norm_eps, cfg.rope_theta, cfg.dense_dim,
            cfg.loop_steps, cfg.max_seq_len, cfg.tie_embedding) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["head_dim"], pub["vocab_size"],
        pub["rms_norm_eps"], pub["rope_theta"], pub["intermediate_size"],
        pub["total_ut_steps"], pub["max_position_embeddings"],
        pub["tie_word_embeddings"])
    assert pub["num_key_value_heads"] == pub["num_attention_heads"]
    assert cfg.num_kv_heads is None              # plain multi-head
    assert set(pub["layer_types"]) == {"full_attention"}
    assert pub["hidden_act"] == "silu" and pub["rope_scaling"] is None
    assert pub["early_exit_threshold"] == 1      # every pass, always
    assert cfg.exit_entropy_coef == CONFIG["assumed"]["exit_entropy_coef"]
    assert (ref.T, ref.BETA, ref.RMS_EPS, ref.ROPE_THETA) == (
        pub["total_ut_steps"], CONFIG["assumed"]["exit_entropy_coef"],
        pub["rms_norm_eps"], pub["rope_theta"])


def test_the_catalogs_row_is_the_files_published_block_key_by_key():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B"][0]
    assert row["config"] == CONFIG["published"]
    assert row["source_url"] == CONFIG["source"]
    # every key of the row is in the file as it is run, under the same
    # name, and differs only where ``reduced`` says
    assert [k for k, v in row["config"].items() if CONFIG[k] != v] \
        == ["num_hidden_layers"]
    entry = [c for c in bench_json("..", "BENCHMARK.json")["configs"]
             if c["name"] == "ouro_2_6b"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds, no
    width differs from the source, all four passes and the whole
    vocabulary are kept, and every assumption the reference marks is
    listed."""
    from benchmark.families import ouro as family
    config = CONFIG
    assert sorted(config["reduced_why"]) == config["reduced"]
    assert (config["num_hidden_layers"], config["total_ut_steps"],
            config["vocab_size"]) == (6, 4, 49152)
    for key in ("embed_scale", "normed_state_carried", "sandwich_norm",
                "attention_bias", "exit_gate", "exit_entropy_coef",
                "stage_II", "optimizer", "weights", "compute"):
        assert key in config["assumed"], key
    for word in ("8-stage pipeline", "stage 0", "head"):
        assert word in config["deployment"], word
    assert "loop_steps" not in config["departures"] \
        and "refuse a looped model by name" in config["departures"]
    cfg = family.model_config(config, 4096)
    assert cfg == lm.LMConfig.ouro_2_6b(num_layers=6, dtype=cfg.dtype)
    assert cfg.dtype == jnp.bfloat16 and cfg.first_k_dense_replace == 6
    shapes = jax.eval_shape(lambda: lm.TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    built = config["parameters_as_built"]
    count = lambda tree: sum(  # noqa: E731
        a.size for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == built["total"] == 509661185
    assert sorted(shapes) == sorted(
        ["embed", "exit_gate", "final_ln", "lm_head"]
        + ["layer_%d" % i for i in range(6)])       # each block ONCE
    layer = shapes["layer_0"]
    assert count(layer["MultiHeadAttention_0"]) == built["attention"]
    assert count(layer["mlp"]) == built["swiglu"]
    assert count(layer) == built["layer"] \
        == built["attention"] + built["swiglu"] + 4 * 2048
    assert shapes["embed"]["embedding"].size == built["embedding"] \
        == shapes["lm_head"]["kernel"].size == built["head"]
    assert count(shapes["exit_gate"]) == built["exit_gate"] == 2049
    assert built["total"] == 6 * built["layer"] + 2 * built["embedding"] \
        + built["final_norm"] + built["exit_gate"]
    assert layer["MultiHeadAttention_0"]["query"]["kernel"].shape \
        == (2048, 16, 128)
    assert layer["mlp"]["gate_proj"]["kernel"].shape == (2048, 5632)
    for number in ("2048", "16 heads of 128", "1e6", "5632", "49152",
                   "total_ut_steps 4"):
        assert number in config["no_width_is_cut"], number
    # 24 B a parameter at the end of set-up fit the chip, nine layers' not
    assert 24 * built["total"] < 0.8 * 16e9
    assert 24 * (built["total"] + 3 * built["layer"]) > 15.8e9


def test_the_closed_forms_at_the_published_sizes():
    """Every pass is counted: four times the layers' and the head's
    parameters, the cores of 24 applications."""
    from benchmark.families import ouro as family
    d, seq, T, L = 2048, 4096, 4, 6
    layer = 4 * d * d + 3 * d * 5632
    assert layer == 51380224
    active = T * (L * layer + d * 49152) + (T - 1) * d
    assert family.active_matmul_params(CONFIG) == active \
        == CONFIG["parameters_as_built"]["active_matmul_per_token"]
    assert round(active / 1e6, 1) == 1635.8
    assert family.block_applications(CONFIG) == 24
    core = 3 * 2 * 2 * 128 * 16 * seq * (seq + 1) / 2 * 24
    assert family.attn_core_flops_per_step(CONFIG, 1, seq) == core
    assert round(core / 1e12, 2) == 4.95
    assert family.train_flops_per_token(CONFIG, TRAFFIC) \
        == 6 * active + core / seq
    step = family.train_flops_per_token(CONFIG, TRAFFIC) * seq
    assert round(step / 1e12, 1) == 45.2
    # the shares ISSUE 44 states: blocks' matmuls, the four heads, the cores
    blocks, heads = 6 * seq * T * L * layer, 6 * seq * T * d * 49152
    assert [round(100 * part / step) for part in (blocks, heads, core)] \
        == [67, 22, 11]
    # one pass alone would read a quarter: mfu is reckoned on four
    once = dict(CONFIG, total_ut_steps=1)
    assert family.train_flops_per_token(once, TRAFFIC) * 4 \
        == pytest.approx(step / seq, rel=1e-5)
    assert family.tokens_per_row(TRAFFIC) == 4096


def test_the_closed_forms_count_the_programs_own_products():
    """The forward pass of the tiny model, traced: the FLOPs of its
    ``dot_general``s, the scanned body as often as it runs, are the closed
    forms' pieces. Projections, SwiGLU, head and gate 2 a parameter and
    token a USE; XLA's scores over the whole square where the closed form
    counts the causal pairs."""
    from benchmark.families import ouro as family
    config, rows = TINY_FILE, 2
    cfg = family.model_config(config, SEQ)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=rows, seed=0)
    counted = dot_flops(jax.make_jaxpr(loss_fn)(params, batch).jaxpr)
    tokens = rows * SEQ
    proj = 2 * tokens * family.active_matmul_params(config)
    core = family.attn_core_flops_per_step(config, rows, SEQ) / 3 \
        * SEQ * SEQ / (SEQ * (SEQ + 1) / 2)
    assert counted == proj + core


# ------------------------- the family: its batches, its reference's numbers


def test_step_1_is_read_on_the_batch_step_0_trained_on():
    from benchmark.families import lm as lm_family
    from benchmark.families import ouro as family
    traffic = {"seq": 16}
    pool = family.host_batches(CONFIG, traffic, 2, 4200000601, 8)
    plain = lm_family.host_batches(CONFIG, traffic, 2, 4200000601, 8)
    assert len(pool) == 8 and pool[1] is pool[0]
    for i in (0, 2, 3, 4, 5, 6, 7):
        assert (pool[i]["tokens"] == plain[i]["tokens"]).all()
    # ids over the WHOLE vocabulary
    assert 8192 < pool[0]["tokens"].max() < CONFIG["vocab_size"]
    assert len({b["tokens"].tobytes() for b in pool}) == 7


@pytest.mark.parametrize("key, other", [
    ("total_ut_steps", 2), ("exit_entropy_coef", 0.1),
    ("rms_norm_eps", 1e-5), ("rope_theta", 10000.0),
    ("num_key_value_heads", 1), ("tie_word_embeddings", True),
    ("early_exit_threshold", 0.5)])
def test_the_family_refuses_numbers_its_reference_does_not_state(key, other):
    """The driver calls the reference with the constants it states and
    hands it no configuration: a file that differs is refused by name,
    not compared with another model."""
    from benchmark.families import ouro as family
    for config in (CONFIG, TINY_FILE):
        family.held_to_the_reference(config)
        if key == "exit_entropy_coef":
            config = dict(config, assumed=dict(config["assumed"],
                                               **{key: other}))
        else:
            config = dict(config, **{key: other})
        with pytest.raises(ValueError, match=key):
            family.train_setup(config, {"seq": 16}, 1, 0)


def test_the_traffic_file_is_the_cells():
    assert TRAFFIC == {
        "kind": "train_fit", "batch_per_chip": 1, "seq": 4096, "pool": 8,
        "warm_steps": 3, "fit": {"metrics_every": 16},
        "trace_from_step": 16, "trace_steps": 16}
    entry = [w for w in bench_json("..", "BENCHMARK.json")["workloads"]
             if w["name"] == NAME][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ouro_2_6b", "train_b1_s4096_every16", 1)
    assert CELL["strategy"] == "AllReduce"


# ----------------------------- the shape rules, as they decide for the cell


def test_the_programs_own_rules_decide_this_cells_step():
    """Blocks recomputed (24 applications: 16 B x 510 M x 5 is over the
    v5e), the flash kernels at heads of 128 and whole 512-row tiles, the
    LEAN head (49,152 words), every core kept."""
    total = CONFIG["parameters_as_built"]["total"]
    assert lm.auto_remat_blocks(total, 6, 16e9, 4)
    assert lm.auto_remat_blocks(total, 6, 32e9, 4)
    assert not lm.auto_remat_blocks(total, 6, 32e9, 1)
    assert lm.auto_flash_attention(4096, 128, "tpu")
    assert not lm.auto_flash_attention(4096, 128, "cpu")
    assert CONFIG["vocab_size"] >= 32768
    one = lm.flash_kept_bytes(4096, 16, 128, 128, 2)
    assert 12 * total + 24 * one < (1 - lm.KEPT_EXPERTS_HBM_LEFT) * 16e9


# ---------------------------------------------------- the normal path, fit


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


@pytest.mark.parametrize("devices", [1, 2])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """make_train_setup -> AutoDist(AllReduce()).build -> Runner.fit, as
    the other six configurations go, against ``train_check``
    (block-accumulated gradients, one float32 Adam step on the shared
    weights' summed gradient); the step hands out the exit counters and
    every leaf moves, the gate's among them."""
    cfg, loss_fn, params, _, _ = tiny
    pool = batches(2, rows=2)
    autodist_tpu.reset()
    try:
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                                   resource_spec=cpu_spec(devices))
        runner = ad.build(loss_fn, optax.adam(1e-3), params, pool[0])
        runner.init(params)
        with jax.default_matmul_precision("highest"):
            history = runner.fit(iter(pool), steps=2)
            want = ref.train_check(ref.nll_sum, ref.batch_weight, params,
                                   pool[0], pool[1], jax.devices()[:1])
        after = flat(runner.gather_params())
    finally:
        autodist_tpu.reset()
    close(np.asarray([float(m["loss"]) for m in history]), np.asarray(want))
    counters = history[0]["counters"]
    masses = [float(counters["loop.exit_mass_%d" % t])
              for t in range(1, PASSES + 1)]
    assert abs(sum(masses) - 1.0) < 1e-5
    assert 0 < float(counters["loop.exit_entropy"]) <= np.log(PASSES) + 1e-6
    before = flat(params)
    assert len(before) == 4 + 1 + 11 * LAYERS
    for name in before:
        assert np.any(np.asarray(after[name]) != np.asarray(before[name])), \
            name


# ---- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_ouro.py)


PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at a tiny size, read
    as the benchmark's driver reads a run."""
    from benchmark.tools import loss_limit_ouro as tool
    traffic = dict(TRAFFIC, batch_per_chip=2, seq=SEQ)
    rows = tool.readings(TINY_FILE, traffic, 7, CELL["loss_rtol"])
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    from benchmark.tools import loss_limit_ouro as tool
    assert PLANTED == sorted(tool.faults())
    assert not set(CELL["loss_rtol_refuses"]) & set(
        CELL["loss_rtol_lets_through"])
    # the configuration's own precision is never refused; the nearest
    # precision under it is
    assert set(tool.WITHIN) <= set(CELL["loss_rtol_lets_through"])
    assert "computed_in_float8_e4m3fn" in CELL["loss_rtol_refuses"]
    # the nine faults ISSUE 44 lists, whichever way each falls
    assert set(PLANTED) - set(tool.WITHIN) == {
        "three_passes_of_four", "norm_between_passes_left_out",
        "head_after_the_last_pass_only", "gate_left_out_uniform_weights",
        "entropy_term_left_out", "output_norms_left_out", "adam_lr_doubled",
        "no_step", "computed_in_float8_e4m3fn"}


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
    return [r["reading"] for r in bench_lines("records", RECORD)
            if r.get("fault") == fault]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len({r["seed"] for r in bench_lines("records", RECORD)
                if r.get("fault") == "sound_on_the_chip"}) >= 15
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


def test_the_limit_lies_between_its_two_readings_with_room():
    """Three times over the worst sound run on the chip, the nearest
    precision under bfloat16 several times over it at every seed, the
    configuration's own precision well under it."""
    float8 = limit_record("computed_in_float8_e4m3fn")
    assert len(float8) >= 2 and min(float8) > 5 * CELL["loss_rtol"]
    assert 5 * max(limit_record("computed_in_bfloat16")) < CELL["loss_rtol"]


def test_a_step_left_out_can_hide_under_the_limit():
    """What the cell file says of ``no_step``: at one seed the sound step
    moved its own batch's loss by a thousandth, so leaving it out reads
    under the limit there and far over it at the other."""
    readings = limit_record("no_step")
    assert min(readings) < CELL["loss_rtol"] < max(readings) / 5
    assert "no_step" in CELL["loss_rtol_lets_through"]


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr44_loss_limit.jsonl): a fault is
    REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])
