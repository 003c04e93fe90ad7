"""The Nemotron-H tower's configuration and cell (``tests/test_nemotron_h.py``
holds the model to its reference): the configuration file against the
catalog's row key by key and against the tree it builds, the closed-form
FLOPs and bytes against the program's own products at a tiny size, the
shape rules of ``make_train_setup`` for this cell (the lean head engaged at
exactly its bytes, the kept counts by KIND), the model through
``Runner.fit``, and what the cell's ``loss_rtol`` refuses
(``benchmark/tools/loss_limit_nemotron_h.py``)."""
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
from benchmark.families import nemotron_h as family
from benchmark.reference import nemotron_h as ref
from tests.test_keye_vl2_cell import bench_json, bench_lines, dot_flops
from tests.test_kimi_linear import close, cpu_spec, flat
from tests.test_lm_pins import steps as pinned_steps
from tests.test_nemotron_h import (GROUPS, HELD, PATTERN, SEQ, TOP_K, batches,
                                   tiny_config)

RTOL = 1e-5
CONFIG = bench_json("configs", "nemotron_twotower_30b_a3b.json")
CELL = bench_json("workloads", "nemotron_twotower_train_1chip.json")
TINY_FILE = bench_json("tests", "configs", "nemotron_h_tiny.json")
REDUCED = ["n_routed_experts", "num_hidden_layers", "vocab_size"]
RECORD = ("records", "pr47_loss_limit.jsonl")


# ------------------------------------------------- the config, the preset


def test_the_published_preset_is_the_files_published_block():
    cfg = lm.LMConfig.nemotron_twotower_30b_a3b()
    pub = CONFIG["published"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.norm_eps, cfg.max_seq_len) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"], pub["vocab_size"], pub["layer_norm_epsilon"],
        pub["max_position_embeddings"])
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.ssm_state_size, cfg.mamba_conv_size, cfg.mamba_chunk) == (
        pub["mamba_num_heads"], pub["mamba_head_dim"], pub["n_groups"],
        pub["ssm_state_size"], pub["conv_kernel"], pub["chunk_size"])
    assert (cfg.mlp_dim, cfg.shared_expert_dim, cfg.num_shared_experts,
            cfg.num_experts, cfg.experts_per_token, cfg.moe_renormalize,
            cfg.routed_scaling_factor) == (
        pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"], pub["n_shared_experts"],
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["norm_topk_prob"], pub["routed_scaling_factor"])
    assert cfg.layer_types == family.layer_types(pub)
    assert pub["mlp_hidden_act"] == "relu2" and not cfg.expert_gated
    assert cfg.experts_held is None     # the published model holds them all
    assert (ref.TOP_K, ref.RMS_EPS, ref.SCALING, ref.N_GROUPS) == (
        pub["num_experts_per_tok"], pub["norm_eps"],
        pub["routed_scaling_factor"], pub["n_groups"])


def test_the_catalogs_row_is_the_files_published_block_key_by_key():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"][0]
    assert row["config"] == CONFIG["published"]
    assert row["source_url"] == CONFIG["source"]
    # every key of the row is in the file as it is run, under the same
    # name, and differs only where ``reduced`` says (the pattern is whole)
    assert sorted(k for k, v in row["config"].items()
                  if CONFIG[k] != v) == REDUCED
    entry = [c for c in bench_json("..", "BENCHMARK.json")["configs"]
             if c["name"] == "nemotron_twotower_30b_a3b"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds
    (``jax.eval_shape``: nothing is allocated), the eight layers are the
    pattern's first eight letters, no width differs from the source, and
    ``reduced`` names every key that does."""
    config = CONFIG
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert sorted(differs) == sorted(config["reduced"]) == REDUCED
    assert sorted(config["reduced_why"]) == REDUCED
    assert config["router_num_experts"] \
        == config["published"]["n_routed_experts"] == 128
    assert config["experts_held"] == list(range(config["n_routed_experts"]))
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (8, 8, 131072 // 8)
    assert config["hybrid_override_pattern"][:8] == "MEMEM*EM"
    family.held_to_the_reference(config)
    cfg = family.model_config(config, 8192)
    assert cfg.layer_types == PATTERN and cfg.dtype == jnp.bfloat16
    assert list(config["layers_built"].values()) == [
        "%s: %s" % (c, k) for c, k in zip("MEMEM*EM", PATTERN)]
    shapes = jax.eval_shape(
        lambda key: lm.TransformerLM(cfg).init(
            key, jnp.zeros((1, 16), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    built = config["parameters_as_built"]
    assert count(shapes) == built["total"] == 566838016
    assert [count(shapes["layer_%d" % i]) for i in range(8)] == [
        built[{"mamba2": "mamba_layer", "moe": "routed_layer",
               "attention": "attention_layer"}[k]] for k in PATTERN]
    moe = shapes["layer_1"]["moe"]
    assert set(moe) == {"router", "e_score_correction_bias", "up_proj",
                        "down_proj", "shared"}     # no gate matrix
    assert moe["up_proj"].shape == (8, 2688, 1856)
    assert count(moe["shared"]) == built["shared_expert"] == 2 * 2688 * 3712
    assert count(moe["up_proj"]) + count(moe["down_proj"]) \
        == built["held_experts_per_layer"] == 8 * built["one_expert"]
    assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape \
        == (2688, 4096 + 6144 + 64)
    assert count(shapes["embed"]) == built["embedding"] == built["head"]
    assert family.active_matmul_params(config) \
        == built["active_matmul_per_token"]


def test_the_closed_forms_at_the_published_sizes():
    traffic = bench_json("traffic", "train_b1_s8192_every16.json")
    d, seq = 2688, 8192
    mamba = d * (2 * 4096 + 2 * 8 * 128 + 64) + 4096 * d
    attn = 2 * d * 128 * (32 + 2)
    moe = d * 128 + 2 * d * 3712 + 2 * d * 1856 * (6 * 8 / 128)
    active = 4 * mamba + attn + 3 * moe + d * 16384
    assert family.active_matmul_params(CONFIG) == active
    core = 3 * 2 * (128 + 128) * 32 * seq * (seq + 1) / 2
    assert family.dsa_core_flops_per_step(CONFIG, 1, seq) == core
    assert round(core / 1e12, 2) == 1.65
    scan = 3 * 2 * (64.5 * (128 * 8 + 64 * 64) + 2 * 64 * 128 * 64) * 4
    assert family.ssd_scan_flops_per_step(CONFIG, 1) == scan
    assert round(scan * seq / 1e12, 3) == 0.271
    assert family.train_flops_per_token(CONFIG, traffic) \
        == 6 * active + core / seq + scan
    assert round(family.train_flops_per_token(CONFIG, traffic) * seq / 1e12,
                 2) == 16.39
    assert family.mamba_proj_flops_per_step(CONFIG, seq) \
        == 3 * 2 * mamba * seq * 4
    assert round(family.mamba_proj_flops_per_step(CONFIG, seq) / 1e12, 2) \
        == 7.61
    # x, B, C in bfloat16 and dt in float32 read three times and their
    # gradients written once, y written and dy read: 54,016 B a token and
    # layer, 2.16 ms at 819 GB/s against the products' 1.4 ms at the peak
    assert family.ssd_scan_bytes_per_step(CONFIG, 1) == 4 * (
        3 * (2 * (4096 + 2048) + 256) + 2 * 2 * 4096) == 4 * 54016
    assert family.ssd_scan_bytes_per_step(CONFIG, seq) / 819e9 \
        > family.ssd_scan_flops_per_step(CONFIG, seq) / 197e12
    # every held expert on every token, two matrices, three routed layers:
    # 21 times the model's (8 held where an even router sends 0.375)
    assert family.expert_flops_per_step(CONFIG, seq) \
        == 12 * d * 1856 * seq * 8 * 3
    assert round(family.expert_flops_per_step(CONFIG, seq) / 1e12, 2) == 11.77
    assert family.chosen_pairs_per_step(CONFIG, seq) == seq * 6 * 3


def test_the_closed_forms_count_the_programs_own_products():
    """The forward pass of the tiny model, traced: the FLOPs of its
    ``dot_general``s are the closed forms' pieces, each by the ratio the
    family states. Projections, router, shared expert and head 2 a
    parameter and token; EVERY held expert on every token
    (``expert_flops_per_step`` / 3); XLA's scores over the whole square
    where the closed form counts the causal pairs; the dual form's ``C
    B^T`` and ``(L o C B^T)(dt x)`` over a chunk's whole square where the
    closed form counts the keys a token sees."""
    config = TINY_FILE
    rows = 2
    cfg = family.model_config(config, SEQ)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=rows, seed=0)
    counted = dot_flops(jax.make_jaxpr(loss_fn)(params, batch).jaxpr)
    tokens = rows * SEQ
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    here = 6 * 4 / 16
    proj = 2 * tokens * (family.active_matmul_params(config)
                         - 3 * 2 * d * f * here)
    experts = family.expert_flops_per_step(config, tokens) / 3
    core = family.dsa_core_flops_per_step(config, rows, SEQ) / 3 \
        * SEQ * SEQ / (SEQ * (SEQ + 1) / 2)
    H, P, G, N, L = 8, 4, 8, 16, 8
    scan = 2.0 * (L * (N * G + P * H) + 2 * P * N * H) * tokens * 4
    assert family.ssd_scan_flops_per_step(config, tokens) / 3 \
        == 2.0 * ((L + 1) / 2 * (N * G + P * H) + 2 * P * N * H) * tokens * 4
    assert counted == proj + experts + core + scan


# ------------------------- the family: its batches, its reference's numbers


def test_step_1_is_read_on_the_batch_step_0_trained_on():
    pool = family.host_batches(CONFIG, {"seq": 16}, 2, 4247000601, 8)
    assert len(pool) == 8 and pool[1] is pool[0]
    assert pool[0]["tokens"].max() < CONFIG["vocab_size"]
    assert len({b["tokens"].tobytes() for b in pool}) == 7


@pytest.mark.parametrize("key, other", [
    ("num_experts_per_tok", 8), ("layer_norm_epsilon", 1e-6),
    ("routed_scaling_factor", 1.0), ("n_groups", 4),
    ("norm_topk_prob", False), ("n_group", 2), ("n_shared_experts", 2),
    ("mlp_hidden_act", "silu"), ("use_conv_bias", False),
    ("tie_word_embeddings", True), ("time_step_limit", [0, 0.1])])
def test_the_family_refuses_numbers_its_reference_does_not_state(key, other):
    """The driver calls the reference with the constants it states and
    hands it no configuration: a file that differs is refused by name,
    not compared with another model."""
    for config in (CONFIG, TINY_FILE):
        family.held_to_the_reference(config)
        with pytest.raises(ValueError, match=key):
            family.train_setup(dict(config, **{key: other}), {"seq": 16}, 1, 0)


def test_a_dense_letter_of_the_pattern_is_refused_by_name():
    with pytest.raises(ValueError, match="not built"):
        family.layer_types(dict(CONFIG, hybrid_override_pattern="ME-M*EME"))


# ----------------------------- the shape rules, as they decide for the cell


def test_the_programs_own_rules_decide_this_cells_step():
    """Blocks recomputed (16 B x 567 M is over half a v5e), the flash
    kernels at seq 8,192, the LEAN head at exactly its bytes, and by KIND:
    all three routed layers keep their ONE up product (243 MB) and their
    shared expert's (61 MB) in the room 12 B a parameter leave."""
    total = CONFIG["parameters_as_built"]["total"]
    assert lm.auto_remat_blocks(total, 8, 16e9)
    assert not lm.auto_remat_blocks(total, 8, 32e9)
    assert lm.auto_flash_attention(8192, 128, "tpu")
    assert 4 * 8192 * 16384 == lm.LEAN_HEAD_LOGIT_BYTES
    assert CONFIG["vocab_size"] < 32768     # the bytes engage it, not the rows
    cfg = family.model_config(CONFIG, 8192)
    assert lm.routed_layer_indices(cfg) == (1, 3, 6)
    # what the cores keep by name in any case: the one attention layer's q,
    # o and log-sum-exp, and the four scans' y (67 MB) and entering states
    # (134 MB): 0.94 GB, booked before the shared experts' room
    from autodist_tpu.ops import ssd
    assert ssd.runs_as_kernels(cfg.mamba_head_dim, cfg.ssm_state_size,
                               cfg.mamba_num_heads // cfg.mamba_n_groups,
                               cfg.mamba_chunk)
    assert ssd.mixer_runs_fused(cfg.mamba_head_dim, cfg.ssm_state_size,
                                cfg.mamba_num_heads // cfg.mamba_n_groups,
                                cfg.mamba_n_groups, cfg.mamba_chunk,
                                cfg.mamba_conv_size)
    core = lm.flash_kept_bytes(8192, 32, 128, 128) + 4 * lm.ssd_kept_bytes(
        1, 8192, 64, 64, 128, 128)
    assert core == 135266304 + 4 * (67108864 + 134217728)
    kept = lm.auto_kept_layers(
        True, total, 16e9, 8192, 2, routed_layers=3,
        held_stack=(8, 2688, 1856), shared_width=3712, core_bytes=core,
        expert_products=1)
    assert kept == lm.KeptLayers(3, 0, 0, 3)
    a_layer = lm.kept_layer_bytes(8192, 2, (8, 2688, 1856), 0, 2688, 3712,
                                  1, 1)
    assert (a_layer.experts, a_layer.shared) == (243269632, 60817408)
    # a gated layer of these sizes would book twice as much
    assert lm.kept_layer_bytes(8192, 2, (8, 2688, 1856), 0, 2688, 3712
                               ).experts == 2 * a_layer.experts


def test_the_last_k_routed_layers_are_the_last_k_that_are_routed():
    """Under single sub-layers the routed layers lie BETWEEN the mixers:
    with one kept expert layer it is layer 6 that saves the name, not
    layer 7 (a Mamba layer)."""
    from autodist_tpu.parallel.expert import KEPT
    cfg = tiny_config()
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    model = lambda **kw: lm.TransformerLM(  # noqa: E731
        cfg, remat_blocks=True, **kw)

    def saved(m):
        text = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(m.apply(
            p, batch["tokens"][:, :-1], mutable=["counters", "losses"])[0]
        )))(params))
        return text.count("name=" + KEPT)
    # the name is on every routed layer's product, forward and recomputed;
    # a policy that saves it drops the recomputed one
    none, one, all3 = (saved(model(kept_expert_layers=k)) for k in (0, 1, 3))
    assert none - one == 1 and none - all3 == 3


def test_the_scans_at_the_published_head_shape_are_booked_and_counted():
    """The tiny model with the cell's Mamba sizes (64 heads of 64 over 8
    groups of 128 states, chunks of 128) and every block recomputed, traced
    and not run: all four scans take the kernels, and what they keep by
    name is booked beside the flash core's, by closed form."""
    cfg = tiny_config(mamba_num_heads=64, mamba_head_dim=64,
                      mamba_n_groups=8, ssm_state_size=128, mamba_chunk=128)
    chip = lm._chip_hbm_bytes
    lm._chip_hbm_bytes = lambda: 1e5
    try:
        loss_fn, params, batch, _ = lm.make_train_setup(
            cfg, seq_len=48, batch_size=2, seed=0, attention="flash")
    finally:
        lm._chip_hbm_bytes = chip
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.remat_blocks"] == 8
    assert gauges["model.mamba_layers"] == 4
    assert gauges["model.ssd_kernel_layers"] == 4
    assert gauges["model.mamba_fused_mixer_layers"] == 4
    # y of the 48 tokens' one padded chunk in float32, one entering state
    a_scan = 2 * 64 * 64 * (128 * 4 + 1 * 128 * 4)
    assert lm.ssd_kept_bytes(2, 48, 64, 64, 128, 128, 4) == a_scan
    assert gauges["model.kept_core_bytes"] == lm.flash_kept_bytes(
        2 * 48, 4, 12, 12, 4) + 4 * a_scan


def test_the_gauges_count_the_layers_by_kind():
    loss_fn, params, batch, _ = lm.make_train_setup(
        tiny_config(), seq_len=16, batch_size=1, seed=0, attention="flash")
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["attention.flash_layers"] == 1
    assert gauges["model.mamba_layers"] == 4
    assert gauges["model.ssd_kernel_layers"] == 0       # heads of 8: jnp
    assert gauges["model.mamba_fused_mixer_layers"] == 0
    assert gauges["model.single_sublayer_blocks"] == 8
    assert gauges["model.kept_expert_layers"] == 0      # no TPU: no recompute
    assert loss_fn.device_counters == (
        "moe.max_expert_pairs", "moe.routed_pairs", "moe.chosen_pairs",
        "mamba.chunk_carry")


@pytest.mark.parametrize("preset", sorted(pinned_steps()))
def test_no_other_preset_fuses_a_mamba_mixer(preset):
    """Every preset at ``tests/test_lm_pins.py``'s tiny size (this family's
    at its narrow heads too): no fused mixer, by the gauge the set-up
    account reads; the cell's widths give 4
    (``test_the_scans_at_the_published_head_shape_are_booked_and_counted``)."""
    _, make, seq, rows, attention = pinned_steps()[preset]
    loss_fn, params, batch, _ = lm.make_train_setup(
        make(), seq_len=seq, batch_size=rows, seed=0, attention=attention)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.mamba_fused_mixer_layers"] == 0
    assert gauges["model.mamba_layers"] == (
        4 if preset == "tiny_nemotron_h_step" else 0)


def test_a_nemotron_h_step_names_its_mamba_mixers_and_their_scans():
    """``mamba`` holds a Mamba-2 mixer whole (both projections' matmuls),
    ``ssd_scan`` the recurrence's core alone (its own small products, no
    projection), each inside ``attention``, so ``attn_ms_per_step`` stays
    the mixers' total; the routed layers, blocks of their own here, are
    under ``moe`` and NOT under ``attention``; the grouped core of the one
    attention layer is under ``dsa_core``; with every block recomputed the
    scan is forward, backward and recomputed."""
    from autodist_tpu.telemetry import scopes
    from tests.test_scopes import STEP, components
    chip = lm._chip_hbm_bytes
    lm._chip_hbm_bytes = lambda: 1e5
    try:
        loss_fn, params, batch, _ = lm.make_train_setup(
            tiny_config(), seq_len=16, batch_size=8)
    finally:
        lm._chip_hbm_bytes = chip
    autodist_tpu.reset()
    try:
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
        runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
        runner.init(params)
        counters = runner.run(batch)["counters"]
        m = telemetry.scope_map(STEP)
    finally:
        autodist_tpu.reset()
    # a replica's row of 16 positions, three routed layers, top-3
    assert int(counters["moe.chosen_pairs"]) == 3 * 16 * 3
    assert 0.0 < float(counters["mamba.chunk_carry"]) < 1.0
    ops = [o for names in m.values() for o in names]
    for scope in (scopes.MAMBA, scopes.SSD_SCAN):
        assert scope in scopes.SCOPES
        inside = [o for o in ops if scope in components(o)
                  and scopes.BLOCKS in components(o)]
        assert inside and all(
            components(o).index(scopes.BLOCKS)
            < components(o).index(scopes.ATTENTION)
            < components(o).index(scopes.MAMBA)
            <= components(o).index(scope) for o in inside)
    mix = [o for o in ops if scopes.MAMBA in components(o)]
    scan = [o for o in ops if scopes.SSD_SCAN in components(o)]
    assert any("in_proj" in o and "dot_general" in o for o in mix)
    assert not any("in_proj" in o or "out_proj" in o for o in scan)
    assert any("dot_general" in o for o in scan)
    assert any("transpose(" in o for o in scan)
    assert any("rematted_computation" in o for o in scan)
    routed = [o for o in ops if scopes.MOE in components(o)]
    assert routed and not any(scopes.ATTENTION in components(o)
                              for o in routed)
    grouped = [o for o in ops if scopes.DSA_CORE in components(o)]
    assert any("dot_general" in o for o in grouped)


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
    the other seven configurations go, against ``train_check``: the
    routers' bias is in the state and does not move, and the step's
    ``mamba.chunk_carry`` leaves it as a device counter."""
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
            got = [float(m["loss"]) for m in history]
            want = ref.train_check(
                lambda p, b: ref.nll_sum(p, b, TOP_K, HELD, GROUPS),
                ref.batch_weight, params, pool[0], pool[1], jax.devices()[:1])
        after = flat(runner.gather_params())
        carry = float(history[0]["counters"]["mamba.chunk_carry"])
    finally:
        autodist_tpu.reset()
    close(np.asarray(got), np.asarray(want))
    assert 0.0 < carry < 1.0
    before = flat(params)
    for name in before:
        moved = np.any(np.asarray(after[name]) != np.asarray(before[name]))
        assert moved == ("e_score_correction_bias" not in name), name


# -- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_nemotron_h.py)


PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at a tiny size, read
    as the benchmark's driver reads a run."""
    from benchmark.tools import loss_limit_nemotron_h as tool
    traffic = dict(bench_json("traffic", "train_b1_s8192_every16.json"),
                   batch_per_chip=2, seq=SEQ)
    rows = tool.readings(TINY_FILE, traffic, 7, CELL["loss_rtol"])
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    from benchmark.tools import loss_limit_nemotron_h as tool
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
    than the 1e-5 the float32 program and reference differ by (the
    filter's bias starts at zero, so leaving it out shows in step 1
    alone, after Adam moved it)."""
    assert tiny_readings["sound"] == 0.0
    # (one Adam step moves the bias by 1e-3: the smallest of them)
    floor = RTOL if fault == "conv_bias_left_out" else 10 * RTOL
    assert tiny_readings[fault] > floor


def limit_record(fault):
    """The readings of one fault at the published widths; a reading that
    was not finite (recorded as null) counts as infinitely far."""
    return [float("inf") if r["reading"] is None else r["reading"]
            for r in bench_lines(*RECORD) if r.get("fault") == fault]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len({r["seed"] for r in bench_lines(*RECORD)
                if r.get("fault") == "sound_on_the_chip"}) >= 15
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


@pytest.mark.parametrize("fault", ["computed_in_float8_e4m3fn", "no_step"])
def test_the_limit_lies_between_its_two_readings_with_room(fault):
    assert limit_record(fault)
    assert min(limit_record(fault)) > 3 * CELL["loss_rtol"]
    assert max(limit_record("computed_in_bfloat16")) < CELL["loss_rtol"]


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr47_loss_limit.jsonl): a fault is
    REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])
