"""Trinity-Mini's configuration and cell (``tests/test_afmoe.py`` holds the
model to its reference): the configuration file against the catalog's row
key by key and against the tree it builds, the closed-form FLOPs against the
program's own products at a tiny size, the shape rules of
``make_train_setup`` for this cell (blocks recomputed, SIX tenants of the
one booking, the gate's product the sixth), the model through
``Runner.fit``, and what the cell's ``loss_rtol`` refuses
(``benchmark/tools/loss_limit_afmoe.py``)."""
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.models import layers, lm
from benchmark.families import afmoe as family
from benchmark.reference import afmoe as ref
from benchmark.tools import loss_limit_afmoe as tool
from tests.test_afmoe import (HELD, SEQ, TOP_K, WINDOW, batches,
                              tiny_config)
from tests.test_keye_vl2_cell import bench_json, bench_lines, dot_flops
from tests.test_kimi_linear import close, cpu_spec, flat

RTOL = 1e-5
CONFIG = bench_json("configs", "trinity_mini_26b_a3b.json")
CELL = bench_json("workloads", "trinity_mini_train_1chip.json")
TRAFFIC = bench_json("traffic", "train_b1_s16384_every16.json")
REDUCED = ["num_dense_layers", "num_experts", "num_hidden_layers",
           "vocab_size"]
RECORD = ("records", "pr53_loss_limit.jsonl")
NAME = "trinity_mini_train_1chip"
TOTAL = 504147712


def tiny_file(**kw):
    """The rehearsal's tiny configuration with a window that bites at SEQ
    and the tests' top-3 of 16 (4 held)."""
    config = bench_json("tests", "configs", "afmoe_tiny.json")
    return dict(config, sliding_window=WINDOW, **kw)


# ------------------------------------------------- the config, the preset

def test_the_published_preset_is_the_files_published_block():
    cfg = lm.LMConfig.trinity_mini_26b_a3b()
    pub = CONFIG["published"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.norm_eps, cfg.max_seq_len) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"], pub["vocab_size"], pub["rms_norm_eps"],
        pub["max_position_embeddings"])
    assert (cfg.mlp_dim, cfg.dense_dim, cfg.first_k_dense_replace,
            cfg.num_experts, cfg.experts_per_token, cfg.num_shared_experts,
            cfg.moe_renormalize, cfg.routed_scaling_factor,
            cfg.sliding_window, cfg.rope_theta, cfg.embed_scale) == (
        pub["moe_intermediate_size"], pub["intermediate_size"],
        pub["num_dense_layers"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["num_shared_experts"],
        pub["route_norm"], pub["route_scale"], pub["sliding_window"],
        pub["rope_theta"], pub["mup_enabled"])
    kinds = ["sliding_attention" if w else "full_attention"
             for w in cfg.window_layers]
    assert kinds == pub["layer_types"] and cfg.rope_layers == cfg.window_layers
    assert pub["score_func"] == cfg.router_activation == "sigmoid"
    assert pub["hidden_act"] == "silu" and cfg.expert_gate_activation == "silu"
    assert not pub["tie_word_embeddings"] and not cfg.tie_embedding
    assert cfg.experts_held is None     # the published model holds them all
    assert (ref.TOP_K, ref.RMS_EPS, ref.ROPE_THETA, ref.WINDOW,
            ref.ROUTE_SCALE, ref.GLOBAL_EVERY) == (
        pub["num_experts_per_tok"], pub["rms_norm_eps"], pub["rope_theta"],
        pub["sliding_window"], pub["route_scale"],
        pub["global_attn_every_n_layers"])
    assert [ref.layout(i) == (True, True) for i in range(32)] \
        == [k == "sliding_attention" for k in pub["layer_types"]]


def test_the_catalogs_row_is_the_files_published_block_key_by_key():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"][0]
    assert row["config"] == CONFIG["published"]
    assert row["source_url"] == CONFIG["source"]
    # every key of the row is in the file as it is run, under the same
    # name, and differs only where ``reduced`` says (layer_types is whole)
    assert sorted(k for k, v in row["config"].items()
                  if CONFIG[k] != v) == REDUCED
    bench = bench_json("..", "BENCHMARK.json")
    entry = [c for c in bench["configs"]
             if c["name"] == "trinity_mini_26b_a3b"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "benchmark/configs/trinity_mini_26b_a3b.json"
    assert len(entry["why"]) <= 200


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds
    (``jax.eval_shape``: nothing is allocated), the five layers are
    published layers 1-5, no width differs from the source, ``reduced``
    names every key that does, and ``assumed`` every equation without a
    key."""
    config = CONFIG
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert sorted(differs) == sorted(config["reduced"]) == REDUCED
    assert sorted(config["reduced_why"]) == REDUCED
    assert config["router_num_experts"] \
        == config["published"]["num_experts"] == 128
    assert config["experts_held"] == list(range(8))
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"],
            config["first_layer_built"]) == (5, 1, 8, 200192 // 8, 1)
    assert family.layer_kinds(config) == (
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention")
    assert family.layouts(config) == ((1, 1, 0, 1, 1), (1, 1, 0, 1, 1))
    assert {"gated_attention", "qk_head_norm", "four_norms_a_block",
            "global_layers_do_not_rotate", "window_counts_the_query",
            "rope_pairing", "router_gates", "optimizer",
            "precision"} <= set(config["assumed"])
    assert all("no key of config.json" in config["assumed"][k] for k in (
        "gated_attention", "qk_head_norm", "four_norms_a_block",
        "global_layers_do_not_rotate"))
    assert "16 chips" in config["deployment"] \
        and "1.00 x" in config["deployment"]
    assert "R-M6" in config["departures"]
    family.held_to_the_reference(config)
    cfg = family.model_config(config, 16384)
    assert cfg.dtype == jnp.bfloat16 and cfg.max_seq_len == 131072
    assert (cfg.window_layers, cfg.rope_layers, cfg.sliding_window,
            cfg.first_k_dense_replace, cfg.num_layers) == (
        (1, 1, 0, 1, 1), (1, 1, 0, 1, 1), 2048, 1, 5)
    assert cfg.gated_attention and cfg.sandwich_norm and cfg.qk_head_norm
    shapes = jax.eval_shape(
        lambda key: lm.TransformerLM(cfg).init(
            key, jnp.zeros((1, 16), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    built = config["parameters_as_built"]
    assert count(shapes) == built["total"] == TOTAL
    assert count(shapes["layer_0"]) == built["dense_layer"] == 65020160
    assert [count(shapes["layer_%d" % i]) for i in range(1, 5)] \
        == [built["routed_layer"]] * 4 == [84156800] * 4
    attention = shapes["layer_1"]["MultiHeadAttention_0"]
    assert count(attention) == built["attention"] \
        == 3 * built["q_or_o_or_gate"] + 2 * built["k_or_v"] \
        + built["qk_norms"] == 27263232
    assert count(attention["gate"]) == built["q_or_o_or_gate"] \
        == 2048 * 32 * 128
    assert count(shapes["layer_0"]["mlp"]) == built["dense_swiglu"] \
        == 3 * 2048 * 6144
    moe = shapes["layer_1"]["moe"]
    assert set(moe) == {"router", "gate_proj", "up_proj", "down_proj",
                        "e_score_correction_bias", "shared"}
    assert count(moe["router"]) == built["router"] == 2048 * 128
    assert count(moe["shared"]) == built["shared_expert"] \
        == built["one_expert"] == 3 * 2048 * 1024
    assert count(moe) - built["router"] - built["shared_expert"] \
        - built["choice_bias"] == built["held_experts_per_layer"] \
        == 8 * built["one_expert"]
    assert count(shapes["embed"]) == count(shapes["lm_head"]) \
        == built["embedding"] == built["head"] == 25024 * 2048
    assert built["total"] == built["dense_layer"] + 4 * built["routed_layer"] \
        + 2 * built["embedding"] + built["final_norm"]


def test_the_closed_forms_at_the_published_sizes():
    d, seq = 2048, 16384
    attn = d * 128 * (3 * 32 + 2 * 4)       # q, gate, o; k, v
    moe = d * 128 + 3 * d * 1024 * (1 + 8 * 8 / 128)
    active = 5 * attn + 4 * moe + 3 * d * 6144 + d * 25024
    assert family.active_matmul_params(CONFIG) == active
    # the gate's projection is counted: 8,388,608 a layer
    assert active - (5 * d * 128 * (2 * 32 + 2 * 4) + 4 * moe + 3 * d * 6144
                     + d * 25024) == 5 * 8388608
    assert family.causal_pairs(seq) == 134225920
    assert family.window_pairs(seq, 2048) == 31458304
    a_core = 3 * 2 * (128 + 128) * 32
    assert family.dsa_core_flops_per_step(CONFIG, 1, seq) \
        == a_core * 134225920 * 1
    assert family.swa_core_flops_per_step(CONFIG, 1, seq) \
        == a_core * 31458304 * 4
    assert round(family.dsa_core_flops_per_step(CONFIG, 1, seq) / 1e12, 2) \
        == 6.60
    assert round(family.swa_core_flops_per_step(CONFIG, 1, seq) / 1e12, 2) \
        == 6.18
    assert family.train_flops_per_token(CONFIG, TRAFFIC) == 6 * active + (
        family.dsa_core_flops_per_step(CONFIG, 1, seq)
        + family.swa_core_flops_per_step(CONFIG, 1, seq)) / seq
    # every held expert on every token, three matrices, four routed layers:
    # 16 times the model's (8 held where an even router sends 0.5)
    assert family.expert_flops_per_step(CONFIG, seq) \
        == 18 * d * 1024 * seq * 8 * 4
    assert round(family.expert_flops_per_step(CONFIG, seq) / 1e12, 2) == 19.79


def test_the_closed_forms_count_the_programs_own_products():
    """The forward pass of the tiny model, traced: the FLOPs of its
    ``dot_general``s are the closed forms' pieces, each by the ratio the
    family states. Projections (THE GATE'S among them), router, shared
    expert, dense layer and head 2 a parameter and token; EVERY held expert
    on every token (``expert_flops_per_step`` / 3); XLA's scores over the
    whole square in EVERY layer, where the closed forms count the causal
    pairs and the window's."""
    config = tiny_file()
    rows = 2
    cfg = family.model_config(config, SEQ)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=rows, seed=0)
    counted = dot_flops(jax.make_jaxpr(loss_fn)(params, batch).jaxpr)
    tokens = rows * SEQ
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    here = 8 * 8 / 16
    proj = 2 * tokens * (family.active_matmul_params(config)
                         - 4 * 3 * d * f * here)
    experts = family.expert_flops_per_step(config, tokens) / 3
    square = SEQ * SEQ / family.causal_pairs(SEQ)
    cores = 5 * family.dsa_core_flops_per_step(config, rows, SEQ) / 3 * square
    assert family.swa_core_flops_per_step(config, rows, SEQ) \
        == 4 * family.dsa_core_flops_per_step(config, rows, SEQ) \
        * family.window_pairs(SEQ, WINDOW) / family.causal_pairs(SEQ)
    assert counted == proj + experts + cores
    # ... and without the gate the program counts exactly the gate's less
    import dataclasses
    plain, p2, _, _ = lm.make_train_setup(
        dataclasses.replace(cfg, gated_attention=False), seq_len=SEQ,
        batch_size=rows, seed=0)
    assert counted - dot_flops(jax.make_jaxpr(plain)(p2, batch).jaxpr) \
        == 5 * 2 * tokens * d * 16 * 8


# ------------------------- the family: its batches, its reference's numbers

def test_step_1_is_read_on_the_batch_step_0_trained_on():
    pool = family.host_batches(CONFIG, {"seq": 16}, 2, 2147483651, 8)
    assert len(pool) == 8 and pool[1] is pool[0]
    assert pool[0]["tokens"].max() < CONFIG["vocab_size"]
    assert len({b["tokens"].tobytes() for b in pool}) == 7


@pytest.mark.parametrize("key, other", [
    ("num_experts_per_tok", 6), ("rms_norm_eps", 1e-6),
    ("rope_theta", 1500000), ("sliding_window", 4096), ("route_scale", 2.5),
    ("route_norm", False), ("score_func", "softmax"), ("mup_enabled", False),
    ("tie_word_embeddings", True), ("hidden_act", "relu"),
    ("num_shared_experts", 2), ("first_layer_built", 0),
    ("global_attn_every_n_layers", 2),
    ("layer_types", ["full_attention"] * 32)])
def test_the_family_refuses_numbers_its_reference_does_not_state(key, other):
    with pytest.raises(ValueError, match=key):
        family.held_to_the_reference(dict(CONFIG, **{key: other}))


def test_a_checkout_without_the_preset_fails_at_once_and_by_name(monkeypatch):
    """The parent commit has no ``LMConfig.trinity_mini_26b_a3b``: the cell
    exits with a message there (rc 1) before anything is built."""
    monkeypatch.delattr(lm.LMConfig, "trinity_mini_26b_a3b")
    with pytest.raises(SystemExit, match="no trinity_mini_26b_a3b"):
        family.model_config(CONFIG, 16384)


# ---------------------------------------------- the program's own rules

KEPT = dict(routed_layers=4, held_stack=(8, 2048, 1024), dense_layers=1,
            dense_width=6144, sandwich_layers=5, d_model=2048,
            shared_width=1024, gate_layers=5, gate_width=4096)
CORES = 5 * 16384 * 32 * (2 * 256 + 4)


def test_the_programs_own_rules_decide_this_cells_step():
    """Blocks recomputed (16 B x 504.1 M x 2 = 16.13e9 is OVER a v5e's
    16e9, by 0.8 %), the flash kernels at seq 16,384, the LEAN head by its
    logits' bytes, and SIX tenants of the one room: all of each fit."""
    assert 16.0 * TOTAL * 2 == 16132726784 > 16e9
    assert lm.auto_remat_blocks(TOTAL, 5, 16e9)
    assert not lm.auto_remat_blocks(TOTAL, 5, None)
    assert lm.auto_flash_attention(16384, 128, "tpu")
    assert 4 * 16384 * CONFIG["vocab_size"] >= lm.LEAN_HEAD_LOGIT_BYTES
    assert CONFIG["vocab_size"] < 32768     # the bytes engage it, not the rows
    # a core's q + out + log-sum-exp at 32 heads of 128: 270.5 MB a layer
    assert lm.flash_kept_bytes(16384, 32, 128, 128) == CORES // 5 == 270532608
    a_layer = lm.kept_layer_bytes(16384, 2, (8, 2048, 1024), 6144, 2048,
                                  1024, gate_width=4096)
    assert a_layer == lm.KeptLayers(536870912, 402653184, 134217728,
                                    67108864, 0, 134217728)
    kept = lm.auto_kept_layers(True, TOTAL, 16e9, 16384, 2,
                               core_bytes=CORES, **KEPT)
    assert kept == lm.KeptLayers(4, 1, 5, 4, 0, 5)
    room = 0.76 * 16e9 - 12.0 * TOTAL
    asked = CORES + sum(n * b for n, b in zip(kept, a_layer))
    assert 6.1e9 < room < 6.12e9 and 5.5e9 < asked < 5.52e9
    # the gate is booked LAST, from what the five before it leave: with 0.2
    # GB less room it is the gate that gives way, a whole layer at a time
    short = lm.auto_kept_layers(True, TOTAL, 16e9 - 0.6e9 / 0.76, 16384, 2,
                                core_bytes=CORES, **KEPT)
    assert short == kept._replace(attn_gate=4)
    # not recomputed, or off a TPU: nothing is made twice, nothing is kept
    assert lm.auto_kept_layers(False, TOTAL, 16e9, 16384, 2, **KEPT) \
        == lm.auto_kept_layers(True, TOTAL, None, 16384, 2, **KEPT) \
        == lm.KeptLayers()


def test_the_step_fits_device_less_with_the_gate_kept_and_without():
    record = bench_json("records", "pr53_aot_memory.json")["cells"][NAME]
    for form in ("gate_kept", "gate_recomputed"):
        step = record[form]
        assert step["argument_size_in_bytes"] + step["temp_size_in_bytes"] \
            < 15.35e9, form
    assert record["gate_recomputed"]["temp_size_in_bytes"] \
        < record["gate_kept"]["temp_size_in_bytes"]


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


def test_the_last_k_gated_layers_keep_the_gates_product(tiny):
    """``TransformerLM(remat_blocks=True, kept_attn_gate_layers=k)``: only
    the LAST k gated blocks' policy saves the name, and kept, not kept and
    not recomputed give the same loss and gradients (to the order of the
    sums: XLA fuses a recomputed block differently)."""
    cfg, _, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    def grads(**kw):
        model = lm.TransformerLM(cfg, **kw)
        return jax.jit(jax.value_and_grad(lambda p: jnp.sum(jnp.square(
            model.apply(p, ids, mutable=["losses", "counters"])[0]))))(params)
    plain = grads()
    for kept in (0, 2, 5):
        got = grads(remat_blocks=True, kept_attn_gate_layers=kept)
        close(got[0], plain[0])
        for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                        jax.tree_util.tree_leaves(plain[1])):
            close(a, b)
    built = []
    real = jax.checkpoint_policies.save_only_these_names
    with mock.patch.object(jax.checkpoint_policies, "save_only_these_names",
                           lambda *names: (built.append(names),
                                           real(*names))[1]):
        jax.eval_shape(lambda p: lm.TransformerLM(
            cfg, remat_blocks=True, kept_attn_gate_layers=2).apply(
            p, ids, mutable=["losses", "counters"]), params)
    assert [layers.ATTN_GATE_KEPT in names for names in built] \
        == [False, False, False, True, True]


# ---------------------------------------------------- the normal path, fit

@pytest.mark.parametrize("devices", [1, 2])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """make_train_setup -> AutoDist(AllReduce()).build -> Runner.fit, as the
    other nine configurations go, against ``train_check``; every leaf of
    the state but the choice bias moves."""
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
                lambda p, b: ref.nll_sum(p, b, TOP_K, HELD, WINDOW),
                ref.batch_weight, params, pool[0], pool[1], jax.devices()[:1])
        after = flat(runner.gather_params())
        pairs = float(history[0]["counters"]["moe.chosen_pairs"])
    finally:
        autodist_tpu.reset()
    close(np.asarray(got), np.asarray(want))
    # (a replica's own rows: the counter is the replicas' mean)
    assert pairs == 2 * SEQ * TOP_K * 4 / devices
    before = flat(params)
    for name in before:
        moved = np.any(np.asarray(after[name]) != np.asarray(before[name]))
        assert moved != name.endswith("e_score_correction_bias"), name


# ----- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_afmoe.py)

PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])
NOT_IN_THE_LOGITS = ["no_step", "computed_in_bfloat16",
                     "computed_in_float8_e4m3fn",
                     "float8_e4m3fn_operands_float32_cotangents"]


@pytest.fixture(scope="module")
def tiny_readings():
    """The step's and the precision's faults planted into the float32
    reference at a tiny size (a window of 10 under 48 positions; published
    layers 1-3), read as the benchmark's driver reads a run."""
    traffic = dict(TRAFFIC, batch_per_chip=2, seq=SEQ)
    rows = tool.readings(tiny_file(num_hidden_layers=3), traffic, 7,
                         CELL["loss_rtol"], only=NOT_IN_THE_LOGITS)
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    assert PLANTED == sorted(tool.faults())
    assert not set(CELL["loss_rtol_refuses"]) & set(
        CELL["loss_rtol_lets_through"])
    # the configuration's own precision is never refused; the nearest
    # precision under it and a state left unchanged are
    assert set(tool.WITHIN) <= set(CELL["loss_rtol_lets_through"])
    assert {"computed_in_float8_e4m3fn", "no_step"} <= set(
        CELL["loss_rtol_refuses"])
    # ISSUE 53's list, each planted
    assert {"gate_left_out", "gate_reads_the_residual", "window_ignored",
            "rotation_on_the_global_layer", "qk_norm_left_out",
            "attn_out_norm_left_out", "mlp_out_norm_left_out",
            "route_scale_left_out", "gates_not_renormalised",
            "shared_expert_lost", "one_held_expert_lost",
            "embedding_not_scaled", "no_step", "computed_in_bfloat16",
            "computed_in_float8_e4m3fn"} <= set(PLANTED)


@pytest.mark.parametrize("fault", NOT_IN_THE_LOGITS)
def test_a_planted_fault_moves_what_the_driver_reads(tiny_readings, fault):
    """The faults that no forward pass shows (``tests/test_afmoe.py`` holds
    the others to the logits) are really planted: each moves the reading by
    far more than the 1e-5 the float32 program and reference differ by."""
    assert tiny_readings["sound"] == 0.0
    assert tiny_readings[fault] > 10 * RTOL


def limit_record(fault):
    """The readings of one fault at the published widths; a reading that
    was not finite (recorded as null) counts as infinitely far."""
    return [float("inf") if r["reading"] is None else r["reading"]
            for r in bench_lines(*RECORD) if r.get("fault") == fault]


def test_the_limit_lies_between_its_two_readings_with_room_on_both_sides():
    """The two readings ISSUE 53 sets the limit from: the largest the
    bfloat16 program reads over its sound seeds on the chip (at least three
    times under the limit) and the reference computed in the nearest
    precision below, float8 e4m3fn, which has to come out as not correct (at
    least three times over it), like a state left unchanged; the
    configuration's own precision stays inside."""
    sound = limit_record("sound_on_the_chip")
    assert len({r["seed"] for r in bench_lines(*RECORD)
                if r.get("fault") == "sound_on_the_chip"}) >= 3
    assert 3 * max(sound) <= CELL["loss_rtol"]
    for fault in ("computed_in_float8_e4m3fn", "no_step"):
        assert limit_record(fault)
        assert min(limit_record(fault)) > 3 * CELL["loss_rtol"]
    assert max(limit_record("computed_in_bfloat16")) < CELL["loss_rtol"]


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr53_loss_limit.jsonl): a fault is
    REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])


# ------------------------------------------------------------ the contract

def test_the_benchmark_gains_one_config_one_cell_and_one_metric():
    bench = bench_json("..", "BENCHMARK.json")
    cell = [w for w in bench["workloads"] if w["name"] == NAME][0]
    assert cell == dict(cell, config="trinity_mini_26b_a3b",
                        traffic="train_b1_s16384_every16", chips=1)
    assert len(cell["why"]) <= 200
    assert bench["workloads"][-1] == cell
    assert bench["configs"][-1]["name"] == "trinity_mini_26b_a3b"
    assert os.path.exists(os.path.join(
        os.path.dirname(__file__), "..", bench["configs"][-1]["file"]))
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [NAME]}
    assert sorted(mine) == ["attn_gate_ms_per_step"]
    assert bench["per_layer"][-1] == mine["attn_gate_ms_per_step"] == {
        "name": "attn_gate_ms_per_step", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model ops",
        "moves": "train_tok_s", "workloads": [NAME]}
    for name in mine:
        assert os.path.exists(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
            name + ".py"))
    reported = {m["name"] for key in ("end_to_end", "per_layer")
                for m in bench[key] if NAME in m.get("workloads", [NAME])}
    assert {"train_tok_s", "setup_s", "mfu_pct", "head_ms_per_step",
            "moe_ms_per_step", "moe_route_ms_per_step",
            "expert_mm_roofline_pct", "moe_held_pairs_share",
            "held_expert_fullest_over_even", "moe_shared_ms_per_step",
            "dense_ffn_ms_per_step", "dsa_core_ms_per_step",
            "dsa_core_roofline_pct", "swa_core_ms_per_step",
            "swa_core_roofline_pct", "swa_tiles_share", "remat_ms_per_step",
            "attn_core_ms_per_step", "block_rest_ms_per_step",
            "attn_gate_ms_per_step"} <= reported
    # no state-space, latent, delta-rule, conv or looped layer, no indexer
    assert not {"mamba_ms_per_step", "mla_ms_per_step", "kda_ms_per_step",
                "conv_ms_per_step", "loop_ms_per_step",
                "dsa_index_ms_per_step", "router_aux_per_layer",
                "coll_ms_per_step"} & reported
    # appended to every list, nothing before it moved
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if NAME in m.get("workloads", []):
            assert m["workloads"][-1] == NAME
            assert m["workloads"] == sorted(m["workloads"], key=order.index)


def test_the_gates_reader_returns_nothing_without_the_scope():
    """The parent's program has no ``attn_gate`` scope, and an untraced run
    no device trace: the reader leaves its metric out and raises nothing;
    with the time under the scope it returns it."""
    from benchmark.layer_metrics import attn_gate_ms_per_step as reader
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 16384,
           "chips": 1}
    assert reader.read(rec, None) is None
    rec["scope_ms_per_step"] = {"attn_gate": 21.5}
    assert reader.read(rec, None) == 21.5
