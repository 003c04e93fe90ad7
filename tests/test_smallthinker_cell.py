"""SmallThinker's configuration and cell (``tests/test_smallthinker.py`` holds
the model to its reference): the configuration file against the catalog's
row key by key and against the tree it builds, the closed-form FLOPs
against the program's own products at a tiny size, the shape rules of
``make_train_setup`` for this cell (no block recomputed, what the cores and
the experts would keep), the scopes and counters of a step, the model
through ``Runner.fit``, and what the cell's ``loss_rtol`` refuses
(``benchmark/tools/loss_limit_smallthinker.py``)."""
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
from autodist_tpu.telemetry import scopes
from benchmark.families import smallthinker as family
from benchmark.reference import smallthinker as ref
from benchmark.tools import loss_limit_smallthinker as tool
from tests.test_keye_vl2_cell import bench_json, bench_lines, dot_flops
from tests.test_kimi_linear import close, cpu_spec, flat
from tests.test_smallthinker import (HELD, SEQ, TOP_K, WINDOW, batches,
                                     tiny_config)

RTOL = 1e-5
CONFIG = bench_json("configs", "smallthinker_21b_a3b.json")
CELL = bench_json("workloads", "smallthinker_train_1chip.json")
TRAFFIC = bench_json("traffic", "train_b1_s16384_every16.json")
REDUCED = ["moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
RECORD = ("records", "pr51_loss_limit.jsonl")
NAME = "smallthinker_train_1chip"


def tiny_file(**kw):
    """The rehearsal's tiny configuration with a window that bites at SEQ
    and the tests' top-3 of 16 (4 held)."""
    config = bench_json("tests", "configs", "smallthinker_tiny.json")
    return dict(config, sliding_window_size=WINDOW, **kw)


# ------------------------------------------------- the config, the preset

def test_the_published_preset_is_the_files_published_block():
    cfg = lm.LMConfig.smallthinker_21b_a3b()
    pub = CONFIG["published"]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.norm_eps, cfg.max_seq_len) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"], pub["vocab_size"], pub["rms_norm_eps"],
        pub["max_position_embeddings"])
    assert (cfg.mlp_dim, cfg.num_experts, cfg.experts_per_token,
            cfg.moe_renormalize, cfg.sliding_window, cfg.rope_theta) == (
        pub["moe_ffn_hidden_size"], pub["moe_num_primary_experts"],
        pub["moe_num_active_primary_experts"], pub["norm_topk_prob"],
        pub["sliding_window_size"], pub["rope_theta"])
    assert list(cfg.window_layers) == pub["sliding_window_layout"]
    assert list(cfg.rope_layers) == pub["rope_layout"]
    assert pub["moe_primary_router_apply_softmax"] \
        and cfg.router_activation == "softmax"
    assert not pub["tie_word_embeddings"] and not cfg.tie_embedding
    assert cfg.experts_held is None     # the published model holds them all
    assert (ref.TOP_K, ref.RMS_EPS, ref.ROPE_THETA, ref.WINDOW) == (
        pub["moe_num_active_primary_experts"], pub["rms_norm_eps"],
        pub["rope_theta"], pub["sliding_window_size"])
    assert list(ref.PERIOD) * 13 == pub["rope_layout"]


def test_the_catalogs_row_is_the_files_published_block_key_by_key():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "SmallThinker-21BA3B-Instruct"][0]
    assert row["config"] == CONFIG["published"]
    assert row["source_url"] == CONFIG["source"]
    # every key of the row is in the file as it is run, under the same
    # name, and differs only where ``reduced`` says (the layouts are whole)
    assert sorted(k for k, v in row["config"].items()
                  if CONFIG[k] != v) == REDUCED
    bench = bench_json("..", "BENCHMARK.json")
    entry = [c for c in bench["configs"]
             if c["name"] == "smallthinker_21b_a3b"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "benchmark/configs/smallthinker_21b_a3b.json"


def test_the_cells_configuration_is_the_built_tree():
    """The file's counts are those of the tree ``LMConfig`` builds
    (``jax.eval_shape``: nothing is allocated), the four layers are one
    period of the two layouts, no width differs from the source, and
    ``reduced`` names every key that does."""
    config = CONFIG
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert sorted(differs) == sorted(config["reduced"]) == REDUCED
    assert sorted(config["reduced_why"]) == REDUCED
    assert config["router_num_experts"] \
        == config["published"]["moe_num_primary_experts"] == 64
    assert config["experts_held"] == list(range(8))
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 8, 151936 // 8)
    assert family.layouts(config) == ((0, 1, 1, 1), (0, 1, 1, 1))
    assert sorted(config["assumed"]) and "8 chips" in config["deployment"] \
        and "1.33 x" in config["deployment"]
    assert "secondary" in config["departures"]
    family.held_to_the_reference(config)
    cfg = family.model_config(config, 16384)
    assert cfg.dtype == jnp.bfloat16 and cfg.max_seq_len == 16384
    assert (cfg.window_layers, cfg.rope_layers, cfg.sliding_window) == (
        (0, 1, 1, 1), (0, 1, 1, 1), 4096)
    shapes = jax.eval_shape(
        lambda key: lm.TransformerLM(cfg).init(
            key, jnp.zeros((1, 16), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    built = config["parameters_as_built"]
    assert count(shapes) == built["total"] == 370547200
    assert [count(shapes["layer_%d" % i]) for i in range(4)] \
        == [built["layer"]] * 4 == [68326400] * 4
    layer = shapes["layer_1"]
    assert count(layer["MultiHeadAttention_0"]) == built["attention"] \
        == 2 * built["q_or_o"] + 2 * built["k_or_v"] == 20971520
    moe = layer["moe"]
    assert set(moe) == {"router", "gate_proj", "up_proj", "down_proj"}
    assert count(moe["router"]) == built["router"] == 2560 * 64
    assert count(moe) - built["router"] == built["held_experts_per_layer"] \
        == 8 * built["one_expert"] == 8 * 3 * 2560 * 768
    assert count(shapes["embed"]) == count(shapes["lm_head"]) \
        == built["embedding"] == built["head"] == 18992 * 2560
    assert built["total"] == 4 * built["layer"] + 2 * built["embedding"] \
        + built["final_norm"]


def test_the_closed_forms_at_the_published_sizes():
    d, seq = 2560, 16384
    attn = 2 * d * 128 * (28 + 4)
    moe = d * 64 + 3 * d * 768 * (6 * 8 / 64)
    active = 4 * (attn + moe) + d * 18992
    assert family.active_matmul_params(CONFIG) == active
    assert family.causal_pairs(seq) == 134225920
    assert family.window_pairs(seq, 4096) == 58722304
    assert family.window_pairs(seq, 4096) / family.causal_pairs(seq) \
        == pytest.approx(0.4375, abs=2e-4)
    assert family.window_pairs(100, 4096) == family.causal_pairs(100)
    # brute force over all (i, j) at a small size
    i, j = np.indices((48, 48))
    assert family.window_pairs(48, 10) == np.count_nonzero(
        (j <= i) & (i - j < 10))
    a_core = 3 * 2 * (128 + 128) * 28
    assert family.dsa_core_flops_per_step(CONFIG, 1, seq) \
        == a_core * 134225920 * 1
    assert family.swa_core_flops_per_step(CONFIG, 1, seq) \
        == a_core * 58722304 * 3
    assert round(family.dsa_core_flops_per_step(CONFIG, 1, seq) / 1e12, 2) \
        == 5.77
    assert round(family.swa_core_flops_per_step(CONFIG, 1, seq) / 1e12, 2) \
        == 7.58
    assert family.train_flops_per_token(CONFIG, TRAFFIC) == 6 * active + (
        family.dsa_core_flops_per_step(CONFIG, 1, seq)
        + family.swa_core_flops_per_step(CONFIG, 1, seq)) / seq
    # every held expert on every token, three matrices, four layers: 10.7
    # times the model's (8 held where an even router sends 0.75)
    assert family.expert_flops_per_step(CONFIG, seq) \
        == 18 * d * 768 * seq * 8 * 4
    assert round(family.expert_flops_per_step(CONFIG, seq) / 1e12, 2) == 18.55


def test_the_closed_forms_count_the_programs_own_products():
    """The forward pass of the tiny model, traced: the FLOPs of its
    ``dot_general``s are the closed forms' pieces, each by the ratio the
    family states. Projections, router and head 2 a parameter and token;
    EVERY held expert on every token (``expert_flops_per_step`` / 3); XLA's
    scores over the whole square in EVERY layer, where the closed forms
    count the causal pairs and the window's."""
    config = tiny_file()
    rows = 2
    cfg = family.model_config(config, SEQ)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=rows, seed=0)
    counted = dot_flops(jax.make_jaxpr(loss_fn)(params, batch).jaxpr)
    tokens = rows * SEQ
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    here = 6 * 8 / 16
    proj = 2 * tokens * (family.active_matmul_params(config)
                         - 4 * 3 * d * f * here)
    experts = family.expert_flops_per_step(config, tokens) / 3
    square = SEQ * SEQ / family.causal_pairs(SEQ)
    cores = 4 * family.dsa_core_flops_per_step(config, rows, SEQ) / 3 * square
    assert family.swa_core_flops_per_step(config, rows, SEQ) \
        == 3 * family.dsa_core_flops_per_step(config, rows, SEQ) \
        * family.window_pairs(SEQ, WINDOW) / family.causal_pairs(SEQ)
    assert counted == proj + experts + cores


# ------------------------- the family: its batches, its reference's numbers

def test_step_1_is_read_on_the_batch_step_0_trained_on():
    pool = family.host_batches(CONFIG, {"seq": 16}, 2, 2147483651, 8)
    assert len(pool) == 8 and pool[1] is pool[0]
    assert pool[0]["tokens"].max() < CONFIG["vocab_size"]
    assert len({b["tokens"].tobytes() for b in pool}) == 7


@pytest.mark.parametrize("key, other", [
    ("moe_num_active_primary_experts", 8), ("rms_norm_eps", 1e-5),
    ("rope_theta", 10000), ("sliding_window_size", 2048),
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("moe_primary_router_apply_softmax", False),
    ("rope_layout", [1, 1, 1, 1]), ("sliding_window_layout", [1, 0, 1, 1])])
def test_the_family_refuses_numbers_its_reference_does_not_state(key, other):
    with pytest.raises(ValueError, match=key):
        family.held_to_the_reference(dict(CONFIG, **{key: other}))


def test_a_checkout_without_the_preset_fails_at_once_and_by_name(monkeypatch):
    """The parent commit has no ``LMConfig.smallthinker_21b_a3b``: the cell
    exits with a message there (rc 1) before anything is built."""
    monkeypatch.delattr(lm.LMConfig, "smallthinker_21b_a3b")
    with pytest.raises(SystemExit, match="no smallthinker_21b_a3b"):
        family.model_config(CONFIG, 16384)


# ---------------------------------------------- the program's own rules

def test_the_programs_own_rules_decide_this_cells_step():
    """No block recomputed (16 B x 370.5 M x 2 is under a v5e: the step
    WITHOUT recomputed blocks stands at 10.92 GB at rest + scratch
    device-less, records/pr51_aot_memory.json), the flash kernels at seq
    16,384, the LEAN head by its logits' bytes, and what a layer's core and
    its two expert products would keep."""
    total = CONFIG["parameters_as_built"]["total"]
    assert not lm.auto_remat_blocks(total, 4, 16e9)
    assert lm.auto_flash_attention(16384, 128, "tpu")
    assert 4 * 16384 * CONFIG["vocab_size"] >= lm.LEAN_HEAD_LOGIT_BYTES
    assert CONFIG["vocab_size"] < 32768     # the bytes engage it, not the rows
    # a core's q + out + log-sum-exp at 28 heads of 128: 235 MB a layer
    assert lm.flash_kept_bytes(16384, 28, 128, 128) == 16384 * 28 * (
        2 * 256 + 4) == 236716032
    # a layer's two expert products [T, 8, 768] bfloat16: 403 MB
    assert lm.held_expert_kept_bytes(16384, (8, 2560, 768)) == 402653184
    assert lm.auto_kept_layers(False, total, 16e9, 16384, 2, routed_layers=4,
                               held_stack=(8, 2560, 768)) == lm.KeptLayers()
    record = bench_json("records", "pr51_aot_memory.json")["cells"][NAME]
    kept, recomputed = record["blocks_kept"], record["blocks_recomputed"]
    assert kept["argument_size_in_bytes"] + kept["temp_size_in_bytes"] \
        < 15.35e9
    assert recomputed["temp_size_in_bytes"] < kept["temp_size_in_bytes"]


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


def test_a_step_names_its_window_cores_and_counts_their_tiles(tiny,
                                                             monkeypatch):
    """Scope ``swa_core`` inside ``attn_core`` on the window layers' cores,
    ``dsa_core`` on the global layer's, the router's product under
    ``moe_route``; gauges and counters as traced."""
    from autodist_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_ROWS", 16)
    cfg, _, params, _, batch = tiny
    loss_fn, _, _, _ = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2,
                                           seed=0, attention="flash")
    before = dict(telemetry.counters())
    text = jax.jit(jax.grad(loss_fn)).lower(params, batch).as_text(
        debug_info=True)
    moved = {k: telemetry.counters()[k] - before.get(k, 0) for k in (
        "attention.window_tiles", "attention.window_tiles_causal")}
    # three window layers, forward and backward: 5 of 6 tiles each
    assert moved == {"attention.window_tiles": 30,
                     "attention.window_tiles_causal": 36}
    names = set()
    for line in text.splitlines():
        if "loc(" in line and "blocks/" in line:
            names.update(part for part in line.split('"') if "blocks/" in part)
    under = lambda *scope: [n for n in names  # noqa: E731
                            if all(s in n.split("/") for s in scope)]
    assert under(scopes.ATTN_CORE, scopes.SWA_CORE)
    assert under(scopes.ATTN_CORE, scopes.DSA_CORE)
    assert not under(scopes.SWA_CORE, scopes.DSA_CORE)
    assert all("layer_0" in n for n in under(scopes.DSA_CORE))
    assert not any("layer_0" in n for n in under(scopes.SWA_CORE))
    assert under(scopes.MOE, scopes.MOE_ROUTE) and under(scopes.MOE_EXPERTS)
    assert scopes.SWA_CORE in scopes.SCOPES


# ---------------------------------------------------- the normal path, fit

@pytest.mark.parametrize("devices", [1, 2])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """make_train_setup -> AutoDist(AllReduce()).build -> Runner.fit, as the
    other eight configurations go, against ``train_check``; every leaf of
    the state moves."""
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
        assert np.any(np.asarray(after[name]) != np.asarray(before[name])), name


# -- what the cell's loss_rtol refuses (benchmark/tools/loss_limit_smallthinker.py)

PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at a tiny size (a
    window of 10 under 48 positions), read as the benchmark's driver reads a
    run."""
    traffic = dict(TRAFFIC, batch_per_chip=2, seq=SEQ)
    rows = tool.readings(tiny_file(), traffic, 7, CELL["loss_rtol"])
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


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_moves_what_the_driver_reads(tiny_readings, fault):
    """The faults are really planted: each moves the reading by far more
    than the 1e-5 the float32 program and reference differ by."""
    assert tiny_readings["sound"] == 0.0
    assert tiny_readings[fault] > 10 * RTOL


def limit_record(fault):
    """The readings of one fault at the published widths; a reading that
    was not finite (recorded as null) counts as infinitely far."""
    return [float("inf") if r["reading"] is None else r["reading"]
            for r in bench_lines(*RECORD) if r.get("fault") == fault]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len({r["seed"] for r in bench_lines(*RECORD)
                if r.get("fault") == "sound_on_the_chip"}) >= 10
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


@pytest.mark.parametrize("fault", ["computed_in_float8_e4m3fn", "no_step"])
def test_the_limit_lies_between_its_two_readings_with_room(fault):
    assert limit_record(fault)
    assert min(limit_record(fault)) > 3 * CELL["loss_rtol"]
    assert max(limit_record("computed_in_bfloat16")) < CELL["loss_rtol"]


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr51_loss_limit.jsonl): a fault is
    REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])


# ------------------------------------------------------------ the contract

def test_the_benchmark_gains_one_config_one_cell_and_three_metrics():
    bench = bench_json("..", "BENCHMARK.json")
    cell = [w for w in bench["workloads"] if w["name"] == NAME][0]
    assert cell == dict(cell, config="smallthinker_21b_a3b",
                        traffic="train_b1_s16384_every16", chips=1)
    assert len(cell["why"]) <= 200
    assert TRAFFIC == dict(
        bench_json("traffic", "train_b1_s8192_every16.json"), seq=16384)
    # (the metrics this cell brought: it is the FIRST of their cells; a
    # later cell with a window, Trinity-Mini's since PR 53, appends itself)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == NAME}
    assert sorted(mine) == ["swa_core_ms_per_step", "swa_core_roofline_pct",
                            "swa_tiles_share"]
    assert all(m["layer"] == "model ops" and m["moves"] == "train_tok_s"
               for m in mine.values())
    reported = {m["name"] for key in ("end_to_end", "per_layer")
                for m in bench[key] if NAME in m.get("workloads", [NAME])}
    assert {"train_tok_s", "setup_s", "mfu_pct", "dsa_core_ms_per_step",
            "dsa_core_roofline_pct", "attn_core_ms_per_step",
            "moe_ms_per_step", "moe_route_ms_per_step",
            "expert_mm_roofline_pct", "moe_held_pairs_share",
            "held_expert_fullest_over_even", "head_ms_per_step",
            "block_rest_ms_per_step"} <= reported
    # no block is recomputed, no shared expert, no state-space layer
    assert not {"remat_ms_per_step", "moe_shared_ms_per_step",
                "mamba_ms_per_step"} & reported


def test_the_tile_share_reads_the_setup_accounts_counters(monkeypatch):
    from benchmark import setup_account as sa
    from benchmark.layer_metrics import swa_tiles_share
    monkeypatch.setattr(sa, "account", lambda: {"counters": {
        "attention.window_tiles": 6 * 252.0,
        "attention.window_tiles_causal": 6 * 528.0}})
    assert swa_tiles_share.read({}, None) == pytest.approx(0.477, abs=5e-4)
    # a program from before the counters, or one without a window layer
    monkeypatch.setattr(sa, "account", lambda: {"counters": {}})
    assert swa_tiles_share.read({}, None) is None
    monkeypatch.setattr(sa, "account", lambda: None)
    assert swa_tiles_share.read({}, None) is None
