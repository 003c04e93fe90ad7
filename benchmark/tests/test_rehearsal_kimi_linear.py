"""The Kimi-Linear cell end to end on the CPU at a tiny size (the harness
finds the family, reference, traffic, cell and the five new readers by
name), the family's closed forms against a hand count, and the readers
on a program that lacks what they read."""
import json
import os
import types

import pytest

from benchmark.families import kimi_linear
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "kimi_linear_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/kimi_linear_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
NEW_READERS = {"kda_ms_per_step", "kda_scan_ms_per_step",
               "kda_scan_roofline_pct", "mla_ms_per_step",
               "moe_held_pairs_share"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace):
    result = rehearse(ROOT, CELL, trace, TINY)
    assert set(result) == KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    bench = load("BENCHMARK.json")
    listed = bench["per_layer" if trace else "end_to_end"]
    allowed = {m["name"] for m in listed
               if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) <= allowed
    if not trace:
        assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
        return
    assert NEW_READERS <= allowed and "expert_mm_roofline_pct" not in allowed
    # the CPU has no device trace: the trace readers return None and are
    # left out; the counters' readers have the program's counters to read
    assert NEW_READERS & set(result["metrics"]) == {"moe_held_pairs_share"}
    # 4 of 32 experts held: an even router sends an eighth of the pairs
    assert 0.02 < result["metrics"]["moe_held_pairs_share"]["value"] < 0.4
    # not listed here: its reader has nothing to divide in a window in
    # which no pair chose a held expert, and a collapsed router gives such
    assert "expert_load_max_over_mean" not in allowed


def test_the_benchmark_gains_one_configuration_and_one_cell():
    bench = load("BENCHMARK.json")
    assert bench["configs"][-1]["name"] == "kimi_linear_48b_a3b"
    assert bench["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train_b1_s8192_every16", 1)
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "kda_ms_per_step", "kda_scan_ms_per_step", "kda_scan_roofline_pct",
        "mla_ms_per_step", "moe_held_pairs_share"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               for m in bench["per_layer"][-5:])
    traffic = load("benchmark", "traffic", "train_b1_s8192_every16.json")
    assert (traffic["batch_per_chip"], traffic["seq"]) == (1, 8192)


def test_readers_return_nothing_from_a_program_without_what_they_read():
    """The parent commit's program has no ``kda`` scope and counts no
    chosen pairs: each new reader leaves its metric out and raises
    nothing."""
    from benchmark.layer_metrics import (kda_ms_per_step,
                                         kda_scan_ms_per_step,
                                         kda_scan_roofline_pct,
                                         mla_ms_per_step,
                                         moe_held_pairs_share)
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 8192,
           "chips": 1,
           "counters": {"runner.steps": 9, "moe.routed_pairs": 65536.0}}
    ctx = types.SimpleNamespace(peaks={"bf16_flops": 197e12,
                                       "hbm_bytes_per_s": 819e9},
                                family=kimi_linear, config={})
    for reader in (kda_ms_per_step, kda_scan_ms_per_step,
                   kda_scan_roofline_pct, mla_ms_per_step,
                   moe_held_pairs_share):
        assert reader.read(rec, ctx) is None
    rec["counters"]["moe.chosen_pairs"] = 2097152.0
    assert moe_held_pairs_share.read(rec, ctx) == 1 / 32
    del rec["counters"]["moe.routed_pairs"]   # stayed at zero all window
    assert moe_held_pairs_share.read(rec, ctx) == 0.0


def test_closed_forms_at_the_published_sizes():
    config = load("benchmark", "configs", "kimi_linear_48b_a3b.json")
    traffic = load("benchmark", "traffic", "train_b1_s8192_every16.json")
    d, hd, dk, h = 2304, 4096, 128, 32
    kda = 4 * d * hd + 2 * (d * dk + dk * hd) + d * h
    mla = d * h * 192 + d * 576 + 512 * h * 256 + hd * d
    moe = d * 256 + 3 * d * 1024 * (1 + 8 * 8 / 256)
    active = 4 * kda + mla + 4 * moe + 3 * d * 9216 + d * 20480
    assert kimi_linear.active_matmul_params(config) == active
    assert round(active / 1e6) == 336
    seq = 8192
    scores = 3 * 2 * (192 + 128) * h * seq * (seq + 1) / 2
    assert kimi_linear.mla_attn_flops_per_step(config, 1, seq) == scores
    assert round(scores / seq / 1e6) == 252          # 84 MFLOP forward
    c = 64
    chunk = 2 * c * c * dk + c * c * 2 * dk + c * c * dk + 6 * c * dk * dk
    core = 3 * chunk * (seq / c) * h * 4
    assert kimi_linear.kda_scan_flops_per_step(config, seq) == core
    assert round(core / seq / 1e6, 1) == 53.5
    assert kimi_linear.train_flops_per_token(config, traffic) == \
        6 * active + scores / seq + core / seq
    assert round(kimi_linear.train_flops_per_token(config, traffic) / 1e9,
                 2) == 2.32
    # the core's bytes bound it: ~110 FLOP a byte against the chip's 240
    moved = kimi_linear.kda_scan_bytes_per_step(config, seq)
    assert moved == (3 * (2 * 3 * dk + 4 * dk + 4) + 2 * 2 * dk) * seq * h * 4
    assert 90 < core / moved < 130
    # every held expert on every token, four routed layers
    assert kimi_linear.expert_flops_per_step(config, seq) == \
        18 * d * 1024 * seq * 8 * 4


def test_layer_types_follow_the_published_indices():
    config = load("benchmark", "configs", "kimi_linear_48b_a3b.json")
    assert kimi_linear.layer_types(config) == (
        "kda", "kda", "kda", "mla", "kda")
    full = dict(config, num_hidden_layers=27)
    types_ = kimi_linear.layer_types(full)
    assert types_.count("mla") == 7 and types_.count("kda") == 20
    assert [i + 1 for i, t in enumerate(types_) if t == "kda"] == \
        config["linear_attn_config"]["kda_layers"]
