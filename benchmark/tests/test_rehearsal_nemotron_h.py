"""The Nemotron-H cell end to end on the CPU at a tiny size (the harness
finds the family, reference, traffic, cell and the five new readers by
name), and the readers on a program that lacks what they read."""
import json
import os
import types

import pytest

from benchmark.families import nemotron_h
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "nemotron_twotower_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/nemotron_h_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
NEW_READERS = ["mamba_ms_per_step", "ssd_scan_ms_per_step",
               "ssd_scan_roofline_pct", "mamba_mix_roofline_pct",
               "mamba_chunk_carry"]
# the readers of the lean head, the routed layer, the held share, the
# grouped attention core and the recomputed blocks: this cell runs those
# layers, so it is on their lists
SHARED_READERS = ["head_ms_per_step", "moe_ms_per_step",
                  "moe_route_ms_per_step", "expert_mm_roofline_pct",
                  "moe_held_pairs_share", "held_expert_fullest_over_even",
                  "attn_ms_per_step", "dsa_core_ms_per_step",
                  "dsa_core_roofline_pct", "remat_ms_per_step"]


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
    assert set(NEW_READERS + SHARED_READERS) <= allowed
    # latent attention's, the indexer's, KDA's, the convolution's, the
    # loop's, DeepSeek-V2's balance loss and OLMoE's load key are not this
    # cell's
    assert not {"mla_ms_per_step", "mla_core_ms_per_step", "kda_ms_per_step",
                "kda_scan_ms_per_step", "dsa_index_ms_per_step",
                "dsa_topk_ms_per_step", "dsa_selected_share",
                "conv_ms_per_step", "loop_ms_per_step",
                "router_aux_per_layer", "expert_load_max_over_mean"} & allowed
    # the CPU has no device trace: the trace readers return None and are
    # left out; the counters' readers have the program's counters
    metrics = result["metrics"]
    assert not set(NEW_READERS[:4]) & set(metrics)
    # 4 of 16 experts held, 6 a token: a quarter of the pairs when even
    assert 0.05 < metrics["moe_held_pairs_share"]["value"] < 0.6
    assert 0 < metrics["held_expert_fullest_over_even"]["value"] <= 16 / 6
    # chunks of 8 tokens: a good part of an incoming state survives one
    assert 0.0 < metrics["mamba_chunk_carry"]["value"] < 1.0


def test_the_benchmark_has_the_configuration_the_cell_and_its_readers():
    """Found by name, not by place: a later PR appends after them."""
    bench = load("BENCHMARK.json")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"]
              if c["name"] == "nemotron_twotower_30b_a3b"]
    assert len(config) == 1 and config[0]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config[0]["file"] == \
        "benchmark/configs/nemotron_twotower_30b_a3b.json"
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and (cell[0]["config"], cell[0]["traffic"],
                               cell[0]["chips"]) == (
        "nemotron_twotower_30b_a3b", "train_b1_s8192_every16", 1)
    assert all(len(e["why"]) <= 200 for e in config + cell)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               and m["layer"] == "model ops" for m in new)
    assert [m["unit"] for m in new] == ["ms", "ms", "%", "%", "ratio"]
    assert [m["source"] for m in new] == ["device_trace"] * 4 + [
        "program_counter"]
    # every list LFM2's cell is in but its convolution's, and the lean
    # head's (LFM2's logits are under its bytes)
    lfm2 = "lfm2_24b_a2b_train_1chip"
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [])}
    theirs = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if lfm2 in m.get("workloads", [])}
    assert theirs - mine == {"conv_ms_per_step", "conv_core_ms_per_step",
                             "conv_mix_roofline_pct"}
    assert mine - theirs == set(NEW_READERS) | {"head_ms_per_step"}
    assert set(SHARED_READERS) <= mine
    # appended, nothing before it moved: every list that holds this cell
    # is in the order of the cells' own list (a later PR appends after it)
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"] == sorted(m["workloads"], key=order.index)


def test_readers_return_nothing_from_a_program_without_what_they_read():
    """The parent commit's program has no ``mamba`` / ``ssd_scan`` scope
    and no ``mamba.chunk_carry`` counter: each new reader leaves its metric
    out and raises nothing; and the closed forms at the cell's size give
    the shares."""
    from benchmark.layer_metrics import (mamba_chunk_carry,
                                         mamba_mix_roofline_pct,
                                         mamba_ms_per_step,
                                         ssd_scan_ms_per_step,
                                         ssd_scan_roofline_pct)
    readers = (mamba_ms_per_step, ssd_scan_ms_per_step,
               ssd_scan_roofline_pct, mamba_mix_roofline_pct,
               mamba_chunk_carry)
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 8192,
           "chips": 1, "counters": {"runner.steps": 9}}
    config = load("benchmark", "configs", "nemotron_twotower_30b_a3b.json")
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        family=nemotron_h, config=config, traffic={"seq": 8192})
    for reader in readers:
        assert reader.read(rec, ctx) is None
    # the time under the scopes, where a trace gave one
    rec["scope_ms_per_step"] = {"mamba": 120.0, "ssd_scan": 45.0}
    assert mamba_ms_per_step.read(rec, ctx) == 120.0
    assert ssd_scan_ms_per_step.read(rec, ctx) == 45.0
    # four layers' recurrence: 1.77 GB at 819 GB/s bound it (2.16 ms; its
    # products are 1.38 ms at the peak)
    assert ssd_scan_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * (4 * 54016 * 8192 / 819e9) / 0.045, rel=1e-9)
    # four layers' two projections, forward and twice backward: 7.61 TFLOP
    # at the peak over the mixers' WHOLE traced time
    assert mamba_mix_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * 7.6101451776e12 / 197e12 / 0.120, rel=1e-9)
    # the counter summed over the steps read back, which the routed
    # layers' chosen pairs count: 8,192 tokens x 6 x 3 layers a step
    rec["counters"] = {"mamba.chunk_carry": 0.5,
                       "moe.chosen_pairs": 5 * 8192 * 18}
    assert mamba_chunk_carry.read(rec, ctx) == pytest.approx(0.1)
    # no peaks (the CPU rehearsal): no share
    ctx.peaks = None
    assert ssd_scan_roofline_pct.read(rec, ctx) is None
    assert mamba_mix_roofline_pct.read(rec, ctx) is None
    # a family without the closed forms: no number
    ctx.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    from benchmark.families import lfm2_moe
    ctx.family = lfm2_moe
    assert ssd_scan_roofline_pct.read(rec, ctx) is None
    assert mamba_mix_roofline_pct.read(rec, ctx) is None
    assert mamba_chunk_carry.read(rec, ctx) is None
