"""The SmallThinker cell end to end on the CPU at a tiny size (the harness
finds the family, reference, traffic, cell and the three new readers by
name), and the readers on a program that lacks what they read."""
import json
import os
import types

import pytest

from benchmark.families import smallthinker
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "smallthinker_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/smallthinker_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
NEW_READERS = ["swa_core_ms_per_step", "swa_core_roofline_pct",
               "swa_tiles_share"]
# the readers of the lean head, the routed layer, the held share and the
# grouped attention core: this cell runs those layers, so it is on their
# lists (no block is recomputed here: not on ``remat_ms_per_step``'s)
SHARED_READERS = ["head_ms_per_step", "moe_ms_per_step",
                  "moe_route_ms_per_step", "expert_mm_roofline_pct",
                  "moe_held_pairs_share", "held_expert_fullest_over_even",
                  "attn_ms_per_step", "attn_core_ms_per_step",
                  "dsa_core_ms_per_step", "dsa_core_roofline_pct",
                  "block_rest_ms_per_step"]


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
    assert not {"mla_ms_per_step", "kda_ms_per_step", "mamba_ms_per_step",
                "dsa_index_ms_per_step", "dsa_selected_share",
                "conv_ms_per_step", "loop_ms_per_step", "remat_ms_per_step",
                "moe_shared_ms_per_step", "router_aux_per_layer",
                "expert_load_max_over_mean"} & allowed
    # the CPU has no device trace and takes XLA's attention (no windowed
    # launch is traced): the three new readers return None and are left out
    metrics = result["metrics"]
    assert not set(NEW_READERS) & set(metrics)
    # 8 of 16 experts held, 6 a token: half of the pairs when even
    assert 0.2 < metrics["moe_held_pairs_share"]["value"] < 0.8
    assert 0 < metrics["held_expert_fullest_over_even"]["value"] <= 16 / 6


def test_the_benchmark_has_the_configuration_the_cell_and_its_readers():
    """Found by name, not by place: a later PR appends after them."""
    bench = load("BENCHMARK.json")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"]
              if c["name"] == "smallthinker_21b_a3b"]
    assert len(config) == 1 and config[0]["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and (cell[0]["config"], cell[0]["traffic"],
                               cell[0]["chips"]) == (
        "smallthinker_21b_a3b", "train_b1_s16384_every16", 1)
    assert all(len(e["why"]) <= 200 for e in config + cell)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               and m["layer"] == "model ops" for m in new)
    assert [m["unit"] for m in new] == ["ms", "%", "ratio"]
    assert [m["source"] for m in new] == ["device_trace"] * 2 + [
        "program_counter"]
    # every list Nemotron-H's cell is in but its state-space layers', its
    # shared expert's and the recomputed blocks'
    nemotron = "nemotron_twotower_train_1chip"
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [])}
    theirs = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if nemotron in m.get("workloads", [])}
    assert theirs - mine == {
        "mamba_ms_per_step", "ssd_scan_ms_per_step", "ssd_scan_roofline_pct",
        "mamba_mix_roofline_pct", "mamba_chunk_carry",
        "moe_shared_ms_per_step", "remat_ms_per_step"}
    assert mine - theirs == set(NEW_READERS)
    assert set(SHARED_READERS) <= mine
    # appended, nothing before it moved
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"] == sorted(m["workloads"], key=order.index)


def test_readers_return_nothing_from_a_program_without_what_they_read():
    """The parent commit's program has no ``swa_core`` scope and no
    ``attention.window_tiles`` counter: each new reader leaves its metric
    out and raises nothing; and the closed form at the cell's size gives
    the share."""
    from benchmark import setup_account as sa
    from benchmark.layer_metrics import (dsa_core_roofline_pct,
                                         swa_core_ms_per_step,
                                         swa_core_roofline_pct,
                                         swa_tiles_share)
    readers = (swa_core_ms_per_step, swa_core_roofline_pct, swa_tiles_share)
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 16384,
           "chips": 1, "counters": {"runner.steps": 9}}
    config = load("benchmark", "configs", "smallthinker_21b_a3b.json")
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        family=smallthinker, config=config, traffic={"seq": 16384})
    for reader in readers:
        assert reader.read(rec, ctx) is None
    # the time under the scopes, where a trace gave one
    rec["scope_ms_per_step"] = {"swa_core": 80.0, "dsa_core": 60.0}
    assert swa_core_ms_per_step.read(rec, ctx) == 80.0
    # three window cores over the 58,722,304 pairs inside the window
    assert swa_core_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * (3 * 2 * 256 * 28 * 58722304 * 3 / 197e12) / 0.080, rel=1e-9)
    # the one global core over all 134,225,920 causal pairs
    assert dsa_core_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * (3 * 2 * 256 * 28 * 134225920 / 197e12) / 0.060, rel=1e-9)
    # no peaks (the CPU rehearsal): no share
    ctx.peaks = None
    assert swa_core_roofline_pct.read(rec, ctx) is None
    # a family without the closed form: no number
    ctx.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    from benchmark.families import lfm2_moe
    ctx.family = lfm2_moe
    assert swa_core_roofline_pct.read(rec, ctx) is None
    # no account, or one without the counters: no share of the tiles
    before = sa.account
    try:
        sa.account = lambda: None
        assert swa_tiles_share.read(rec, ctx) is None
        sa.account = lambda: {"counters": {
            "attention.window_tiles": 1512.0,
            "attention.window_tiles_causal": 3168.0}}
        assert swa_tiles_share.read(rec, ctx) == pytest.approx(252 / 528)
    finally:
        sa.account = before
