"""The LFM2 cell end to end on the CPU at a tiny size (the harness finds the
family, reference, traffic, cell and the three new readers by name), and
the readers on a program that lacks what they read."""
import json
import os
import types

import pytest

from benchmark.families import lfm2_moe
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "lfm2_24b_a2b_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/lfm2_moe_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
NEW_READERS = ["conv_ms_per_step", "conv_core_ms_per_step",
               "conv_mix_roofline_pct"]
# PR 37's readers of the grouped core's scope and PR 31's of the held
# experts' fullest: this cell runs those layers, so it is on their lists
SHARED_READERS = ["dsa_core_ms_per_step", "dsa_core_roofline_pct",
                  "held_expert_fullest_over_even"]


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
    assert {"expert_mm_roofline_pct", "remat_ms_per_step", "attn_ms_per_step",
            "moe_held_pairs_share", "moe_route_ms_per_step"} <= allowed
    # the lean head's reader (the plain head carries no such scope), latent
    # attention's, the indexer's, KDA's, DeepSeek-V2's balance loss and
    # OLMoE's load key are not this cell's
    assert not {"head_ms_per_step", "mla_ms_per_step", "mla_core_ms_per_step",
                "kda_ms_per_step", "dsa_index_ms_per_step",
                "dsa_topk_ms_per_step", "dsa_selected_share",
                "router_aux_per_layer", "expert_load_max_over_mean"} & allowed
    # the CPU has no device trace: the trace readers return None and are
    # left out; the counters' readers have the program's counters
    metrics = result["metrics"]
    assert not set(NEW_READERS + SHARED_READERS[:2]) & set(metrics)
    # 4 of 16 experts held, 4 a token: a quarter of the pairs when even,
    # and the fullest held expert between an even router's and every token
    assert 0.05 < metrics["moe_held_pairs_share"]["value"] < 0.6
    assert 0 < metrics["held_expert_fullest_over_even"]["value"] <= 16 / 4


def test_the_benchmark_has_the_configuration_the_cell_and_its_readers():
    """Found by name, not by place: a later PR appends after them."""
    bench = load("BENCHMARK.json")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b"]
    assert len(config) == 1 and config[0]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config[0]["file"] == "benchmark/configs/lfm2_24b_a2b.json"
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and (cell[0]["config"], cell[0]["traffic"],
                               cell[0]["chips"]) == (
        "lfm2_24b_a2b", "train_b1_s8192_every16", 1)
    assert all(len(e["why"]) <= 200 for e in config + cell)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               and m["layer"] == "model ops"
               and m["source"] == "device_trace" for m in new)
    assert [m["unit"] for m in new] == ["ms", "ms", "%"]
    # every list Keye-VL-2.0's cell is in, but its indexer's and the lean
    # head's; and DeepSeek-V2-Lite's reader of the fullest held expert
    keye = "keye_vl2_train_1chip"
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [])}
    theirs = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if keye in m.get("workloads", [])}
    assert theirs - mine == {
        "head_ms_per_step", "dsa_index_ms_per_step", "dsa_topk_ms_per_step",
        "dsa_selected_share"}
    assert mine - theirs == set(NEW_READERS) | {
        "held_expert_fullest_over_even"}
    assert set(SHARED_READERS) <= mine
    # appended, nothing before it moved: every list that holds this cell
    # is in the order of the cells' own list (a later PR appends after it)
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"] == sorted(m["workloads"], key=order.index)


def test_readers_return_nothing_from_a_program_without_what_they_read():
    """The parent commit's program has no ``conv_mix`` / ``conv_core``
    scope: each new reader leaves its metric out and raises nothing; and
    the closed forms at the cell's size give the roofline shares."""
    from benchmark.layer_metrics import (conv_core_ms_per_step,
                                         conv_mix_roofline_pct,
                                         conv_ms_per_step,
                                         dsa_core_ms_per_step,
                                         dsa_core_roofline_pct)
    readers = (conv_ms_per_step, conv_core_ms_per_step,
               conv_mix_roofline_pct, dsa_core_ms_per_step,
               dsa_core_roofline_pct)
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 8192,
           "chips": 1, "counters": {"runner.steps": 9}}
    config = load("benchmark", "configs", "lfm2_24b_a2b.json")
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        family=lfm2_moe, config=config, traffic={"seq": 8192})
    for reader in readers:
        assert reader.read(rec, ctx) is None
    # the time under the scopes, where a trace gave one
    rec["scope_ms_per_step"] = {"conv_mix": 50.0, "conv_core": 10.0,
                                "dsa_core": 16.0}
    assert conv_ms_per_step.read(rec, ctx) == 50.0
    assert conv_core_ms_per_step.read(rec, ctx) == 10.0
    assert dsa_core_ms_per_step.read(rec, ctx) == 16.0
    # five conv layers' two projections, 4 d^2 weights, forward and twice
    # backward: 4.12 TFLOP at the peak over the mixers' WHOLE traced time,
    # whatever part of it the stand-alone passes' 10 ms are
    assert lfm2_moe.conv_mix_flops_per_step(config, 8192) == \
        3 * 2 * 4 * 2048 * 2048 * 8192 * 5
    assert conv_mix_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * 4.12316860416e12 / 197e12 / 0.050, rel=1e-6)
    # one layer's causal Q K^T and P V at 32 heads of 64, three times:
    # PR 37's reader, this family's closed form under the name it asks for
    assert dsa_core_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * 0.824734384128e12 / 197e12 / 0.016, rel=1e-6)
    # no peaks (the CPU rehearsal): no share
    ctx.peaks = None
    assert conv_mix_roofline_pct.read(rec, ctx) is None
    assert dsa_core_roofline_pct.read(rec, ctx) is None
    # a family without the closed form: no number
    ctx.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    from benchmark.families import keye_vl2
    ctx.family = keye_vl2
    assert conv_mix_roofline_pct.read(rec, ctx) is None
