"""The Keye-VL-2.0 cell end to end on the CPU at a tiny size (the harness
finds the family, reference, traffic, cell and the five new readers by
name), and the readers on a program that lacks what they read."""
import json
import os
import types

import pytest

from benchmark.families import keye_vl2
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "keye_vl2_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/keye_vl2_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
NEW_READERS = ["dsa_index_ms_per_step", "dsa_topk_ms_per_step",
               "dsa_core_ms_per_step", "dsa_core_roofline_pct",
               "dsa_selected_share"]


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
    assert set(NEW_READERS) <= allowed
    assert {"expert_mm_roofline_pct", "head_ms_per_step",
            "remat_ms_per_step", "moe_held_pairs_share"} <= allowed
    # latent attention's readers, DeepSeek-V2's balance loss and OLMoE's
    # load key are not this cell's
    assert not {"mla_ms_per_step", "mla_core_ms_per_step",
                "mla_core_roofline_pct", "router_aux_per_layer",
                "held_expert_fullest_over_even",
                "expert_load_max_over_mean", "kda_ms_per_step"} & allowed
    # the CPU has no device trace: the trace readers return None and are
    # left out; the counter's reader has the program's counters to read.
    # Sequences of 32 are under topk 2,048 (the reference's constant):
    # every query keeps all it sees
    metrics = result["metrics"]
    assert set(NEW_READERS) & set(metrics) == {"dsa_selected_share"}
    assert metrics["dsa_selected_share"]["value"] == 1.0
    # 4 of 16 experts held, 8 a token: a quarter of the pairs when even
    assert 0.05 < metrics["moe_held_pairs_share"]["value"] < 0.6


def test_the_benchmark_has_the_configuration_the_cell_and_its_readers():
    """Found by name, not by place: a later PR appends after them."""
    bench = load("BENCHMARK.json")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"] if c["name"] == "keye_vl2_30b_a3b"]
    assert len(config) == 1 and config[0]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config[0]["file"] == "benchmark/configs/keye_vl2_30b_a3b.json"
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and (cell[0]["config"], cell[0]["traffic"],
                               cell[0]["chips"]) == (
        "keye_vl2_30b_a3b", "train_b1_s8192_every16", 1)
    assert all(len(e["why"]) <= 200 for e in config + cell)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               and m["layer"] == "model ops" for m in new)
    assert [m["source"] for m in new] == ["device_trace"] * 4 + [
        "program_counter"]
    # every list DeepSeek-V2-Lite's cell is in, but latent attention's and
    # its balance loss's, and the lean head's
    ds = "deepseek_v2_lite_train_1chip"
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [])}
    theirs = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if ds in m.get("workloads", [])}
    assert theirs - mine == {"mla_ms_per_step", "mla_core_ms_per_step",
                             "mla_core_roofline_pct", "router_aux_per_layer",
                             "held_expert_fullest_over_even"}
    assert mine - theirs == set(NEW_READERS) | {"head_ms_per_step"}
    # appended, nothing before them moved: each list ends with this cell
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL


def test_readers_return_nothing_from_a_program_without_what_they_read():
    """The parent commit's program has no ``dsa_*`` scope and counts no
    ``dsa.*`` pairs: each new reader leaves its metric out and raises
    nothing."""
    from benchmark.layer_metrics import (dsa_core_ms_per_step,
                                         dsa_core_roofline_pct,
                                         dsa_index_ms_per_step,
                                         dsa_selected_share,
                                         dsa_topk_ms_per_step)
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 8192,
           "chips": 1, "counters": {"runner.steps": 9}}
    config = load("benchmark", "configs", "keye_vl2_30b_a3b.json")
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        family=keye_vl2, config=config, traffic={"seq": 8192})
    for reader in (dsa_index_ms_per_step, dsa_topk_ms_per_step,
                   dsa_core_ms_per_step, dsa_core_roofline_pct,
                   dsa_selected_share):
        assert reader.read(rec, ctx) is None
    assert dsa_selected_share.read({}, ctx) is None
    # nine steps of five layers at 8,192 positions and topk 2,048
    rec["counters"].update({"dsa.causal_pairs": 9 * 5 * 33558528.0,
                            "dsa.selected_pairs": 9 * 5 * 14681088.0})
    assert dsa_selected_share.read(rec, ctx) == pytest.approx(0.4375,
                                                              abs=5e-5)
    # the time under the scopes, where a trace gave one: 3.61 TFLOP of
    # model work over the chosen pairs in 100 ms
    rec["scope_ms_per_step"] = {"dsa_core": 100.0, "dsa_index": 30.0,
                                "dsa_topk": 40.0}
    assert dsa_core_ms_per_step.read(rec, ctx) == 100.0
    assert dsa_index_ms_per_step.read(rec, ctx) == 30.0
    assert dsa_topk_ms_per_step.read(rec, ctx) == 40.0
    assert dsa_core_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * 3.60802e12 / 197e12 / 0.1, rel=1e-4)
    # a family without the closed form: no number
    from benchmark.families import deepseek_v2
    ctx.family = deepseek_v2
    assert dsa_core_roofline_pct.read(rec, ctx) is None
