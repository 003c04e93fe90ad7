"""The DeepSeek-V2-Lite cell end to end on the CPU at a tiny size (the
harness finds the family, reference, traffic, cell and the four new
readers by name), and the readers on a program that lacks what they
read."""
import json
import os
import types

import pytest

from benchmark.families import deepseek_v2
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "deepseek_v2_lite_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/deepseek_v2_lite_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
NEW_READERS = ["mla_core_ms_per_step", "mla_core_roofline_pct",
               "router_aux_per_layer", "held_expert_fullest_over_even"]


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
    assert "expert_mm_roofline_pct" in allowed
    # its reader divides by a window's routed pairs, which a collapsed
    # router leaves at zero (ROADMAP R-W2), and asks for OLMoE's key:
    # held_expert_fullest_over_even reads the same counters for a share.
    # The plain head carries no lean_head scope: head_ms_per_step would
    # read a constant 0 here
    assert not {"expert_load_max_over_mean", "head_ms_per_step"} & allowed
    # the CPU has no device trace: the trace readers return None and are
    # left out; the counters' readers have the program's counters to read
    metrics = result["metrics"]
    assert set(NEW_READERS) & set(metrics) == {
        "router_aux_per_layer", "held_expert_fullest_over_even"}
    # 4 of 16 experts held, 6 a token: a quarter of the pairs when even
    assert 0.05 < metrics["moe_held_pairs_share"]["value"] < 0.6
    assert 1.0 <= metrics["router_aux_per_layer"]["value"] < 16 / 6
    # the fullest of the 4 held is at least as full as their mean, and
    # no fuller than every token's choice
    assert 16 * metrics["moe_held_pairs_share"]["value"] / 4 <= metrics[
        "held_expert_fullest_over_even"]["value"] <= 16 / 6


def test_the_benchmark_has_the_configuration_the_cell_and_its_readers():
    """Found by name, not by place: a later PR appends after them (PR 29's
    twin of this test pins the last entries and fails since this PR's)."""
    bench = load("BENCHMARK.json")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"] if c["name"] == "deepseek_v2_lite"]
    assert len(config) == 1 and config[0]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and (cell[0]["config"], cell[0]["traffic"],
                               cell[0]["chips"]) == (
        "deepseek_v2_lite", "train_b1_s8192_every16", 1)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               and m["layer"] == "model ops" for m in new)
    listed = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert len(listed) == 1 + 17 + 4 and "train_tok_s" in listed


def test_readers_return_nothing_from_a_program_without_what_they_read():
    """The parent commit's program has no ``mla_core`` scope and counts no
    ``moe.aux_loss``: each new reader leaves its metric out and raises
    nothing."""
    from benchmark.layer_metrics import (mla_core_ms_per_step,
                                         mla_core_roofline_pct,
                                         router_aux_per_layer)
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 8192,
           "chips": 1,
           "counters": {"runner.steps": 9, "moe.chosen_pairs": 2211840.0}}
    config = load("benchmark", "configs", "deepseek_v2_lite.json")
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        family=deepseek_v2, config=config, traffic={"seq": 8192})
    for reader in (mla_core_ms_per_step, mla_core_roofline_pct,
                   router_aux_per_layer):
        assert reader.read(rec, ctx) is None
    # a program that counts no chosen pairs (every expert held), or a
    # configuration that names no router width: no number; no pair on a
    # held expert: 0; the fullest of the held at twice the even share: 2
    from benchmark.layer_metrics import held_expert_fullest_over_even as fullest
    assert fullest.read({"counters": {"moe.routed_pairs": 7.0}}, ctx) is None
    assert fullest.read(rec, types.SimpleNamespace(config={})) is None
    assert fullest.read(rec, ctx) == 0.0
    rec["counters"]["moe.max_expert_pairs"] = 2 * 2211840.0 / 64
    assert fullest.read(rec, ctx) == pytest.approx(2.0)
    # nine steps of five routed layers, each layer's loss 1.5
    rec["counters"]["moe.aux_loss"] = 9 * 5 * 1.5
    assert router_aux_per_layer.read(rec, ctx) == pytest.approx(1.5)
    # the time under the scope, where a trace gave one: 6.19 TFLOP of
    # causal model work over 100 ms
    rec["scope_ms_per_step"] = {"mla_core": 100.0}
    assert mla_core_ms_per_step.read(rec, ctx) == 100.0
    assert mla_core_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * 6.1855e12 / 197e12 / 0.1, rel=1e-4)
    # Kimi-Linear's family has the closed form too; lm's has none
    from benchmark.families import lm as lm_family
    ctx.family = lm_family
    assert mla_core_roofline_pct.read(rec, ctx) is None
