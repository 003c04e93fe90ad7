"""The Trinity-Mini cell end to end on the CPU at a tiny size (the harness
finds the family, reference, traffic, cell and the new reader by name), and
the reader on a program that lacks what it reads."""
import json
import os
import types

import pytest

from benchmark.families import afmoe
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "trinity_mini_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/afmoe_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
NEW_READERS = ["attn_gate_ms_per_step"]
# the readers of the lean head, the routed layer with its shared expert, the
# held share, the dense layer, the grouped and the windowed attention cores
# and the recomputed blocks: this cell runs those layers, so it is on their
# lists
SHARED_READERS = ["head_ms_per_step", "moe_ms_per_step",
                  "moe_route_ms_per_step", "expert_mm_roofline_pct",
                  "moe_held_pairs_share", "held_expert_fullest_over_even",
                  "moe_shared_ms_per_step", "dense_ffn_ms_per_step",
                  "attn_ms_per_step", "attn_core_ms_per_step",
                  "dsa_core_ms_per_step", "dsa_core_roofline_pct",
                  "swa_core_ms_per_step", "swa_core_roofline_pct",
                  "swa_tiles_share", "remat_ms_per_step",
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
                "conv_ms_per_step", "loop_ms_per_step",
                "router_aux_per_layer", "expert_load_max_over_mean",
                "coll_ms_per_step"} & allowed
    # the CPU has no device trace: the new reader returns None and its
    # metric is left out
    metrics = result["metrics"]
    assert not set(NEW_READERS) & set(metrics)
    # 8 of 16 experts held, 8 a token: half of the pairs when even
    assert 0.2 < metrics["moe_held_pairs_share"]["value"] < 0.8
    assert 0 < metrics["held_expert_fullest_over_even"]["value"] <= 16 / 8


def test_the_benchmark_has_the_configuration_the_cell_and_its_reader():
    """Found by name, not by place: a later PR appends after them."""
    bench = load("BENCHMARK.json")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"]
              if c["name"] == "trinity_mini_26b_a3b"]
    assert len(config) == 1 and config[0]["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert config[0]["reduced"] == load(*config[0]["file"].split("/"))[
        "reduced"]
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and (cell[0]["config"], cell[0]["traffic"],
                               cell[0]["chips"]) == (
        "trinity_mini_26b_a3b", "train_b1_s16384_every16", 1)
    assert all(len(e["why"]) <= 200 for e in config + cell)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    # (a later cell with a gated attention appends itself after this one)
    assert all(m["workloads"][0] == CELL and m["moves"] == "train_tok_s"
               and m["layer"] == "model ops" and m["unit"] == "ms"
               and m["source"] == "device_trace" for m in new)
    # every list SmallThinker's cell is on, and those of the dense layer,
    # the shared expert, the recomputed blocks and the gate besides
    small = "smallthinker_train_1chip"
    listed = lambda name: {  # noqa: E731
        m["name"] for m in bench["end_to_end"] + bench["per_layer"]
        if name in m.get("workloads", [])}
    assert listed(small) - listed(CELL) == set()
    assert listed(CELL) - listed(small) >= {
        "attn_gate_ms_per_step", "dense_ffn_ms_per_step",
        "moe_shared_ms_per_step", "remat_ms_per_step"}
    assert set(SHARED_READERS) <= listed(CELL)
    # appended, nothing before it moved
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"] == sorted(m["workloads"], key=order.index)


def test_the_reader_returns_nothing_from_a_program_without_what_it_reads():
    """The parent commit's program has no ``attn_gate`` scope: the new
    reader leaves its metric out and raises nothing; the closed forms at the
    cell's size give the cores' shares, the gate's projection counted in
    the model's FLOPs."""
    from benchmark.layer_metrics import (attn_gate_ms_per_step,
                                         dsa_core_roofline_pct,
                                         swa_core_roofline_pct)
    rec = {"kind": "train_fit", "tracer": None, "tokens_per_step": 16384,
           "chips": 1, "counters": {"runner.steps": 9}}
    config = load("benchmark", "configs", "trinity_mini_26b_a3b.json")
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        family=afmoe, config=config, traffic={"seq": 16384})
    assert attn_gate_ms_per_step.read(rec, ctx) is None
    # the time under the scopes, where a trace gave one
    rec["scope_ms_per_step"] = {"attn_gate": 30.0, "swa_core": 70.0,
                                "dsa_core": 56.0}
    assert attn_gate_ms_per_step.read(rec, ctx) == 30.0
    # four window cores over the 31,458,304 pairs inside the window
    assert swa_core_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * (3 * 2 * 256 * 32 * 31458304 * 4 / 197e12) / 0.070, rel=1e-9)
    # the one global core over all 134,225,920 causal pairs
    assert dsa_core_roofline_pct.read(rec, ctx) == pytest.approx(
        100 * (3 * 2 * 256 * 32 * 134225920 / 197e12) / 0.056, rel=1e-9)
    # 6 FLOPs a token for each of the gate's 5 x 8,388,608 parameters
    ungated = 6.0 * (afmoe.active_matmul_params(config) - 5 * 8388608)
    assert afmoe.train_flops_per_token(config, {"seq": 16384}) - (
        ungated + (afmoe.dsa_core_flops_per_step(config, 1, 16384)
                   + afmoe.swa_core_flops_per_step(config, 1, 16384))
        / 16384) == 6.0 * 5 * 8388608
