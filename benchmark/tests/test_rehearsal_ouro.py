"""The Ouro cell end to end on the CPU at a tiny size (the harness finds the
family, reference, traffic, cell and the four new readers by name), and
the readers on a program that lacks what they read."""
import json
import math
import os
import types

import pytest

from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "ouro_2_6b_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/ouro_tiny.json",
        "--traffic-set", "batch_per_chip=2", "--traffic-set", "seq=32"]
TRACE_READERS = ["loop_ms_per_step", "exit_gate_ms_per_step"]
COUNTER_READERS = ["loop_exit_entropy", "loop_last_pass_mass"]
NEW_READERS = TRACE_READERS + COUNTER_READERS


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
    assert set(NEW_READERS) | {"head_ms_per_step", "attn_ms_per_step",
                               "remat_ms_per_step", "mfu_pct"} <= allowed
    # no routed layer, no latent or delta-rule mixer, no indexer, no conv
    assert not {n for n in allowed if n.startswith(
        ("moe_", "expert_", "mla_", "kda_", "dsa_", "conv_", "held_",
         "router_", "coll_", "sync_"))}
    # the CPU has no device trace: the trace readers return None and are
    # left out; the counters' readers have the program's counters
    metrics = result["metrics"]
    assert not set(TRACE_READERS) & set(metrics)
    assert 0 < metrics["loop_exit_entropy"]["value"] <= math.log(4)
    assert 0 < metrics["loop_last_pass_mass"]["value"] < 1


def test_the_benchmark_has_the_configuration_the_cell_and_its_readers():
    """Found by name, not by place: a later PR appends after them."""
    bench = load("BENCHMARK.json")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"] if c["name"] == "ouro_2_6b"]
    assert len(config) == 1 and config[0]["reduced"] == ["num_hidden_layers"]
    assert config[0]["file"] == "benchmark/configs/ouro_2_6b.json"
    assert config[0]["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and (cell[0]["config"], cell[0]["traffic"],
                               cell[0]["chips"]) == (
        "ouro_2_6b", "train_b1_s4096_every16", 1)
    assert all(len(e["why"]) <= 200 for e in config + cell)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               and m["layer"] == "model ops" for m in new)
    assert [m["source"] for m in new] == ["device_trace"] * 2 \
        + ["program_counter"] * 2
    assert [m["unit"] for m in new] == ["ms", "ms", "nats", "ratio"]
    # every list the dense sibling's cell is in, and the recomputed
    # blocks' reader, which lm1b's step (nothing recomputed) is not on
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [])}
    lm1b = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if "lm1b_train_1chip" in m.get("workloads", [])}
    assert mine - lm1b == set(NEW_READERS) | {"remat_ms_per_step"}
    assert not lm1b - mine
    # appended, nothing before it moved: every list that holds this cell
    # is in the order of the cells' own list (a later PR appends after it)
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"] == sorted(m["workloads"], key=order.index)


def test_readers_return_nothing_from_a_program_without_what_they_read():
    """The parent commit's program has no ``loop`` / ``exit_gate`` scope
    and counts no exit mass: each new reader leaves its metric out and
    raises nothing."""
    from benchmark.layer_metrics import (exit_gate_ms_per_step,
                                         loop_exit_entropy,
                                         loop_last_pass_mass,
                                         loop_ms_per_step)
    readers = (loop_ms_per_step, exit_gate_ms_per_step, loop_exit_entropy,
               loop_last_pass_mass)
    config = load("benchmark", "configs", "ouro_2_6b.json")
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        config=config, traffic={"seq": 4096})
    for rec in ({"kind": "train_fit", "tracer": None,
                 "counters": {"runner.steps": 9}},
                {"kind": "train_fit", "tracer": None}):
        for reader in readers:
            assert reader.read(rec, ctx) is None
    # the time under the scopes, where a trace gave one
    rec = {"kind": "train_fit", "tracer": None,
           "scope_ms_per_step": {"loop": 400.0, "exit_gate": 0.5},
           # two steps read back: the masses of each sum to 1
           "counters": {"loop.exit_mass_1": 0.9, "loop.exit_mass_2": 0.5,
                        "loop.exit_mass_3": 0.3, "loop.exit_mass_4": 0.3,
                        "loop.exit_entropy": 2.4}}
    assert loop_ms_per_step.read(rec, ctx) == 400.0
    assert exit_gate_ms_per_step.read(rec, ctx) == 0.5
    assert loop_exit_entropy.read(rec, ctx) == pytest.approx(1.2)
    assert loop_last_pass_mass.read(rec, ctx) == pytest.approx(0.15)
    # a configuration of another family names no last pass
    ctx.config = {"num_hidden_layers": 6}
    assert loop_last_pass_mass.read(rec, ctx) is None
