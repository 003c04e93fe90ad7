"""The OLMoE cell end to end on the CPU at a tiny size (the harness finds
the family, reference, traffic, cell and the four moe readers by name),
and the family's closed-form FLOPs against a hand count."""
import json
import os

import pytest

from benchmark.families import olmoe
from benchmark.tests.test_rehearsal import KEYS, ROOT, rehearse

CELL = "olmoe_train_1chip"
TINY = ["--config-file", "benchmark/tests/configs/olmoe_tiny.json",
        "--traffic-set", "batch_per_chip=8", "--traffic-set", "seq=16"]
MOE_READERS = {"moe_ms_per_step", "moe_route_ms_per_step",
               "expert_mm_roofline_pct", "expert_load_max_over_mean"}


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
    assert MOE_READERS <= allowed
    # the CPU has no device trace: the three trace readers return None and
    # are left out; the counters' reader has the program's counters to read
    assert MOE_READERS & set(result["metrics"]) == {"expert_load_max_over_mean"}
    assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0


def test_readers_return_nothing_from_a_program_without_the_counters():
    from benchmark.layer_metrics import (expert_load_max_over_mean,
                                         expert_mm_roofline_pct,
                                         moe_ms_per_step,
                                         moe_route_ms_per_step)
    rec = {"kind": "train_fit", "tracer": None, "counters": {"runner.steps": 9}}
    for reader in (moe_ms_per_step, moe_route_ms_per_step,
                   expert_mm_roofline_pct, expert_load_max_over_mean):
        assert reader.read(rec, None) is None


def test_a_fusion_is_named_by_its_own_scope_then_by_its_members():
    from benchmark.layer_metrics.moe_ms_per_step import in_scope
    fwd = "jit(local_step)/shard_map/jvp(loss)/blocks/layer_0/moe/moe_route/gather"
    bwd = "jit(local_step)/shard_map/transpose(jvp(loss))/moe/moe_route/gather"
    opt = "jit(local_step)/shard_map/optimizer/mul"
    assert in_scope([fwd], "moe") and in_scope([bwd], "moe_route")
    assert not in_scope([fwd], "moe_experts") and not in_scope([], "moe")
    assert not in_scope([opt, fwd, fwd], "moe")  # its own name has a phase
    assert in_scope(["", fwd, fwd, opt], "moe")  # unnamed: the majority
    assert not in_scope(["", fwd, opt, opt], "moe")


def test_train_flops_per_token_at_the_published_sizes():
    config = load("benchmark", "configs", "olmoe_1b_7b.json")
    traffic = load("benchmark", "traffic", "train_b4_s2048_every16.json")
    d, f, k, n_experts, vocab, seq = 2048, 1024, 8, 64, 50304, 2048
    by_hand = 6 * (4 * d * d + k * 3 * d * f + d * n_experts + d * vocab) \
        + 12 * seq * d
    assert olmoe.train_flops_per_token(config, traffic) == by_hand
    assert round(by_hand / 1e9, 2) == 1.07
    # experts: 302 MFLOP a token, 8,192 tokens a step
    assert olmoe.expert_flops_per_step(config, 8192) == 18 * d * f * 8192 * k
    assert round(18 * d * f * k / 1e6) == 302
    # at the published depth the routed feed-forward is ~60 % of the FLOPs
    full = dict(config, num_hidden_layers=16)
    share = 16 * 18 * d * f * k / olmoe.train_flops_per_token(full, traffic)
    assert 0.58 < share < 0.63


def test_the_configuration_file_keeps_the_published_config():
    config = load("benchmark", "configs", "olmoe_1b_7b.json")
    differs = [k for k, v in config["published"].items() if config[k] != v]
    assert differs == config["reduced"] == ["num_hidden_layers"]
    cfg = olmoe.model_config(config, 2048)
    from autodist_tpu.models.lm import LMConfig
    import dataclasses
    want = dataclasses.replace(LMConfig.olmoe_1b_7b(num_layers=1),
                               dtype=cfg.dtype)
    assert cfg == want
