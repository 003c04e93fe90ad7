"""The seven readers of the step account (PR 49), by hand on the CPU: the
memory readers return a number from a traced rehearsal and nothing from
an untraced one; the time readers, on a device trace made up over the
scope map of a tiny step this process compiled, tile the time under
``blocks``; all seven read None on a program from before the account;
their entries in ``BENCHMARK.json`` are found by name, never by position."""
import importlib
import json
import os
import types

import pytest

from benchmark import phases
from benchmark.tests.test_phases import FakeTracer
from benchmark.tests.test_rehearsal import CELLS, ROOT, rehearse
from benchmark.trace import reduce as tr

CELL = "lm1b_train_1chip"
NINE = ["lm1b_train_1chip", "lm1b_train_4chip_ar", "olmoe_train_1chip",
        "kimi_linear_train_1chip", "deepseek_v2_lite_train_1chip",
        "keye_vl2_train_1chip", "lfm2_24b_a2b_train_1chip",
        "ouro_2_6b_train_1chip", "nemotron_twotower_train_1chip"]
MEMORY_FIELDS = {"temp_bytes", "argument_bytes", "output_bytes",
                 "alias_bytes", "code_bytes", "peak_bytes"}
PARTS = ("attention", "moe", "dense_ffn", "block_rest")
READERS = {  # name: (unit, source, layer)
    "step_scratch_gib": ("GiB", "program_counter", "device"),
    "step_code_mib": ("MiB", "program_counter", "lowering"),
    "dense_ffn_ms_per_step": ("ms", "device_trace", "model ops"),
    "attn_core_ms_per_step": ("ms", "device_trace", "model ops"),
    "plain_head_ms_per_step": ("ms", "device_trace", "model ops"),
    "moe_shared_ms_per_step": ("ms", "device_trace", "model ops"),
    "block_rest_ms_per_step": ("ms", "device_trace", "model ops")}


def read(name, rec):
    module = importlib.import_module("benchmark.layer_metrics." + name)
    return module.read(rec, types.SimpleNamespace())


def test_the_benchmark_lists_the_seven_by_name_for_accepted_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, layer) in READERS.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, layer, "train_tok_s",
                                 "lower"), name
        listed = m["workloads"]
        assert listed and listed == [c for c in cells if c in listed], name
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py")), name
    for name in ("step_scratch_gib", "step_code_mib", "attn_core_ms_per_step",
                 "block_rest_ms_per_step"):  # (a later cell may join them)
        assert [c for c in by_name[name]["workloads"] if c in NINE] == NINE


@pytest.mark.parametrize("trace", [0, 1])
def test_a_traced_rehearsal_reads_the_steps_scratch_an_untraced_one_nothing(
        tmp_path, trace):
    config, extra = CELLS[CELL]
    diag = str(tmp_path / "diag.json")
    result = rehearse(ROOT, CELL, trace, extra + [
        "--config-file", "benchmark/tests/configs/" + config,
        "--diag", diag])
    assert result["correct"] is True
    with open(diag) as f:
        diag = json.load(f)
    if not trace:  # nothing is compiled for an account nobody reads
        assert diag["step_account"] is None
        assert not {"step_scratch_gib", "step_code_mib"} & set(diag["values"])
        return
    assert result["metrics"]["step_scratch_gib"]["value"] > 0
    assert result["metrics"]["step_code_mib"]["value"] >= 0  # 0 on the CPU
    memory = diag["step_account"]
    assert set(memory) == MEMORY_FIELDS
    assert memory["temp_bytes"] == pytest.approx(
        result["metrics"]["step_scratch_gib"]["value"] * 2 ** 30)
    assert memory["argument_bytes"] > 0 and memory["output_bytes"] > 0
    # the CPU has no device trace: the time readers are left out
    assert not {n for n in READERS if n.endswith("_ms_per_step")} & set(
        result["metrics"])


@pytest.fixture(scope="module")
def tiny_step():
    """The scope map of a tiny lm1b step (lean head and plain head, one
    build each) and a device trace made up over it: one chip, two runs of
    the step's module, every instruction of the map that carries a name
    one event, of a length of its own."""
    import optax

    import autodist_tpu
    from autodist_tpu import strategy, telemetry
    from autodist_tpu.models import lm
    out = {}
    for head, lean in (("lean", True), ("plain", False)):
        loss_fn, params, batch, _ = lm.make_train_setup(
            lm.LMConfig.tiny(), seq_len=16, batch_size=8, lean_head=lean)
        autodist_tpu.reset()
        ad = autodist_tpu.AutoDist(strategy_builder=strategy.AllReduce())
        runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
        runner.init(params)
        runner.run(batch)
        scope_map = telemetry.scope_map(phases.STEP_MODULE)
        named = sorted(n for n, ops in scope_map.items() if any(ops))
        events, t = [], 0
        for i, name in enumerate(named):
            events.append([name, t, 100 + 7 * (i % 13), {}])
            t += events[-1][2]
        shifted = [[n, s + t + 50, d, st] for n, s, d, st in events]
        table = {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": tr.MODULES_LINE, "events": [
                [phases.STEP_MODULE + "(1)", 0, t, {}],
                [phases.STEP_MODULE + "(1)", t + 50, t, {}]]},
            {"name": tr.OPS_LINE, "events": events + shifted}]}]}
        out[head] = (scope_map, table, (0, 2 * t + 50))
    autodist_tpu.reset()
    return out


def record(table, window):
    return {"kind": "train_fit", "chips": 1, "traced_steps": 2,
            "tracer": FakeTracer(table, window)}


def test_the_four_parts_tile_the_time_under_blocks(tiny_step, monkeypatch):
    scope_map, table, window = tiny_step["lean"]
    monkeypatch.setattr(phases, "program_map", lambda name: scope_map)
    rec = record(table, window)
    rest = read("block_rest_ms_per_step", rec)
    kept = rec["scope_ms_per_step"]
    assert set(PARTS) | {"blocks"} <= set(kept)
    assert kept["moe"] is None              # a dense model: no such scope
    assert kept["block_rest"] == rest > 0
    assert kept["named_outside_blocks"] == 0.0  # nothing hoisted out of a loop
    assert sum(kept[p] or 0.0 for p in PARTS) == pytest.approx(
        kept["blocks"], abs=1e-6)
    dense = read("dense_ffn_ms_per_step", rec)
    core = read("attn_core_ms_per_step", rec)
    assert dense == kept["dense_ffn"] > 0
    assert 0 < core < kept["attention"] == read("attn_ms_per_step", rec)
    # no matmul is in the rest, and its ops are in the diagnostics
    assert rec["block_rest_ops"] and len(rec["block_rest_ops"]) <= 10
    for label, ms in rec["block_rest_ops"]:
        names = scope_map[label.split(" [", 1)[0]]
        assert not names[0].endswith(("dot_general", "conv_general_dilated"))
        assert ms > 0
    # this step has the lean head and no shared expert
    assert read("plain_head_ms_per_step", rec) is None
    assert read("moe_shared_ms_per_step", rec) is None


def test_what_a_loop_hoists_out_of_blocks_is_counted_apart(tiny_step,
                                                           monkeypatch):
    """A looped model's loop-invariant casts leave the scanned body with
    the body's own names alone (``jit(local_step)/TransformerLM.one_pass/
    layer_0/.../dense_ffn/mlp/up_proj/convert_element_type``: no ``loss``,
    no ``blocks``): ``dense_ffn_ms_per_step`` holds them, the time under
    ``blocks`` does not, and the diagnostics say how much that is."""
    scope_map, table, window = tiny_step["lean"]
    name = next(n for n, ops in sorted(scope_map.items()) if len(ops) == 1
                and "dense_ffn" in ops[0].split("/"))
    tail = scope_map[name][0].split("/")
    hoisted = dict(scope_map, **{name: [
        "jit(local_step)/" + "/".join(tail[tail.index("blocks") + 1:])]})
    monkeypatch.setattr(phases, "program_map", lambda name: hoisted)
    rec = record(table, window)
    read("block_rest_ms_per_step", rec)
    kept = rec["scope_ms_per_step"]
    assert kept["named_outside_blocks"] > 0
    assert sum(kept[p] or 0.0 for p in PARTS) - kept[
        "named_outside_blocks"] == pytest.approx(kept["blocks"], abs=1e-6)


def test_the_plain_head_is_read_where_the_step_has_it(tiny_step, monkeypatch):
    scope_map, table, window = tiny_step["plain"]
    monkeypatch.setattr(phases, "program_map", lambda name: scope_map)
    rec = record(table, window)
    head = read("plain_head_ms_per_step", rec)
    assert head > 0 and read("head_ms_per_step", rec) == 0.0  # the lean one's


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_from_before_the_account_reads_none(tiny_step, monkeypatch,
                                                      name):
    """The parent of PR 49: no ``step_account``, a map without the three
    new scopes. Nothing raises, nothing is compiled, the metric is left
    out."""
    from autodist_tpu import telemetry
    scope_map, table, window = tiny_step["plain"]
    new = {"attn_core", "dense_ffn", "plain_head", "moe_shared"}
    bare = {n: ["/".join(p for p in o.split("/") if p not in new)
                for o in ops] for n, ops in scope_map.items()}
    monkeypatch.setattr(phases, "program_map", lambda name: bare)
    monkeypatch.delattr(telemetry, "step_account")
    rec = record(table, window)
    assert read(name, rec) is None
    assert rec.get("step_account") is None
    # and an untraced run asks the program nothing
    monkeypatch.setattr(phases, "program_map", lambda name: 1 / 0)
    assert read(name, {"kind": "train_fit", "tracer": None}) is None
