"""The eight readers of the program's set-up account (PR 33): on the CPU
each returns a number from a traced rehearsal, and None on a program
that keeps no account (a checkout from before it) or recorded none; their
entries in ``BENCHMARK.json`` are found by name, never by position."""
import importlib
import json
import os
import types

import pytest

from benchmark import setup_account as sa
from benchmark.tests.test_rehearsal import CELLS, ROOT, rehearse

CELL = "lm1b_train_1chip"
READERS = {  # name: (unit, source, layer, moves)
    "setup_program_s": ("s", "program_span", "entry plan", "setup_s"),
    "trace_lower_s": ("s", "program_span", "lowering", "setup_s"),
    "xla_compile_s": ("s", "program_span", "lowering", "setup_s"),
    "cache_load_s": ("s", "program_span", "lowering", "setup_s"),
    "init_state_s": ("s", "program_span", "entry plan", "setup_s"),
    "window_compiles": ("count", "program_counter", "lowering",
                        "train_tok_s"),
    "hbm_at_rest_gib": ("GiB", "program_counter", "device", "train_tok_s"),
    "setup_peak_hbm_gib": ("GiB", "program_counter", "device",
                           "train_tok_s")}


def read(name, rec, ctx=None):
    module = importlib.import_module("benchmark.layer_metrics." + name)
    return module.read(rec, ctx or types.SimpleNamespace(t_start=0.0))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    config, extra = CELLS[CELL]
    diag = str(tmp_path_factory.mktemp("setup") / "diag.json")
    result = rehearse(ROOT, CELL, 1, extra + [
        "--config-file", "benchmark/tests/configs/" + config,
        "--diag", diag])
    with open(diag) as f:
        return result, json.load(f)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_traced_rehearsal_lists_the_reader_with_a_number(traced, name):
    result, _ = traced
    assert result["correct"] is True
    m = result["metrics"][name]
    assert m["unit"] == READERS[name][0] and m["value"] >= 0.0


def test_the_parts_of_set_up_fit_into_the_programs_part(traced):
    result, diag = traced
    v = {k: m["value"] for k, m in result["metrics"].items()}
    assert v["setup_program_s"] > 0 and v["init_state_s"] > 0
    assert v["trace_lower_s"] > 0
    assert v["xla_compile_s"] + v["cache_load_s"] > 0   # one or the other
    assert (v["trace_lower_s"] + v["xla_compile_s"] + v["cache_load_s"]
            <= v["setup_program_s"])
    assert v["window_compiles"] == 0.0
    # the driver's two clocks hold the same intervals from outside
    assert abs(v["setup_program_s"] - v["build_s"] - v["compile_s"]) < 0.25
    acc = diag["setup_account"]
    names = [p["name"] for p in acc["phases"]]
    assert names[0] == "setup.build" and names[-1] == "setup.first_step"
    assert all(p["self_s"] >= 0 and p["offset_s"] > 0 for p in acc["phases"])
    assert "jit(local_step)" in acc["phases"][-1]["programs"]
    assert acc["gauges"]["lean_head.chunks"] > 0
    assert acc["counters"]["compile.traces"] > 0
    assert acc["hbm_peak_set_in"] is None   # the CPU reports no memory


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_an_account_reads_none(monkeypatch, name):
    from autodist_tpu import telemetry
    rec = {"counters": {}, "peak_bytes_after_reference": 0}
    telemetry.reset()
    assert read(name, rec) is None          # an account with nothing in it
    monkeypatch.delattr(telemetry, "setup_account")
    assert read(name, rec) is None          # a program from before it
    assert "setup_account" not in rec


def test_window_compiles_counts_compiles_and_loads_of_the_window(monkeypatch):
    monkeypatch.setattr(sa, "account", lambda: {"phases": []})
    assert read("window_compiles", {"counters": {"runner.steps": 9.0}}) == 0.0
    assert read("window_compiles", {"counters": {
        "compile.backend_compiles": 1.0, "compile.cache_hits": 2.0,
        "compile.traces": 40.0}}) == 3.0
    assert read("window_compiles", {}) is None  # an untraced run: no counters


def test_the_peak_is_named_after_the_phase_that_set_it():
    def p(i, name, parent, a, b, peak):
        return {"name": name, "id": i, "parent": parent,
                "start_ns": a, "end_ns": b,
                "args": {"hbm_in_use": peak // 2, "hbm_peak": peak,
                         "trace_lower_s": 0.5, "programs": {}}}
    acc = {"phases": [p(2, "setup.capture", 1, 1, 2, 10),
                      p(1, "setup.build", 0, 0, 3, 10),
                      p(4, "setup.init_state", 3, 4, 8, 30),
                      p(3, "setup.init", 0, 4, 9, 30),
                      p(5, "setup.first_step", 0, 9, 12, 30)],
           "counters": {}, "gauges": {}}
    out = sa.diagnostics(acc, 0.0, peak_before_build=10)
    assert out["hbm_peak_set_in"] == "setup.init_state"
    assert out["hbm_peak_bytes"] == 30
    assert [r["name"] for r in out["phases"]][:2] == ["setup.build",
                                                      "setup.capture"]
    assert out["phases"][0]["self_s"] == pytest.approx(2e-9)
    assert out["phases"][0]["trace_lower_s"] == 0.5
    assert sa.diagnostics(acc, 0.0, peak_before_build=30)[
        "hbm_peak_set_in"] == "before setup.build"


def test_the_benchmark_lists_the_eight_for_every_cell_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, layer, moves) in READERS.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, layer, moves, "lower"), name
        assert m["workloads"] == cells, name
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py")), name
