"""BENCHMARK.json against the contract's rules that can be checked here,
and against the files the harness finds by name."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def exists(*parts):
    return os.path.exists(os.path.join(ROOT, *parts))


def test_keys_names_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
        names.append(w["name"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    metric_names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len(json.dumps(b)) < 64 * 1024


def test_cells_metrics_and_files_fit_together():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    configs = {c["name"]: c for c in b["configs"]}
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == \
        len(cells)
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for w in b["workloads"]:
        assert exists(configs[w["config"]]["file"])
        assert exists("benchmark", "traffic", w["traffic"] + ".json")
        assert exists("benchmark", "workloads", w["name"] + ".json")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]

    def where(m):
        return set(m.get("workloads", cells))

    for m in b["end_to_end"]:
        assert where(m) <= set(cells)
        assert exists("benchmark", "end_to_end", m["name"] + ".py")
    for m in b["per_layer"]:
        assert exists("benchmark", "layer_metrics", m["name"] + ".py")
        # reported only where the metric it moves is
        assert where(m) <= where(e2e[m["moves"]]), m["name"]
    for cell in cells:
        assert any(cell in where(m) for m in b["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(cell in where(m) for m in b["per_layer"])
    layers = {}
    for m in b["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_config_files_state_their_cut():
    for c in bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert "assumed" in cfg and "deployment" in cfg and "family" in cfg
        assert exists("benchmark", "families", cfg["family"] + ".py")
        assert exists("benchmark", "reference", cfg["family"] + ".py")


def test_file_names_use_the_contracts_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, "benchmark")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert ok.match(rel), rel
