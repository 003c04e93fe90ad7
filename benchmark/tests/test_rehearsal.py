"""The cells end to end on the CPU at a tiny size; a new configuration,
traffic mix, cell and per-layer metric found by name in a temporary copy
with no edit to a file that was there; and a serving cell added the same
way, from data files alone, over the serving driver and readers that no
cell of ``BENCHMARK.json`` uses yet."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY_TRAIN = ["--traffic-set", "batch_per_chip=8", "--traffic-set", "seq=16"]
CELLS = {
    "lm1b_train_1chip": ("lm_tiny.json", TINY_TRAIN),
    "lm1b_train_4chip_ar": ("lm_tiny.json", TINY_TRAIN),
}
TINY_DECODE = {
    "slots": 4, "prefill_len": 16, "max_new_tokens": 24, "rate_rps": 10,
    "ramp_s": 1, "grace_s": 5, "trace_seconds": 1, "check_requests": 4,
    "prompt_len": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                   "lo": 2, "hi": 16},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                   "lo": 2, "hi": 24}}
SERVING_METRICS = [  # (reader under layer_metrics/, unit, source, layer)
    ("decode_step_ms_p50", "ms", "program_counter", "serving"),
    ("admit_stall_ms_p50", "ms", "program_span", "serving"),
    ("decode_ms_per_tok_p50", "ms/token", "host_clock", "serving"),
    ("decode_ms_per_tok_p90", "ms/token", "host_clock", "serving"),
    ("slot_occupancy_pct", "%", "program_counter", "serving"),
    ("gen_lag_ms_p95", "ms", "host_clock", "benchmark generator"),
    ("decode_roofline_pct", "%", "device_trace", "model ops")]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(root, cell, trace, extra, seconds=3):
    """The harness on the CPU backend: its rehearsal line, parsed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "3",
         "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse-on-cpu"] + extra,
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", "a rehearsal prints no result line"
    line = [l for l in proc.stderr.splitlines()
            if l.startswith("benchmark rehearsal: ")][-1]
    return json.loads(line[len("benchmark rehearsal: "):])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearsal(cell, trace):
    config, extra = CELLS[cell]
    result = rehearse(ROOT, cell, trace, extra + [
        "--config-file", "benchmark/tests/configs/" + config])
    assert set(result) == KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = bench["per_layer" if trace else "end_to_end"]
    allowed = {m["name"] for m in listed
               if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) <= allowed
    if not trace:
        assert set(result["metrics"]) == allowed  # host-clock metrics all
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}, name


@pytest.mark.parametrize("override", [
    'fit={"fuse_steps": 2}',   # another program: not this driver's to warm
    "trace_from_step=12"])     # the profiler would start inside a group
def test_the_training_driver_refuses_what_it_cannot_measure(override):
    config, extra = CELLS["lm1b_train_1chip"]
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "lm1b_train_1chip",
         "--seed", "3", "--seconds", "1", "--trace", "1", "--rehearse-on-cpu",
         "--config-file", "benchmark/tests/configs/" + config,
         "--traffic-set", override] + extra,
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "metrics_every" in proc.stderr


def test_a_real_run_without_a_tpu_fails_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "lm1b_train_1chip",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_alone_in_a_directory_it_fails_and_prints_nothing(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "lm1b_train_1chip",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_new_files_are_found_by_name_without_editing_any(tmp_path):
    copy_with_program(tmp_path)
    before = {p: os.path.getmtime(p) for p in
              (os.path.join(d, f) for d, _, fs in os.walk(tmp_path / "benchmark")
               for f in fs)}
    b = tmp_path / "benchmark"
    shutil.copy(b / "tests" / "configs" / "lm_tiny.json",
                b / "configs" / "lm_new.json")
    (b / "traffic" / "train_new.json").write_text(json.dumps(
        {"kind": "train_fit", "batch_per_chip": 4, "seq": 8, "pool": 4,
         "warm_steps": 1, "trace_from_step": 4, "trace_steps": 2}))
    (b / "workloads" / "lm_new_cell.json").write_text(json.dumps(
        {"strategy": "AllReduce", "loss_rtol": 0.02}))
    (b / "layer_metrics" / "steps_done.py").write_text(
        "def read(rec, ctx):\n    return rec['steps']\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "lm_new", "source": "test",
                             "file": "benchmark/configs/lm_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "lm_new_cell", "config": "lm_new",
                               "traffic": "train_new", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tok_s":
            m["workloads"].append("lm_new_cell")
    bench["per_layer"].append({"name": "steps_done", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "run loop", "moves": "train_tok_s",
                               "workloads": ["lm_new_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result = rehearse(str(tmp_path), "lm_new_cell", 1, [], seconds=2)
    assert result["correct"] and result["metrics"]["steps_done"]["value"] > 0
    assert all(os.path.getmtime(p) == t for p, t in before.items())


def copy_with_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "autodist_tpu"), tmp_path / "autodist_tpu")
    return tmp_path / "benchmark"


def add_serving_cell(tmp_path, grace):
    """What a later PR does to bring a serving cell: a traffic file, a cell
    file, entries. No code."""
    b = copy_with_program(tmp_path)
    shutil.copy(b / "tests" / "configs" / "lm_tiny.json",
                b / "configs" / "lm_new.json")
    mix = json.loads((b / "traffic" / "decode_poisson_mixed.json").read_text())
    mix.update(TINY_DECODE, grace_s=grace)
    (b / "traffic" / "decode_new.json").write_text(json.dumps(mix))
    (b / "workloads" / "lm_new_decode.json").write_text(json.dumps(
        {"strategy": "AllReduce", "logit_margin": 1e-3}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "lm_new", "source": "test",
                             "file": "benchmark/configs/lm_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "lm_new_decode", "config": "lm_new",
                               "traffic": "decode_new", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "decode_tok_s", "unit": "tokens/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["lm_new_decode"]})
    for name, unit, source, layer in SERVING_METRICS:
        bench["per_layer"].append({"name": name, "unit": unit,
                                   "better": "lower", "source": source,
                                   "layer": layer, "moves": "decode_tok_s",
                                   "workloads": ["lm_new_decode"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_serving_cell_comes_as_data_files_only(tmp_path):
    add_serving_cell(tmp_path, grace=5)
    result = rehearse(str(tmp_path), "lm_new_decode", 0, [], seconds=3)
    assert set(result) == KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 30  # due in the window; the ramp's 10 not
    assert set(result["metrics"]) == {"decode_tok_s", "setup_s"}
    # everything due completed, so the window's completions are about the
    # offered tokens of 3 of the 4 s of load (8-token median answers)
    assert result["metrics"]["decode_tok_s"]["value"] > 0
    traced = rehearse(str(tmp_path), "lm_new_decode", 1, [], seconds=3)
    assert traced["correct"] is True
    host_side = {"decode_step_ms_p50", "admit_stall_ms_p50",
                 "decode_ms_per_tok_p50", "decode_ms_per_tok_p90",
                 "slot_occupancy_pct", "gen_lag_ms_p95"}
    assert host_side <= set(traced["metrics"])  # roofline needs a device


def test_above_capacity_unfinished_requests_are_not_failures(tmp_path):
    add_serving_cell(tmp_path, grace=None)
    diag = tmp_path / "diag.json"
    # 500 requests/s for 2 s on 4 slots: more than the tiny engine serves
    result = rehearse(str(tmp_path), "lm_new_decode", 0, [
        "--traffic-set", "rate_rps=500", "--traffic-set", "ramp_s=0",
        "--diag", str(diag)], seconds=2)
    rec = json.loads(diag.read_text())
    assert rec["unfinished_at_end"] > 0
    assert result["attempted"] == 1000 and result["failed"] == 0
    assert result["correct"] is True
    # only what completed inside the window counts: less than was offered
    w1 = rec["w0"] + 2.0
    inside = sum(r["cap"] for r in rec["requests"]
                 if r["ok"] and r["done"] <= w1)
    offered = sum(r["cap"] for r in rec["requests"])
    assert result["metrics"]["decode_tok_s"]["value"] == \
        pytest.approx(inside / 2.0)
    assert 0 < inside < offered
