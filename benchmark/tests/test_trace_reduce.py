"""The trace reduction on recorded tables: a hand-made one whose numbers
can be checked by eye, and one recorded on the v5e. Run by hand:

    python3 -m pytest benchmark/tests -q
"""
import json
import os

import pytest

from benchmark.trace import reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def table(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_interval_algebra():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3
    assert tr.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_idle_and_gaps_handmade():
    t = table("handmade_trace.json")
    p0, p1 = tr.device_planes(t)
    window = (0, 1000)
    assert tr.busy_ns(p0, window) == 700
    assert tr.busy_ns(p1, window) == 500
    assert tr.idle_gaps(p0, window) == [(700, 1000)]
    # a window cut inside an op counts only the part inside
    assert tr.busy_ns(p0, (50, 150)) == 100


def test_collective_exposure_handmade_overlap():
    p0, p1 = tr.device_planes(table("handmade_trace.json"))
    assert tr.collective_ns(p0, (0, 1000)) == (200, 100)
    assert tr.collective_ns(p1, (0, 1000)) == (400, 210)  # asynchronous


def test_self_times_do_not_count_a_loop_twice():
    t = table("handmade_trace.json")
    p0 = tr.device_planes(t)[0]
    own = tr.self_times(tr.line_events(p0, tr.OPS_LINE), (0, 1000))
    assert own["while.1"] == 50            # 300 less its body's 250
    assert own["fusion.3"] == 250
    assert own["fusion.1 [jit(sharded)/dot_general]"] == 100
    top = tr.top_ops(t, (0, 1000), 2)
    assert top[0] == ["fusion.3", pytest.approx(250 / 2 / 1e9)]
    assert top[1][0].startswith("fusion.1") and top[1][1] == pytest.approx(
        (100 + 100) / 2 / 1e9)


def test_gap_attribution_and_alignment():
    t = table("handmade_trace.json")
    assert tr.align_offset_ns(t, 10) == 30
    spans = [("runner.fit", 0, 1000), ("runner.readback", 690, 950),
             ("runner.feed", 960, 990)]
    gaps = [(700, 1000), (300, 310)]
    named = tr.attribute_gaps(gaps, spans, 5)
    # [700, 1000]: readback holds 250, feed 30, fit's own time 20
    assert named[0] == ["runner.readback 83%", pytest.approx(300e-9)]
    assert named[1][0] == "runner.fit 100%"
    assert tr.attribute_gaps([(2000, 2100)], spans, 1)[0][0] == "no span 100%"
    half = tr.attribute_gaps([(940, 980)], spans, 1)[0][0]
    assert half == "runner.feed 50% + runner.readback 25% + runner.fit 25%"


def test_module_runs():
    p0 = tr.device_planes(table("handmade_trace.json"))[0]
    assert tr.module_runs(p0, (0, 1000), "jit_sharded") == [(0, 700)]
    assert tr.module_runs(p0, (100, 1000), "jit_sharded") == []


def test_recorded_v5e_trace_reduces_to_its_recorded_numbers():
    """One step of lm1b_train_4chip_ar as the v5e's profiler wrote it (op
    names as the TPU writes them, -start/-done pairs, the async line)."""
    import gzip
    with gzip.open(os.path.join(DATA, "v5e_recorded_trace.json.gz"), "rt") as f:
        t = json.load(f)
    want = t["expected"]
    planes = tr.device_planes(t)
    assert len(planes) == want["chips"]
    window = tuple(want["window"])
    busy = [tr.busy_ns(p, window) for p in planes]
    assert sum(busy) / len(busy) == pytest.approx(want["busy_ns_mean"])
    # the ops of one step cover its module's run, and nothing else runs
    assert busy[0] == pytest.approx(want["module_ns"], rel=2e-3)
    assert len(tr.module_runs(planes[0], (window[0], window[1] + 1),
                              "jit_")) == 1
    coll = [tr.collective_ns(p, window) for p in planes]
    assert sum(c[0] for c in coll) / len(coll) == pytest.approx(
        want["coll_ns_mean"])
    assert sum(c[1] for c in coll) / len(coll) == pytest.approx(
        want["coll_exposed_ns_mean"])
    assert all(0 <= c[1] <= c[0] for c in coll)
    assert [n for n, _ in tr.top_ops(t, window, 3)] == want["top3_ops"]
