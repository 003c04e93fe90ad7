"""The phase rule and the readers that use it, on a small synthetic table
and on the step recorded on the v5e with the scope map of its program
(``tools/make_scope_fixture.py``). Run by hand:

    python3 -m pytest benchmark/tests -q
"""
import gzip
import importlib
import json
import os
import types

import pytest

from benchmark import phases
from benchmark.tools import make_scope_fixture
from benchmark.trace import reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
PRE = "jit(local_step)/shard_map/"
READERS = ("fwd_ms_per_step", "bwd_ms_per_step", "head_ms_per_step",
           "opt_ms_per_step", "sync_ms_per_step", "unscoped_pct",
           "host_ms_per_step")


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name).read


@pytest.mark.parametrize("op_name, want", [
    (PRE + "jvp(loss)/TransformerLM.hidden/blocks/layer_0/dot_general",
     ("fwd", False, False)),
    (PRE + "transpose(jvp(loss))/TransformerLM.hidden/blocks/mul",
     ("bwd", False, False)),
    (PRE + "jvp(loss)/lean_head/while/body/dot_general", ("fwd", True, False)),
    (PRE + "transpose(jvp(loss))/lean_head_bwd/while/body/exp",
     ("bwd", True, False)),
    # a recomputed forward op runs in the backward pass
    (PRE + "transpose(jvp(loss))/checkpoint/rematted_computation/blocks/add",
     ("bwd", False, True)),
    (PRE + "jvp(loss)/checkpoint/rematted_computation/blocks/add",
     ("bwd", False, True)),
    # as jax 0.9 writes a backward op under jax.checkpoint
    (PRE + "transpose(jvp(loss))/jvp(loss)/checkpoint/rematted_computation/"
     "TransformerLM.hidden/blocks/layer_0/dot_general", ("bwd", False, True)),
    (PRE + "transpose(jvp(loss))/jvp(loss)/checkpoint/lean_head_bwd/while",
     ("bwd", True, False)),
    (PRE + "loss/TransformerLM.hidden/embed/take", ("fwd", False, False)),
    (PRE + "optimizer/mul", ("opt", False, False)),
    (PRE + "grad_sync/psum", ("sync", False, False)),
    ("grad_sync/psum", ("sync", False, False)),
    (PRE + "params/all_gather", ("params", False, False)),
    (PRE + "sentinel/reduce_sum", ("sentinel", False, False)),
    # a user scope that merely contains a table name is not that scope
    (PRE + "my_loss_helper/optimizers/add", (None, False, False)),
    (PRE + "broadcast.7", (None, False, False)),
    ("", (None, False, False)),
])
def test_tag(op_name, want):
    assert phases.tag(op_name) == want


def test_classify_fusions_by_their_own_name_then_by_majority():
    fwd, bwd, opt = (PRE + "jvp(loss)/a", PRE + "transpose(jvp(loss))/b",
                     PRE + "optimizer/c")
    bare = PRE + "broadcast.1"
    # a plain instruction: its own string
    assert phases.classify([bwd]) == (("bwd", False, False), False)
    assert phases.classify([]) == ((None, False, False), False)
    assert phases.classify([""]) == ((None, False, False), False)
    # a loop fusion named after its root: the last backward op fused into
    # the optimizer's update
    assert phases.classify([opt, bwd, opt, opt, opt, bare]) == \
        (("opt", False, False), False)
    # an output fusion named after its hero: a weight-gradient matmul with
    # that weight's update behind it is backward time, and flagged as what
    # a count of members would have called otherwise
    assert phases.classify([bwd, fwd, bwd] + [opt] * 15) == \
        (("bwd", False, False), True)
    assert phases.classify([bwd, fwd, bwd])[1] is False
    assert phases.classify([bwd, fwd, bwd, fwd, bwd])[1] is False  # a tie
    # the fusion itself has no scope: its members' majority decides;
    # compiler-made members do not vote
    assert phases.classify(["", fwd, fwd, fwd, bwd, bwd, bare, bare]) == \
        (("fwd", False, False), False)
    assert phases.classify([bare, opt, bare, bare]) == \
        (("opt", False, False), False)
    assert phases.classify(["", bare]) == ((None, False, False), False)


def synthetic():
    """Two runs of the step on one chip: a loop whose body is backward
    head work, a forward op, an update, an unscoped copy, and an op of
    ANOTHER module between the runs."""
    def run(t0):
        return [["fusion.1", t0, 100, {}], ["while.2", t0 + 100, 300, {}],
                ["fusion.3", t0 + 120, 250, {}],   # the loop's body
                ["fusion.4", t0 + 400, 50, {}], ["copy.5", t0 + 450, 30, {}],
                ["all-reduce.6", t0 + 480, 20, {}]]
    table = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": [
            ["jit_local_step(1)", 0, 500, {}],
            ["jit_other(2)", 500, 100, {}],
            ["jit_local_step(1)", 1000, 500, {}]]},
        {"name": tr.OPS_LINE,
         "events": run(0) + [["fusion.9", 500, 100, {}]] + run(1000)}]}]}
    scope_map = {
        "fusion.1": [PRE + "jvp(loss)/blocks/dot_general"],
        "while.2": [PRE + "transpose(jvp(loss))/lean_head_bwd/while"],
        "fusion.3": ["", PRE + "transpose(jvp(loss))/lean_head_bwd/while/"
                     "body/dot_general"],
        "fusion.4": [PRE + "optimizer/add", PRE + "optimizer/mul",
                     PRE + "transpose(jvp(loss))/blocks/mul",
                     PRE + "transpose(jvp(loss))/blocks/mul"],
        "copy.5": [],
        "all-reduce.6": ["grad_sync/psum"],
        "fusion.9": [PRE + "jvp(loss)/blocks/dot_general"]}
    return table, scope_map


def test_sum_phases_synthetic():
    table, scope_map = synthetic()
    got = phases.sum_phases(table, (0, 1500), scope_map)
    ns = {k: round(v * 1e6) for k, v in got.items() if k != "runs"}
    assert got["runs"] == 2
    # self time: the loop keeps 300 - 250; the other module's op is out
    assert ns == {"total": 500, "fwd": 100, "bwd": 300, "opt": 50,
                  "sync": 20, "other": 0, "unscoped": 30, "head": 300,
                  "remat": 0, "weak": 50, "unmapped": 0}
    # a window that cuts the second run keeps the first alone
    assert phases.sum_phases(table, (0, 1400), scope_map)["runs"] == 1
    assert phases.sum_phases(table, (100, 900), scope_map) is None
    # a map that does not know an instruction says so
    del scope_map["fusion.4"]
    got = phases.sum_phases(table, (0, 1500), scope_map)
    assert round(got["unmapped"] * 1e6) == 50
    assert round(got["unscoped"] * 1e6) == 80


def recorded():
    with gzip.open(os.path.join(DATA, "v5e_recorded_trace.json.gz"),
                   "rt") as f:
        table = json.load(f)
    scope_map, module, expected = make_scope_fixture.load(
        os.path.join(DATA, "v5e_recorded_scope_map.json.gz"))
    return table, scope_map, module, expected


def test_recorded_step_with_its_map():
    table, scope_map, module, expected = recorded()
    window = tuple(table["expected"]["window"])
    got = phases.sum_phases(table, window, scope_map, module)
    assert got == pytest.approx(expected)
    busy = table["expected"]["busy_ns_mean"] / 1e6
    # the four phases tile the step's busy time to 5 %, nothing is
    # unknown to the map, little has no scope
    assert got["total"] == pytest.approx(busy, rel=1e-3)
    parts = got["fwd"] + got["bwd"] + got["opt"] + got["sync"]
    assert 0.95 * busy <= parts <= busy
    assert got["unmapped"] == 0 and got["unscoped"] < 0.05 * got["total"]
    # grad_sync holds every collective and the packing around them
    assert got["sync"] >= table["expected"]["coll_ns_mean"] / 1e6
    assert 80 < got["head"] < 110 and got["head"] < got["fwd"] + got["bwd"]
    assert 1.5 < got["bwd"] / got["fwd"] < 2.5 and 8 < got["opt"] < 16


class FakeTracer:
    def __init__(self, table, window):
        self.table, self.window_ns, self.offset_ns = table, window, 0

    def window_on_trace_clock(self):
        return self.window_ns


def test_readers_on_the_recorded_step(monkeypatch):
    table, scope_map, module, expected = recorded()
    asked = []
    monkeypatch.setattr(phases, "program_map",
                        lambda name: asked.append(name) or scope_map)
    rec = {"kind": "train_fit", "chips": 4, "traced_steps": 1,
           "tracer": FakeTracer(table, tuple(table["expected"]["window"]))}
    ctx = types.SimpleNamespace()
    values = {name: reader(name)(rec, ctx) for name in READERS[:6]}
    assert asked == [module]  # one map for all six
    assert values["fwd_ms_per_step"] == pytest.approx(expected["fwd"])
    assert values["bwd_ms_per_step"] == pytest.approx(expected["bwd"])
    assert values["head_ms_per_step"] == pytest.approx(expected["head"])
    assert values["opt_ms_per_step"] == pytest.approx(expected["opt"])
    assert values["sync_ms_per_step"] == pytest.approx(expected["sync"])
    assert values["unscoped_pct"] == pytest.approx(
        100 * expected["unscoped"] / expected["total"])
    assert rec["phase_ms_per_step"]["weak"] == pytest.approx(
        expected["weak"])  # the diagnostics carry the whole sum
    assert reader("sync_ms_per_step")(dict(rec, chips=1), ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_without_a_trace_or_a_map(name, monkeypatch):
    ctx = types.SimpleNamespace()
    # untraced run, CPU rehearsal: nothing to read, and no map is asked for
    monkeypatch.setattr(phases, "program_map", lambda name: 1 / 0)
    assert reader(name)({"kind": "train_fit", "tracer": None}, ctx) is None
    assert reader(name)({"kind": "train_fit", "chips": 4, "spans": [],
                         "tracer": FakeTracer(None, None)}, ctx) is None
    # a program from before the scopes: traced, but it gives no map and
    # has no runner.wait_device span
    table = recorded()[0]
    monkeypatch.setattr(phases, "program_map", lambda name: None)
    rec = {"kind": "train_fit", "chips": 4, "traced_steps": 1,
           "spans": [("runner.readback", 0, 100, {})],
           "tracer": FakeTracer(table, tuple(table["expected"]["window"]))}
    assert reader(name)(rec, ctx) is None


def test_program_map_of_a_program_without_the_registry(monkeypatch):
    from autodist_tpu import telemetry
    monkeypatch.delattr(telemetry, "scope_map")
    assert phases.program_map(phases.STEP_MODULE) is None


def test_host_time_is_self_time_without_the_device_wait():
    # one step of the per-step loop, in ns; the PS thread's span and a
    # span outside the window do not count
    spans = [("runner.fit", 0, 10_000, {}),
             ("runner.next_batch", 100, 400, {"step": 7}),
             ("prefetch.place", 150, 350, {}),
             ("runner.dispatch", 500, 9_000, {"step": 7}),
             ("runner.feed", 600, 700, {"step": 7}),
             ("dstep.dispatch", 800, 1_800, {"step": 7}),
             ("runner.control", 1_900, 2_000, {"step": 7}),
             ("runner.readback", 2_100, 8_900, {"step": 7}),
             ("runner.wait_device", 2_150, 8_000, {"step": 7}),
             ("runner.fetch", 8_050, 8_850, {"step": 7}),
             ("runner.callbacks", 9_100, 9_300, {"step": 7}),
             ("ps.apply", 3_000, 6_000, {}),
             ("runner.dispatch", 20_000, 30_000, {"step": 8})]
    own = phases.host_self_ms(spans, (0, 10_000))
    ns = {k: round(v * 1e6) for k, v in own.items()}
    assert ns == {"runner.fit": 1000, "runner.next_batch": 100,
                  "prefetch.place": 200, "runner.dispatch": 500,
                  "runner.feed": 100, "dstep.dispatch": 1000,
                  "runner.control": 100, "runner.readback": 150,
                  "runner.fetch": 800, "runner.callbacks": 200}
    rec = {"spans": spans, "traced_steps": 1,
           "tracer": FakeTracer(None, (0, 10_000))}
    ctx = types.SimpleNamespace()
    assert reader("host_ms_per_step")(rec, ctx) == pytest.approx(4150e-6)
    assert rec["host_self_ms_per_step"]["runner.fetch"] == \
        pytest.approx(800e-6)
    # a window inside the fit clips every span
    assert round(sum(phases.host_self_ms(
        spans, (2_000, 9_000)).values()) * 1e6) == 7000 - 5850
