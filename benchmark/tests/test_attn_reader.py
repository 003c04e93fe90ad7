"""``attn_ms_per_step`` (PR 28) on the recorded v5e step, and on a run
with nothing to read."""
import types

import pytest

from benchmark import phases
from benchmark.layer_metrics import attn_ms_per_step
from benchmark.tests.test_phases import FakeTracer, recorded


def test_attention_on_the_recorded_step(monkeypatch):
    """lm1b's four-chip step: eight blocks' attention is a part of the
    blocks' forward + backward, beside the head, and well over nothing."""
    table, scope_map, module, expected = recorded()
    monkeypatch.setattr(phases, "program_map", lambda name: scope_map)
    rec = {"kind": "train_fit", "chips": 4, "traced_steps": 1,
           "tracer": FakeTracer(table, tuple(table["expected"]["window"]))}
    got = attn_ms_per_step.read(rec, types.SimpleNamespace())
    blocks = expected["fwd"] + expected["bwd"] - expected["head"]
    assert 0.2 * blocks < got < 0.6 * blocks
    assert rec["scope_ms_per_step"]["attention"] == pytest.approx(got)


def test_nothing_to_read_gives_none(monkeypatch):
    ctx = types.SimpleNamespace()
    # no device trace (an untraced run, the CPU rehearsal)
    rec = {"kind": "train_fit", "tracer": None}
    assert attn_ms_per_step.read(rec, ctx) is None
    # a program without the scope: the metric is left out, nothing raises
    table, scope_map, _, _ = recorded()
    bare = {name: [s for s in strings if "attention" not in s.split("/")]
            for name, strings in scope_map.items()}
    monkeypatch.setattr(phases, "program_map", lambda name: bare)
    rec = {"kind": "train_fit", "chips": 4, "traced_steps": 1,
           "tracer": FakeTracer(table, tuple(table["expected"]["window"]))}
    assert attn_ms_per_step.read(rec, ctx) is None
