"""Device time per phase of the compiled train step, and the host's own
time per step: the rule and the sums the phase readers share
(``layer_metrics/fwd_ms_per_step.py`` and its six siblings).

A device event carries the name of its HLO instruction and nothing else.
The program says which scope each instruction was traced under:
``autodist_tpu.telemetry.scope_map(<XLA module name>)`` gives, per
instruction, the raw ``op_name`` strings of the compiled module (a
fusion: its own first, then its fused instructions'). The RULE that
turns those strings into a phase lives here, in the benchmark, so that a
later change of the program cannot move a metric by reclassifying.

The rule. An ``op_name`` is a path (``jit(local_step)/shard_map/
transpose(jvp(loss))/lean_head_bwd/while/body/dot_general``); its phase
is decided by the first component whose innermost name is one of the
program's step scopes:

- ``loss``: ``bwd`` where the component is wrapped in ``transpose(``
  (JAX writes ``transpose(jvp(loss))`` on backward ops) or the path holds
  ``rematted_computation`` (recomputed forward ops run in the backward
  pass; counted apart as ``remat``), else ``fwd``;
- ``optimizer`` -> ``opt``; ``grad_sync`` -> ``sync``; ``params`` and
  ``sentinel`` keep their names (no metric of their own: they are small,
  and summed into ``other`` by the readers' diagnostics).

``head`` is a cross-cut: any component ``lean_head`` or ``lean_head_bwd``.
A FUSION is named by its OWN ``op_name``: XLA gives an output fusion the
metadata of its hero (the convolution) and a loop fusion that of its
root. Only where that has no phase does the MAJORITY, by count, of its
fused instructions' tags decide (compiler-made members carry no scope
and do not vote). Why not the majority first: on one chip XLA fuses each
weight-gradient matmul with the Adam update of that weight, one
convolution with some twenty elementwise ops, and five sixths of such a
fusion's time is the matmul's; by count it read as optimizer time (34
ms a step against 11, PR 23). The diagnostics carry ``weak``, the time in
fusions whose members' majority differs from the tag chosen: what the
other rule would have moved.

Device time is SELF time (``reduce.self_times``: a loop is not counted
with its body) of the ops inside whole runs of the step's module in the
traced window, per run, mean over chips.
"""
import re

from benchmark.readers import traced
from benchmark.trace import reduce as tr

STEP_MODULE = "jit_local_step"
SCOPE_PHASE = {"loss": None, "optimizer": "opt", "grad_sync": "sync",
               "params": "params", "sentinel": "sentinel"}
HEAD_SCOPES = ("lean_head", "lean_head_bwd")
REMAT = "rematted_computation"
_WRAPPERS = re.compile(r"^(?:[\w.\-]+\()+")


def tag(op_name):
    """(phase, head, remat) of one raw ``op_name``; phase None where no
    step scope is on the path."""
    parts = op_name.split("/")
    phase = None
    for part in parts:
        inner = _WRAPPERS.sub("", part).rstrip(")")
        if inner not in SCOPE_PHASE:
            continue
        phase = SCOPE_PHASE[inner]
        if phase is None:  # the loss
            phase = ("bwd" if "transpose(" in part or REMAT in parts
                     else "fwd")
        break
    if phase is None:
        return None, False, False
    return (phase, any(p in HEAD_SCOPES for p in parts),
            phase == "bwd" and REMAT in parts)


def classify(op_names):
    """((phase, head, remat), weak) of one instruction from the strings
    the program's map gives for it: its own tag, or for a fusion whose
    own ``op_name`` has no phase the majority of its members'; weak = the
    members' majority is another tag than the one chosen."""
    if not op_names:
        return (None, False, False), False
    own = tag(op_names[0])
    votes = {}
    for name in op_names[1:]:
        t = tag(name)
        if t[0] is not None:
            votes[t] = votes.get(t, 0) + 1
    if not votes:
        return own, False
    top = max(votes.values())
    majority = sorted(t for t, n in votes.items() if n == top)
    if own[0] is None:
        return majority[0], False
    return own, own not in majority


def program_map(module_name):
    """The program's instruction -> op_names map, or None where this
    program has none (a checkout from before the scopes, the CPU
    rehearsal's tiny model is fine)."""
    try:
        from autodist_tpu import telemetry
    except ImportError:
        return None
    scope_map = getattr(telemetry, "scope_map", None)
    return scope_map(module_name) if scope_map is not None else None


def sum_phases(table, window, scope_map, module=STEP_MODULE):
    """{"runs", "total", "fwd", "bwd", "opt", "sync", "other", "unscoped",
    "head", "remat", "weak", "unmapped"}: ms per run of ``module`` (but
    "runs"), mean over chips; None where no whole run lies in the window.
    "other" is params + sentinel; "unmapped" the part of "unscoped" whose
    instruction the map does not know at all (a stale map shows here)."""
    keys = ("total", "fwd", "bwd", "opt", "sync", "other", "unscoped",
            "head", "remat", "weak", "unmapped")
    per_plane = []
    for plane in tr.device_planes(table):
        runs = tr.module_runs(plane, window, module)
        if not runs:
            continue
        inside = [e for e in tr.line_events(plane, tr.OPS_LINE)
                  if any(a <= e[1] and e[1] + e[2] <= b for a, b in runs)]
        acc = dict.fromkeys(keys, 0.0)
        for label, ns in tr.self_times(inside, window).items():
            name = label.split(" [", 1)[0]  # self_times appends the shape
            (phase, head, remat), weak = classify(scope_map.get(name) or ())
            acc["total"] += ns
            if phase is None:
                acc["unscoped"] += ns
                acc["unmapped"] += ns * (name not in scope_map)
            else:
                acc[phase if phase in acc else "other"] += ns
            acc["head"] += ns * head
            acc["remat"] += ns * remat
            acc["weak"] += ns * weak
        per_plane.append((len(runs), acc))
    if not per_plane:
        return None
    out = {k: sum(acc[k] / n for n, acc in per_plane) / len(per_plane) / 1e6
           for k in keys}
    out["runs"] = sum(n for n, _ in per_plane) / len(per_plane)
    return out


def step_phases(rec):
    """``sum_phases`` of a traced training run, computed once per record
    and left in the run's diagnostics as ``phase_ms_per_step``. None
    where there is no device trace or the program gives no map."""
    if "phase_ms_per_step" in rec:
        return rec["phase_ms_per_step"]
    got = traced(rec)
    out = None
    if got is not None and rec.get("kind") == "train_fit":
        scope_map = program_map(STEP_MODULE)
        if scope_map is not None:
            out = sum_phases(got[0], got[1], scope_map)
    rec["phase_ms_per_step"] = out
    return out


def phase_ms(rec, key):
    phases = step_phases(rec)
    return None if phases is None else phases[key]


# ------------------------------------------------------------- host side

HOST_PREFIXES = ("runner.", "dstep.", "prefetch.")
DEVICE_WAIT = "runner.wait_device"


def host_self_ms(spans, window_ns):
    """{span name: self ms} of the fit loop's own spans inside
    ``window_ns``: ``runner.*``, ``dstep.*``, ``prefetch.*``, each less
    its children among them, the wait for the device left out. The spans
    of one thread nest, so the nesting is read from the intervals."""
    events = [[name, s, e - s, {}] for name, s, e, _ in spans
              if name.startswith(HOST_PREFIXES)]
    own = tr.self_times(events, window_ns)
    return {name: ns / 1e6 for name, ns in own.items() if name != DEVICE_WAIT}
