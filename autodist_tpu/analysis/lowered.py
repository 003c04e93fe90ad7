"""Lint the *lowered* program (jaxpr / StableHLO text).

The plan-level rules (``rules.py``) prove the Strategy well-formed; this
second pass inspects what the lowering actually emitted — via
``Runner.lowered_text()`` (StableHLO from ``jax.jit(...).lower()``) or a
jaxpr pretty-print — for hazards no plan-level rule can see:

- ``ADT405``: an all-gather materializing the FULL value of a
  model-parallel (``mp_axes``) parameter. ZeRO-partitioned storage
  all-gathers by design; model-parallel compute must consume the local
  shard, so a full-shape gather means a sharding rule failed to
  propagate and the "parallel" run pays replicated bandwidth.
- ``ADT406``: host transfers on the hot path (infeed/outfeed,
  host memory-space annotations, send/recv custom calls) — each one
  serializes the step on PCIe.
- ``ADT407``: collectives under divergent control flow
  (``stablehlo.if``/``case`` branches, jaxpr ``cond``): if the predicate
  ever differs across replicas, the collective deadlocks — the
  mis-sharded-collective hang this framework's fault harness exists to
  catch at runtime, surfaced at lint time instead.
- ``ADT408``: a host transfer inside a loop body (``stablehlo.while``,
  jaxpr ``scan``/``while``) — in the fused multi-step program
  (``Runner.lowered_text(..., fuse_steps=k)``) the loop body IS the
  microstep, so one such transfer serializes every microstep on PCIe and
  undoes exactly the k× host-round-trip saving fusion exists for.

Text-based on purpose: it works on any ``as_text()`` dump (including ones
saved from a real TPU run) without re-lowering, and it has no opinion
about which JAX version produced the text.
"""
import re
from typing import Dict, List, Optional, Sequence, Tuple

from autodist_tpu.analysis.diagnostics import (Diagnostic, sort_diagnostics,
                                               warning)

# StableHLO / MHLO / jaxpr spellings of cross-replica collectives.
COLLECTIVE_TOKENS = (
    "all_gather", "all-gather",
    "all_reduce", "all-reduce",
    "reduce_scatter", "reduce-scatter",
    "collective_permute", "collective-permute",
    "all_to_all", "all-to-all",
    "psum", "psum_scatter", "ppermute", "pgather",
)

_GATHER_TOKENS = ("all_gather", "all-gather")

# substrings marking host traffic in StableHLO dumps
_HOST_TOKENS = ("infeed", "outfeed", "send_to_host", "recv_from_host",
                "SendToHost", "RecvFromHost", "pinned_host",
                "annotate_device_placement", "host_compute")

# result tensor type, e.g. tensor<128x512xf32>
_TENSOR_RE = re.compile(r"tensor<([0-9]+(?:x[0-9]+)*)x[a-z][a-z0-9]*>")
# StableHLO/MHLO region ops delimit their bodies with BRACES; jaxpr
# pretty-prints delimit the whole statement — params AND sub-jaxprs —
# with the op's square BRACKET (``scan[ ... jaxpr={...} ... ] a b``), so
# the two families need different span tracking. A jaxpr ``while[``
# carries TWO sub-jaxprs (cond_jaxpr + body_jaxpr) and nested scans
# re-open brackets inside the span, which is why brace-only tracking
# used to lose every region after the first (one level deep).
_BRANCH_BRACE_TOKENS = ("stablehlo.if", "stablehlo.case", "mhlo.if",
                        "mhlo.case")
_BRANCH_BRACKET_TOKENS = ("cond[",)
_LOOP_BRACE_TOKENS = ("stablehlo.while", "mhlo.while")
_LOOP_BRACKET_TOKENS = ("scan[", "while[")


def _line_tensor_shapes(line: str) -> List[Tuple[int, ...]]:
    return [tuple(int(x) for x in m.group(1).split("x"))
            for m in _TENSOR_RE.finditer(line)]


def lint_lowered_text(text: str,
                      mp_full_shapes: Optional[Dict[str, Sequence[int]]] = None
                      ) -> List[Diagnostic]:
    """Scan a lowered-program dump for communication hazards.

    ``mp_full_shapes`` maps model-parallel variable names to their FULL
    (global) shapes; an all-gather whose result matches one of them is
    flagged as ADT405. Without it the all-gather check is skipped (there
    is no way to tell an accidental full gather from a legitimate one).
    """
    out: List[Diagnostic] = []
    full_shapes = {tuple(int(d) for d in shape): name
                   for name, shape in (mp_full_shapes or {}).items()}
    # StableHLO regions: depth of every open if/case (and while) region,
    # tracked by brace nesting; an opener whose braces land on a LATER
    # line is held pending (counted — two openers can be pending) until
    # its first ``{``. jaxpr statements: bracket-depth spans of every
    # open ``scan[``/``while[``/``cond[`` — the whole span (params and
    # every sub-jaxpr, however deeply nested) is the region.
    brace_depth = 0
    bracket_depth = 0
    branch_starts: List[int] = []
    loop_starts: List[int] = []
    branch_spans: List[int] = []
    loop_spans: List[int] = []
    pending_branch = 0
    pending_loop = 0
    flagged_branch = False
    seen_host: set = set()
    seen_loop_host: set = set()
    seen_gather: set = set()
    for lineno, line in enumerate(text.splitlines(), 1):
        lowered_line = line.strip()
        is_branch_open = any(tok in line for tok in _BRANCH_BRACE_TOKENS)
        is_loop_open = any(tok in line for tok in _LOOP_BRACE_TOKENS)
        has_collective = any(tok in line for tok in COLLECTIVE_TOKENS)
        if any(tok in line for tok in _BRANCH_BRACKET_TOKENS):
            branch_spans.append(bracket_depth)
        if any(tok in line for tok in _LOOP_BRACKET_TOKENS):
            loop_spans.append(bracket_depth)
        in_branch = (branch_starts or pending_branch or is_branch_open
                     or branch_spans)
        in_loop = (loop_starts or pending_loop or is_loop_open
                   or loop_spans)
        if in_branch and has_collective and not flagged_branch:
            out.append(warning(
                "ADT407",
                "collective inside a conditional branch (line %d: %s) — "
                "if the predicate ever differs across replicas this "
                "deadlocks" % (lineno, lowered_line[:80]),
                fixit="hoist the collective out of the branch or prove "
                      "the predicate replica-uniform"))
            flagged_branch = True  # one finding per program is enough signal
        if has_collective and any(tok in line for tok in _GATHER_TOKENS):
            for shape in _line_tensor_shapes(line):
                name = full_shapes.get(shape)
                if name is not None and name not in seen_gather:
                    seen_gather.add(name)
                    out.append(warning(
                        "ADT405",
                        "all-gather materializes the full value of "
                        "model-parallel variable (shape %s, line %d) — "
                        "its compute should consume the local shard"
                        % (list(shape), lineno),
                        var=name,
                        fixit="check the model's mp_rules cover every "
                              "consumer of this variable"))
        for tok in _HOST_TOKENS:
            if tok not in line:
                continue
            if in_loop:
                # inside a while/scan body the transfer repeats PER
                # ITERATION — the more specific ADT408 supersedes ADT406
                # here (docs/linting.md). In the fused multi-step program
                # the loop body IS the microstep, so this is the exact
                # per-step host round-trip fusion exists to remove.
                if tok not in seen_loop_host:
                    seen_loop_host.add(tok)
                    out.append(warning(
                        "ADT408",
                        "host transfer inside a while/scan body (%s, line "
                        "%d) — it repeats every iteration; in a fused "
                        "multi-step program that is a per-microstep PCIe "
                        "round-trip, undoing the superstep fusion"
                        % (tok, lineno),
                        fixit="hoist the transfer out of the loop; in the "
                              "fused engine, pull PS values once per "
                              "superstep (the fused carry), never per "
                              "microstep"))
            elif tok not in seen_host:
                seen_host.add(tok)
                out.append(warning(
                    "ADT406",
                    "host transfer on the hot path (%s, line %d) — each "
                    "one serializes the step on PCIe" % (tok, lineno),
                    fixit="keep the step device-resident; host-PS pulls "
                          "belong in the store, not the compiled step"))
        opens = line.count("{")
        if opens > 0:
            if is_branch_open or pending_branch:
                branch_starts.append(brace_depth)
                pending_branch = max(pending_branch - 1, 0)
            if is_loop_open or pending_loop:
                loop_starts.append(brace_depth)
                pending_loop = max(pending_loop - 1, 0)
        else:
            if is_branch_open:
                pending_branch += 1  # braces arrive on a later line
            if is_loop_open:
                pending_loop += 1
        brace_depth += opens - line.count("}")
        while branch_starts and brace_depth <= branch_starts[-1]:
            branch_starts.pop()
        while loop_starts and brace_depth <= loop_starts[-1]:
            loop_starts.pop()
        bracket_depth += line.count("[") - line.count("]")
        while branch_spans and bracket_depth <= branch_spans[-1]:
            branch_spans.pop()
        while loop_spans and bracket_depth <= loop_spans[-1]:
            loop_spans.pop()
    return sort_diagnostics(out)


def mp_full_shapes_of(distributed_step) -> Dict[str, Tuple[int, ...]]:
    """Full global shapes of the model-parallel variables of a compiled
    ``DistributedStep`` — the ``mp_full_shapes`` input of
    :func:`lint_lowered_text`."""
    infos = distributed_step.model_item.var_infos
    out: Dict[str, Tuple[int, ...]] = {}
    for name, layout in distributed_step.layouts.items():
        if getattr(layout, "mp_axes", ()):
            info_ = infos.get(name)
            if info_ is not None:
                out[name] = tuple(info_.shape)
    return out


def lint_runner(runner, batch, state=None,
                fuse_steps: int = 1) -> List[Diagnostic]:
    """Lower the runner's step for ``batch`` and lint the StableHLO.

    The single implementation behind ``Runner.lint_lowered`` — keep the
    two entry points from drifting. ``fuse_steps=k > 1`` lints the fused
    k-microstep scan program instead: its scan body is the microstep, so
    ADT408 findings there mean a per-microstep host round-trip survived
    the fusion. The ADT60x numerics dtype-flow pass
    (``analysis/numerics.py``) rides the same lowered text."""
    from autodist_tpu.analysis import numerics
    text = runner.lowered_text(batch, state, fuse_steps=fuse_steps)
    out = lint_lowered_text(text, mp_full_shapes_of(runner.distributed_step))
    out.extend(numerics.lint_text(text))
    return sort_diagnostics(out)
