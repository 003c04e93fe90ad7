"""Counters the DEVICE fills: scalars computed inside the compiled train
step that leave it beside the loss and are added to the telemetry
counters when the Runner reads a step's metrics back.

Host counters (:func:`~autodist_tpu.telemetry.spans.counter_add`) count
what the host did. Some quantities exist only on the device and differ
every step: how many routed pairs the fullest expert got. A loss that
counts such things DECLARES their names (``loss_fn.device_counters =
("moe.routed_pairs", ...)``, as ``models/lm.py:make_train_setup`` does
for a routed config) and calls :func:`add` for each while it is traced.
The lowering (``kernel/graph_transformer.py``) reads the declaration,
which costs a loss that counts nothing nothing, traces the loss under
:func:`collect`, returns what was added as extra outputs of the
differentiated function (the route the sparse-ids capture of
``ops/embedding.py`` takes), reduces them over the mesh (integers by
``pmax``, floats by ``pmean``: the rule of a loss's own ``aux``) and hands
them out as ``metrics["counters"]``. No host callback runs inside the
step. With tracing on, ``MetricsHandle.result`` adds each to the counter
of its name.

Outside :func:`collect` (eval, serving, a plain ``jax.grad`` of the loss,
a loss that declared nothing) :func:`add` does nothing. The value must be
a tracer of the loss's own trace, so :func:`add` is called from the loss
function itself and not from a layer: a layer under ``nn.remat`` or
``nn.scan`` hands its scalars up through a flax collection
(``models/layers.py:MoEFeedForward`` sows ``counters``).
"""
import contextlib
import threading
from typing import Sequence

_TLS = threading.local()


def add(name: str, value) -> None:
    """Add a traced scalar to the step's device counter ``name`` (several
    calls under one name sum)."""
    got = getattr(_TLS, "collecting", None)
    if got is not None:
        got[name] = got[name] + value if name in got else value


@contextlib.contextmanager
def collect(names: Sequence[str]):
    """Collect what the code traced inside adds: yields {name: scalar},
    and holds the loss to the ``names`` it declared."""
    prev = getattr(_TLS, "collecting", None)
    _TLS.collecting = got = {}
    try:
        yield got
    finally:
        _TLS.collecting = prev
    if set(got) != set(names):
        raise ValueError(
            "the loss declares the device counters %s and added %s while "
            "it was traced" % (sorted(names), sorted(got)))
