"""Embedding lookup with a sparse-gradient wire path.

Analog of the reference's sparse synchronization: AllReduce all-gathers
IndexedSlices' indices+values instead of densifying
(reference ``autodist/kernel/synchronization/all_reduce_synchronizer.py:132-173``)
and the PS path ships/splits slices by index range
(reference ``kernel/partitioner.py:660-684``, sparse accumulators
``ps_synchronizer.py:476-535``). JAX has no IndexedSlices — ``jax.grad``
materializes a DENSE cotangent for a gathered table — so the sparse wire
path needs the lowering's cooperation:

**The tap trick.** ``embedding_lookup(table, ids, name=...)`` is an
ordinary ``take`` until the lowering activates a capture context. Then the
lookup computes ``stop_gradient(table)[ids] + tap`` where ``tap`` is a
zeros array shaped like the gathered rows: the table itself receives NO
dense gradient, while ``d loss / d tap`` IS exactly the per-row gradient
values (and ``ids`` is already in hand). The step then synchronizes
``(ids, values)`` — batch-sized — instead of a vocab-sized dense array:

- AllReduce path: all-gather ids+values across the mesh, scatter-add
  locally into the update (wire bytes ~ batch x dim instead of
  vocab x dim);
- host-PS path: ship (ids, values) to the store, which scatter-adds into
  each owner shard's index range on the host (the reference's
  index-range split).

``embedding_lookup`` is the framework's opt-in surface (the reference had
the same property: sparsity flowed only through ``tf.nn.embedding_lookup``
producing IndexedSlices). A sparse-detected variable whose lookups don't
carry a matching ``name`` falls back to dense psum with a warning.
"""
import contextlib
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

_TLS = threading.local()


class SparseCapture:
    """State of one traced step under the capture context.

    ``record=True`` (discovery trace): log each lookup's ids/feature shapes so
    the lowering can build taps. ``record=False`` (the real step): consume
    taps and collect the traced ids for the aux output."""

    def __init__(self, taps: Optional[Dict[str, List]] = None,
                 record: bool = False):
        self.taps = taps or {}
        self.record = record
        self.calls: Dict[str, int] = {}
        self.ids: Dict[str, List] = {}
        # name -> [(ids_shape, ids_dtype_str, feat_shape), ...] per call
        self.shapes: Dict[str, List[Tuple]] = {}


def current_capture() -> Optional[SparseCapture]:
    return getattr(_TLS, "capture", None)


def clear_capture() -> None:
    """Drop any capture context leaked on this thread (an exception can
    escape a trace before ``capture``'s finally restores the previous
    context chain) — called by ``autodist_tpu.reset()``."""
    _TLS.capture = None


@contextlib.contextmanager
def capture(taps: Optional[Dict[str, List]] = None, record: bool = False):
    prev = current_capture()
    cap = SparseCapture(taps, record)
    _TLS.capture = cap
    try:
        yield cap
    finally:
        _TLS.capture = prev


def embedding_lookup(table: jax.Array, ids: jax.Array,
                     name: Optional[str] = None) -> jax.Array:
    """Row lookup ``table[ids]`` with an optional sparse-gradient identity.

    ``name`` must equal the table's flattened parameter name (e.g.
    ``"embed/table"``) for the sparse wire path to engage; without it the
    op is exactly ``jnp.take(table, ids, axis=0)``."""
    cap = current_capture()
    if cap is None or name is None:
        return jnp.take(table, ids, axis=0)
    k = cap.calls.get(name, 0)
    cap.calls[name] = k + 1
    if cap.record:
        cap.shapes.setdefault(name, []).append(
            (tuple(ids.shape), str(ids.dtype), tuple(table.shape[1:]),
             str(table.dtype)))
        return jnp.take(table, ids, axis=0)
    taps = cap.taps.get(name)
    if taps is None or k >= len(taps):
        return jnp.take(table, ids, axis=0)
    cap.ids.setdefault(name, []).append(ids)
    rows = jnp.take(jax.lax.stop_gradient(table), ids, axis=0)
    return rows + taps[k]


def discover(loss_fn, params, example_batch,
             candidate_names) -> Dict[str, List[Tuple]]:
    """Trace the loss once in record mode; return the tap shapes for every
    candidate sparse var that flowed through a named ``embedding_lookup``."""
    # a fresh wrapper defeats JAX's trace cache: the recording side effect
    # must run even when the same loss fn was already traced (sparse
    # detection, metric-spec eval) without the capture context active
    def fresh(p, b):
        return loss_fn(p, b)
    with capture(record=True) as cap:
        jax.eval_shape(fresh, params, example_batch)
    return {n: specs for n, specs in cap.shapes.items()
            if n in candidate_names}


def tap_grad_jaxpr(loss_fn, params, example_batch, specs):
    """The jaxpr of the loss's gradient w.r.t. ``params`` under tap
    capture (one trace of the backward pass; its first outputs are the
    gradients in params-flatten order)."""
    def wrapped(p, taps, b):
        with capture(taps):
            return loss_fn(p, b)

    return jax.make_jaxpr(jax.grad(wrapped, argnums=0))(
        params, make_taps(specs), example_batch).jaxpr


def safe_sparse_names(jaxpr, specs, param_names) -> set:
    """Subset of discovered sparse vars whose DENSE cotangent is
    structurally zero under tap capture — i.e. the table's only gradient
    path is through the lookups. A table with other differentiable uses
    (tied output embeddings, weight sharing) gets a real dense gradient
    that the sparse wire would silently drop, so those vars must stay on
    the dense path. Checked on the gradient jaxpr
    (:func:`tap_grad_jaxpr`): a clean table's grad is a broadcast of
    literal zero."""
    producers = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producers[ov] = eqn

    def is_zero(atom, depth=0) -> bool:
        if hasattr(atom, "val"):  # literal
            import numpy as _np
            try:
                return bool((_np.asarray(atom.val) == 0).all())
            except Exception:  # noqa: BLE001
                return False
        eqn = producers.get(atom)
        if eqn is None or depth > 3:
            return False
        if eqn.primitive.name in ("broadcast_in_dim", "convert_element_type"):
            return is_zero(eqn.invars[0], depth + 1)
        return False

    out = set()
    flat_names = param_names
    for i, n in enumerate(flat_names):
        if n not in specs:
            continue
        if is_zero(jaxpr.outvars[i]):
            out.add(n)
    return out


def make_taps(shape_specs: Dict[str, List[Tuple]]) -> Dict[str, List]:
    """Zeros taps matching a discovery result (per lookup call)."""
    return {
        name: [jnp.zeros(tuple(ids_shape) + tuple(feat_shape), feat_dtype)
               for ids_shape, _dt, feat_shape, feat_dtype in specs]
        for name, specs in shape_specs.items()}


def flatten_pairs(ids_list: List, tap_grads: List) -> Tuple[jax.Array, jax.Array]:
    """Merge a var's per-call (ids, values) into one flat pair:
    ids (L,), values (L, feat_elems)."""
    flat_ids, flat_vals = [], []
    for ids, vals in zip(ids_list, tap_grads):
        flat_ids.append(ids.reshape(-1))
        flat_vals.append(vals.reshape(ids.size, -1))
    return jnp.concatenate(flat_ids), jnp.concatenate(flat_vals, axis=0)


def gather_pairs(ids: jax.Array, vals: jax.Array, axis_names) -> Tuple[jax.Array, jax.Array]:
    """All-gather an (ids, values) pair across mesh axes — the sparse wire
    (reference ``all_reduce_synchronizer.py:155-169``). Wire bytes are
    batch-shaped, not vocab-shaped."""
    g_ids = jax.lax.all_gather(ids, axis_names, axis=0, tiled=True)
    g_vals = jax.lax.all_gather(vals, axis_names, axis=0, tiled=True)
    return g_ids, g_vals


def scatter_add_dense(ids: jax.Array, vals: jax.Array, vocab: int,
                      feat_shape: Tuple[int, ...]) -> jax.Array:
    """(ids, values) -> dense gradient (the local densify after the wire)."""
    import math
    feat = math.prod(feat_shape) if feat_shape else 1
    dense = jnp.zeros((vocab, feat), vals.dtype).at[ids].add(vals)
    return dense.reshape((vocab,) + tuple(feat_shape))
