"""ModelItem — the captured training program.

TPU-native analog of reference ``autodist/graph_item.py:218-553``. Where the
reference wraps a ``tf.Graph`` and mines it for gradient/variable/update-op
metadata via op-type tables (``kernel/common/op_info.py``) and optimizer
monkeypatches (``graph_item.py:73-109``), here the program is a pure JAX
function and the metadata comes from *tracing*:

- variables        -> the params pytree (flattened to slash-joined path names)
- gradients        -> ``jax.grad`` of the user's loss function (a pytree that
                      mirrors params exactly — the "grad/target pairs" of
                      ``graph_item.py:301-322`` fall out structurally)
- update ops       -> the optax ``GradientTransformation`` the user passes
                      (its name/args are recorded by ``autodist_tpu.patch``,
                      mirroring ``wrap_optimizer_init``)
- sparse variables -> jaxpr inspection: a param that flows into a ``gather``
                      as the operand being indexed is embedding-like (the
                      analog of the reference detecting ``IndexedSlices``
                      gradients, ``kernel/partitioner.py:660-684``)
"""
import dataclasses
import json
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import tree_flatten_with_path, keystr

from autodist_tpu.utils import logging


def _normalize_path(path) -> str:
    """Turn a jax key path into a slash-joined name: ``dense/kernel``."""
    parts = []
    for k in path:
        s = keystr((k,))
        s = s.strip("[]'\". ")
        if s.startswith("'") or s.startswith('"'):
            s = s[1:-1]
        parts.append(s)
    return "/".join(p for p in parts if p)


def flatten_with_names(tree) -> List[Tuple[str, Any]]:
    """Flatten a pytree into (name, leaf) pairs with deterministic order."""
    flat, _ = tree_flatten_with_path(tree)
    return [(_normalize_path(path), leaf) for path, leaf in flat]


def names_of(tree) -> List[str]:
    return [n for n, _ in flatten_with_names(tree)]


def shapes_of(tree):
    """``tree`` with a ``jax.ShapeDtypeStruct`` for every leaf: what a
    trace, ``eval_shape`` or a restore's template reads of it."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            np.shape(leaf), leaf.dtype if hasattr(leaf, "dtype")
            else np.asarray(leaf).dtype), tree)


@dataclasses.dataclass
class VarInfo:
    """Metadata for one trainable variable."""
    name: str
    shape: Tuple[int, ...]
    dtype: str
    trainable: bool = True
    sparse: bool = False  # embedding-like (gather-indexed) variable

    @property
    def byte_size(self) -> int:
        return int(np.prod(self.shape or (1,))) * np.dtype(self.dtype).itemsize

    @property
    def num_elements(self) -> int:
        return int(np.prod(self.shape or (1,)))

    def to_dict(self):
        return {"name": self.name, "shape": list(self.shape), "dtype": self.dtype,
                "trainable": self.trainable, "sparse": self.sparse}

    @classmethod
    def from_dict(cls, d):
        return cls(name=d["name"], shape=tuple(d["shape"]), dtype=d["dtype"],
                   trainable=d.get("trainable", True), sparse=d.get("sparse", False))


# ------------------------------------------------------------------ sparse detection

from autodist_tpu.kernel.common.op_info import (  # noqa: E402
    TRANSPARENT_PRIMITIVES as _TRANSPARENT_PRIMS)


def _gather_indexed_invars(jaxpr, candidates: set) -> set:
    """Return the subset of ``candidates`` (jaxpr in-vars) that flow, through
    shape-preserving ops, into a ``gather``'s operand-being-indexed.

    This is the recognition step the reference does by looking for
    ``IndexedSlices`` grads / sparse update-op types
    (reference ``kernel/common/op_info.py:73-117``).
    """
    return _gather_indexed_invars_mapped(
        jaxpr, {v: {v} for v in jaxpr.invars if v in candidates})


def _gather_indexed_invars_mapped(jaxpr, invar_roots: Dict[Any, set]) -> set:
    alias: Dict[Any, set] = {v: set(r) for v, r in invar_roots.items()}
    hit = set()

    def roots(atom):
        if hasattr(atom, "val"):
            return set()
        return alias.get(atom, set())

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "gather":
            hit.update(roots(eqn.invars[0]))
        for name, val in eqn.params.items():
            # sub-jaxprs appear as ClosedJaxpr (.jaxpr), as a PLAIN Jaxpr
            # (e.g. shard_map's "jaxpr" param), or in lists of either
            subs = []
            for item in (val if isinstance(val, (list, tuple)) else (val,)):
                if hasattr(item, "jaxpr"):
                    subs.append(item.jaxpr)
                elif hasattr(item, "eqns") and hasattr(item, "invars"):
                    subs.append(item)
            for sub in subs:
                if len(sub.invars) == len(eqn.invars):
                    inner_map = {}
                    for inner_v, outer_a in zip(sub.invars, eqn.invars):
                        r = roots(outer_a)
                        if r:
                            inner_map[inner_v] = r
                    if inner_map:
                        hit.update(_gather_indexed_invars_mapped(sub, inner_map))
        if prim in _TRANSPARENT_PRIMS and eqn.invars:
            r = roots(eqn.invars[0])
            if r:
                for ov in eqn.outvars:
                    alias.setdefault(ov, set()).update(r)
    return hit


def _axis_env_jaxpr(loss_fn: Callable, params, example_batch):
    """Trace with every framework axis name bound (size 1), for loss fns
    that use mesh collectives (``psum("model")``, ``axis_index("seq")``
    in ring attention, ...) and therefore cannot trace bare. Size-1 axes
    leave shapes untouched, and the jaxpr comes out un-wrapped so the
    gather walker sees the same program as inside the step."""
    from autodist_tpu.utils.axis_env import bound_axes
    with bound_axes():
        return jax.make_jaxpr(loss_fn)(params, example_batch)


def detect_sparse_vars(loss_fn: Callable, params, example_batch) -> set:
    """Names of params that are indexed by a ``gather`` in the forward pass."""
    try:
        closed = jax.make_jaxpr(loss_fn)(params, example_batch)
    except Exception:  # noqa: BLE001 — retry under a bound axis env
        try:
            closed = _axis_env_jaxpr(loss_fn, params, example_batch)
        except Exception as e:  # noqa: BLE001 — detection is best-effort
            logging.warning(
                "sparse-var detection failed (%s: %s); treating ALL vars "
                "dense — Parallax will route embeddings to AllReduce and "
                "sparse wire paths stay off; if the model has embedding "
                "tables, fix the trace failure or mark them via "
                "VarInfo.sparse", type(e).__name__, e)
            return set()
    jaxpr = closed.jaxpr
    flat_params, _ = tree_flatten_with_path(params)
    n_param_leaves = len(flat_params)
    param_invars = jaxpr.invars[:n_param_leaves]
    candidates = set(param_invars)
    hits = _gather_indexed_invars(jaxpr, candidates)
    names = []
    for (path, _leaf), invar in zip(flat_params, param_invars):
        if invar in hits:
            names.append(_normalize_path(path))
    return set(names)


# ------------------------------------------------------------------ ModelItem


class ModelItem:
    """The captured program + metadata handed to strategy builders.

    Two capture modes:

    * ``loss_fn`` mode (recommended): the framework owns the train step, so
      strategies can intercept gradients (compression, PS routing, sharded
      weight update). ``loss_fn(params, batch) -> scalar`` (or
      ``(scalar, aux)`` with ``has_aux=True``).
    * ``step_fn`` mode: an opaque user step; strategies can only assign
      shardings (the reference has no analog — its kernels always rewrite the
      graph — but this is the natural JAX low-level escape hatch). Lowered
      by ``GraphTransformer._transform_step_fn`` (jit in/out_shardings from
      the layouts; AllReduce/Partitioned families; entry:
      ``AutoDist.build_step``).

    ``params`` is the caller's initial parameter tree until ``Runner.init``
    has a placed state, and from then on a tree of ``jax.ShapeDtypeStruct``
    of the same structure, shapes and dtypes (:meth:`release_params`): the
    state owns copies, and all that is asked of ``params`` afterwards is a
    trace or a shape, so keeping the arrays would hold 4 B a parameter of
    device memory for the whole run.
    """

    def __init__(self,
                 loss_fn: Optional[Callable] = None,
                 optimizer=None,
                 params=None,
                 example_batch=None,
                 has_aux: bool = False,
                 step_fn: Optional[Callable] = None,
                 apply_fn: Optional[Callable] = None,
                 trainable_filter: Optional[Callable[[str], bool]] = None,
                 mp_rules=None, mp_meta=None):
        if loss_fn is None and step_fn is None:
            raise ValueError("ModelItem needs loss_fn or step_fn")
        self.loss_fn = loss_fn
        self.step_fn = step_fn
        self.apply_fn = apply_fn
        self.optimizer = optimizer
        self.params = params
        self.example_batch = example_batch
        self.has_aux = has_aux
        # model-parallel sharding rules the model family exports (e.g.
        # models.tp_lm.tp_rules()); registering them lets AutoStrategy
        # enumerate model-parallel candidates for this model — the rules'
        # axis names decide the family (model -> TP, pipe -> PP,
        # expert -> EP; see strategy/auto_strategy.mp_candidates)
        self.mp_rules = list(mp_rules) if mp_rules else None
        # extra search hints: pp_microbatches / pp_schedules the loss was
        # built with, seq_parallel=True when the model's attention shards
        # the sequence dim (ring/Ulysses)
        self.mp_meta = dict(mp_meta) if mp_meta else None
        # default: everything trains except flax's batch_stats collection
        # (BatchNorm running statistics are EMA state, not weights — updating
        # them by gradient would corrupt normalization)
        self.trainable_filter = trainable_filter or (
            lambda name: not (name.startswith("batch_stats/")
                              or "/batch_stats/" in name))
        # filled by patch.py when optimizer construction was captured
        self.optimizer_name: Optional[str] = None
        self.optimizer_args: Dict[str, Any] = {}
        self._var_infos: Optional[Dict[str, VarInfo]] = None
        self._opt_state_spec = None
        if optimizer is not None:
            from autodist_tpu import patch as _patch
            name, args = _patch.lookup_optimizer(optimizer)
            if name:
                self.optimizer_name, self.optimizer_args = name, args

    # ---------------------------------------------------------------- capture

    def prepare(self) -> "ModelItem":
        """Collect variable metadata (analog of ``graph_item.prepare()``,
        reference ``autodist/graph_item.py:494-497``)."""
        if self.params is None:
            raise ValueError("ModelItem.prepare() requires params")
        infos: Dict[str, VarInfo] = {}
        sparse = set()
        if self.loss_fn is not None and self.example_batch is not None:
            loss = self.loss_fn
            if self.has_aux:
                loss = lambda p, b: self.loss_fn(p, b)[0]  # noqa: E731
            sparse = detect_sparse_vars(loss, self.params, self.example_batch)
        for name, leaf in flatten_with_names(self.params):
            arr = jnp.asarray(leaf) if not hasattr(leaf, "shape") else leaf
            infos[name] = VarInfo(
                name=name,
                shape=tuple(arr.shape),
                dtype=str(np.dtype(arr.dtype)),
                trainable=bool(self.trainable_filter(name)),
                sparse=name in sparse,
            )
        self._var_infos = infos
        if self.optimizer is not None:
            self._opt_state_spec = jax.eval_shape(self.optimizer.init, self.params)
        logging.debug("ModelItem.prepare: %d vars (%d sparse)", len(infos), len(sparse))
        return self

    # ---------------------------------------------------------------- queries

    @property
    def var_infos(self) -> Dict[str, VarInfo]:
        if self._var_infos is None:
            self.prepare()
        return self._var_infos

    @property
    def trainable_var_names(self) -> List[str]:
        return [n for n, v in self.var_infos.items() if v.trainable]

    @property
    def sparse_var_names(self) -> List[str]:
        return [n for n, v in self.var_infos.items() if v.sparse]

    @property
    def opt_state_spec(self):
        if self._opt_state_spec is None and self.optimizer is not None and self.params is not None:
            self._opt_state_spec = jax.eval_shape(self.optimizer.init, self.params)
        return self._opt_state_spec

    def grad_fn(self) -> Callable:
        """value_and_grad of the loss — the grad/target pairing of
        reference ``graph_item.py:301-322`` is the returned pytree itself."""
        if self.loss_fn is None:
            raise ValueError("grad_fn requires loss_fn capture mode")
        return jax.value_and_grad(self.loss_fn, has_aux=self.has_aux)

    def total_bytes(self) -> int:
        return sum(v.byte_size for v in self.var_infos.values())

    def release_params(self):
        """Let go of the initial parameters' arrays and keep their shapes
        (``Runner.init``, once the state is placed). The caller's own
        reference stays valid."""
        self.params = shapes_of(self.params)

    # ------------------------------------------------------------ serialization

    def to_spec_dict(self) -> dict:
        """Spec-level serialization (analog of graphitem.proto,
        reference ``proto/graphitem.proto:31-48``) — records metadata, not code."""
        return {
            "vars": [v.to_dict() for v in self.var_infos.values()],
            "optimizer_name": self.optimizer_name,
            "optimizer_args": {k: repr(v) for k, v in (self.optimizer_args or {}).items()},
            "has_aux": self.has_aux,
            "mode": "loss_fn" if self.loss_fn is not None else "step_fn",
        }

    def serialize_spec(self) -> bytes:
        return json.dumps(self.to_spec_dict(), sort_keys=True).encode()

    @staticmethod
    def spec_from_bytes(b: bytes) -> dict:
        return json.loads(b.decode())
