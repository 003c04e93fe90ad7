"""Remapper — feed/fetch adaptation between user values and the mesh.

Analog of reference ``autodist/remapper.py:29-313``. The reference splits
each fed batch along its first (polymorphic) dimension across replica
placeholders and maps fetches back (train ops fetched on all replicas,
tensors taken from the master replica or concatenated). Here:

- **feed**: a host-global batch (numpy/pytree) is placed onto the mesh
  sharded along the data axis (``Remapper.remap_feed``); values whose
  leading dim can't shard (scalars) are replicated — the analog of
  "duplicate when no polymorphic dim" (reference ``remapper.py:81-123``).
- **fetch**: step outputs are device-global arrays; replicated metrics come
  back as single host values (the "master replica" read,
  ``remapper.py:125-185``), sharded outputs are gathered and concatenated.
"""
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu.model_item import _normalize_path
from autodist_tpu.utils import logging


class Remapper:
    def __init__(self, mesh, mesh_axis: str, seq_axis: str = None,
                 batch_axes=None, seq_keys=None):
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.seq_axis = seq_axis
        # leaf names whose dim 1 is the sequence dim (strategy
        # graph_config.seq_feed_keys); None = every rank>=2 leaf
        self.seq_keys = frozenset(seq_keys) if seq_keys else None
        # axes the batch dim shards over (expert-parallel strategies add the
        # expert axis so every device sees distinct tokens)
        self.batch_axes = tuple(batch_axes) if batch_axes else (mesh_axis,)
        self.num_replicas = 1
        for a in self.batch_axes:
            self.num_replicas *= int(mesh.shape[a])
        self.seq_shards = mesh.shape[seq_axis] if seq_axis else 1
        # device_put can only retarget arrays onto meshes this process fully
        # owns; multi-process meshes must go through host_to_mesh
        self._fully_addressable = all(
            d.process_index == jax.process_index()
            for d in np.asarray(mesh.devices).flat)

    # ------------------------------------------------------------------ feed

    def _place(self, value, pspec):
        from autodist_tpu.parallel.mesh import host_to_mesh
        return host_to_mesh(self.mesh, value, pspec)

    def _leaf_spec(self, shape, replicas: int, what: str,
                   name: str = None) -> P:
        """PartitionSpec + divisibility validation shared by the global
        and process-local feed paths (``replicas`` is the batch-dim
        divisor the caller needs: all replicas, or this process's).
        With ``seq_keys`` declared, only the named leaves shard dim 1
        over the sequence axis — a one-hot label leaf [B, C] must not
        have its class dim sliced (or spuriously rejected) just for
        being rank 2."""
        if len(shape) == 0:
            return P()
        if shape[0] % replicas != 0:
            raise ValueError(
                "%s batch dim %d is not divisible by the %d replicas; pad "
                "or resize the batch (TPU programs need static, even "
                "shards)" % (what, shape[0], replicas))
        seq_applies = (self.seq_axis and len(shape) >= 2
                       and (self.seq_keys is None or name in self.seq_keys))
        if seq_applies:
            if shape[1] % self.seq_shards != 0:
                raise ValueError(
                    "sequence dim %d of %r is not divisible by the %d "
                    "sequence shards (not a sequence leaf? declare the "
                    "token keys via SequenceParallelAR(seq_keys=[...]))"
                    % (shape[1], name, self.seq_shards))
            return P(self.batch_axes, self.seq_axis)
        return P(self.batch_axes)

    def _place_leaf(self, leaf, spec: P):
        """Place one leaf with ``spec``, passing through leaves already
        mesh-placed with an equivalent sharding — re-placing would
        round-trip them through the host."""
        if isinstance(leaf, jax.Array):
            want = NamedSharding(self.mesh, spec)
            if leaf.sharding.is_equivalent_to(want, leaf.ndim):
                return leaf
            if self._fully_addressable:
                return jax.device_put(leaf, want)
            if not leaf.is_fully_addressable:
                # a multi-process global array with the WRONG sharding
                # cannot be read back host-side (np.asarray raises on
                # non-addressable shards) — tell the caller what to do
                raise ValueError(
                    "feed %s is a multi-process global array with "
                    "sharding %s (want %s); feed host numpy arrays, or "
                    "pre-place with Remapper.remap_feed's target "
                    "sharding" % (np.shape(leaf), leaf.sharding, want))
            # process-local device array: re-place via the host-global
            # path (make_array_from_callback), which every process runs
        return self._place(np.asarray(leaf), spec)

    def remap_feed(self, batch) -> Any:
        """Split the global batch across replicas along dim 0. Leaves that
        are already mesh-placed with the right sharding (e.g. by
        ``data.DevicePrefetcher``) pass through untouched — re-placing
        would round-trip them through the host."""
        def place(path, leaf):
            spec = self._leaf_spec(np.shape(leaf), self.num_replicas,
                                   "global", _normalize_path(path))
            return self._place_leaf(leaf, spec)
        return jax.tree_util.tree_map_with_path(place, batch)

    def feed_avals(self, batch, stack: int = 0) -> Any:
        """Shapes, dtypes and shardings of ``batch`` as :meth:`remap_feed`
        places it (``stack=k``: as :meth:`remap_feed_stack` places k of
        them stacked) without placing anything: what lowering a program
        for that feed needs."""
        def aval(path, leaf):
            shape = tuple(np.shape(leaf))
            spec = self._leaf_spec(shape, self.num_replicas, "global",
                                   _normalize_path(path))
            if stack:
                shape, spec = (stack,) + shape, P(None, *spec)
            dtype = (leaf.dtype if hasattr(leaf, "dtype")
                     else np.asarray(leaf).dtype)
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_map_with_path(aval, batch)

    def remap_feed_stack(self, stacked_batch) -> Any:
        """Place a STACKED ``[k, ...]`` batch for the fused multi-step
        engine: dim 0 is the microstep (scan) dim, kept unsharded; the
        ORIGINAL leaf layout — batch split over the data axes, sequence
        dim over the sequence axis — applies from dim 1 on. One transfer
        feeds k microsteps. Pre-placed leaves (``DevicePrefetcher``'s
        stack mode) pass through untouched."""
        def place(path, leaf):
            shape = np.shape(leaf)
            if len(shape) == 0:
                raise ValueError(
                    "stacked feed %r is a scalar — every leaf needs the "
                    "leading [k] microstep dim" % _normalize_path(path))
            inner = self._leaf_spec(shape[1:], self.num_replicas,
                                    "stacked global", _normalize_path(path))
            return self._place_leaf(leaf, P(None, *inner))
        return jax.tree_util.tree_map_with_path(place, stacked_batch)

    def remap_feed_local(self, local_batch) -> Any:
        """Place a PROCESS-LOCAL batch as this process's slice of the
        global batch — the scalable multi-host feed: each process loads
        only its own 1/process_count of the data (e.g.
        ``RecordFileDataset(shard=(process_index, process_count))``)
        instead of materializing the identical global batch everywhere,
        and the slices concatenate along dim 0 in process order. The
        result is mesh-placed, so ``run``/``remap_feed`` pass it through
        untouched. Single-process jobs: identical to ``remap_feed``."""
        if jax.process_count() == 1:
            return self.remap_feed(local_batch)
        if self.num_replicas % jax.process_count() != 0:
            raise ValueError(
                "cannot feed process-local batches: the %d batch replicas "
                "do not divide evenly over %d processes (each process must "
                "own a whole number of replicas)"
                % (self.num_replicas, jax.process_count()))
        local_replicas = self.num_replicas // jax.process_count()

        def place(path, leaf):
            arr = np.asarray(leaf)
            if arr.ndim == 0:
                # scalars are replicated; every process must provide the
                # same value (cannot be a per-process slice)
                return self._place(arr, P())
            spec = self._leaf_spec(arr.shape, local_replicas, "local",
                                   _normalize_path(path))
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, spec), arr)
        return jax.tree_util.tree_map_with_path(place, local_batch)

    # ----------------------------------------------------------------- fetch

    def remap_fetch(self, fetched) -> Any:
        """Bring step outputs to host: replicated values as scalars/arrays,
        sharded values gathered (concatenated along their sharded dim)."""
        return jax.device_get(fetched)
