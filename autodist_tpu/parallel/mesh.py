"""Deterministic device-mesh construction.

The TPU-native replacement for the reference's TF ClusterSpec + deterministic
ip:port ordering (reference ``autodist/cluster.py:70-82``): every process must
independently build the *same* mesh so that independently-lowered programs
agree on collective participants — the analog of the reference's
deterministic collective key generation
(``kernel/synchronization/collective_key.py:43-70``).

Devices are ordered by (process_index, device id), which is stable across
all processes of one jax.distributed job.
"""
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh

from autodist_tpu import const
from autodist_tpu.utils import logging


def ordered_devices(n: Optional[int] = None, backend: Optional[str] = None) -> List:
    devs = sorted(jax.devices(backend) if backend else jax.devices(),
                  key=lambda d: (d.process_index, d.id))
    if n is not None:
        if len(devs) < n:
            raise ValueError("need %d devices, runtime has %d" % (n, len(devs)))
        devs = devs[:n]
    return devs


def build_mesh(num_devices: Optional[int] = None,
               axes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence] = None,
               backend: Optional[str] = None) -> Mesh:
    """Build a Mesh with named axes.

    ``axes`` maps axis name -> size, in major-to-minor order; sizes must
    multiply to the device count. Default: a 1-D data-parallel mesh over all
    devices. Axis order convention (outer->inner): pipe, data, expert, seq,
    model — inner axes get the fastest ICI links (nearest-neighbor), which is
    where tensor-parallel collectives belong.
    """
    if devices is None:
        devices = ordered_devices(num_devices, backend)
    devices = list(devices)
    if not axes:
        axes = {const.DATA_AXIS: len(devices)}
    sizes = list(axes.values())
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError("mesh axes %s don't cover %d devices" % (axes, len(devices)))
    arr = np.array(devices, dtype=object).reshape(sizes)
    mesh = Mesh(arr, tuple(axes.keys()))
    logging.debug("built mesh %s over %d devices", dict(axes), len(devices))
    return mesh


def host_to_mesh(mesh: Mesh, value, pspec) -> jax.Array:
    """Place a value onto the mesh with the given PartitionSpec.
    Works single- and multi-process (every process provides its addressable
    shards from the same host-global value).

    On a single-process mesh, already-device-resident values take the
    ``device_put`` path: XLA reshards on device (a no-op when the sharding
    already matches). ``np.asarray`` on a jax.Array would DOWNLOAD it to
    host and re-upload for nothing. Multi-process
    meshes stay on the callback path: ``device_put`` cannot retarget a
    committed process-local array onto a mesh this process only partly
    owns, and for uncommitted arrays it inserts per-leaf cross-host
    equality collectives — each-process-provides-its-shards is the
    multi-process contract here."""
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, pspec)
    if isinstance(value, jax.Array) and jax.process_count() == 1:
        return jax.device_put(value, sharding)
    arr = np.asarray(value)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def tree_to_mesh(mesh: Mesh, tree, pspec):
    """Place a whole pytree onto the mesh with ONE shared PartitionSpec.
    Single-process meshes take the batched ``device_put`` fast path (one
    dispatch for the whole tree, not one per leaf — the per-step PS pull of
    a 100-variable model is 100x fewer host round-trips); multi-process
    falls back to the per-leaf host-global placement."""
    from jax.sharding import NamedSharding
    if jax.process_count() == 1:
        return jax.device_put(tree, NamedSharding(mesh, pspec))
    return jax.tree_util.tree_map(
        lambda leaf: host_to_mesh(mesh, leaf, pspec), tree)


def dcn_axes(mesh: Mesh) -> tuple:
    """Mesh axes that cross process (host) boundaries — the axes whose
    collectives ride DCN rather than ICI. Detected from the device layout
    (process_index varies along the axis); ``ADT_DCN_AXES`` (comma list)
    overrides for single-process tests and exotic topologies."""
    ov = const.ENV.ADT_DCN_AXES.val
    if ov:
        names = [a.strip() for a in ov.split(",") if a.strip()]
        return tuple(a for a in names if a in mesh.axis_names)
    procs = np.vectorize(lambda d: d.process_index)(mesh.devices)
    out = []
    for i, name in enumerate(mesh.axis_names):
        if procs.min(axis=i).tolist() != procs.max(axis=i).tolist():
            out.append(name)
    return tuple(out)


def local_mesh(backend: Optional[str] = None,
               axes: Optional[Dict[str, int]] = None) -> Mesh:
    """Mesh over THIS process's devices only — the between-graph replication
    substrate for async PS (no cross-process collectives; processes couple
    only through the parameter service, reference
    ``ps_synchronizer.py:556-633`` semantics)."""
    devs = sorted(jax.local_devices(backend=backend) if backend
                  else jax.local_devices(), key=lambda d: d.id)
    return build_mesh(devices=devs, axes=axes)


def mesh_from_strategy(strategy, resource_spec=None, backend: Optional[str] = None) -> Mesh:
    """Mesh for a compiled Strategy: replicas define the data axis; the
    optional ``mesh_shape`` extension adds model/pipeline/sequence axes."""
    n = len(strategy.graph_config.replicas)
    shape = strategy.graph_config.mesh_shape
    if shape:
        return build_mesh(axes=dict(shape), backend=backend)
    return build_mesh(num_devices=n or None, backend=backend)
