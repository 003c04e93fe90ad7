"""Host-offloaded parameter-server data path.

Analog of the reference's between-graph PS placement: the reference places
each PS variable and its update op ON the parameter-server device — a host
CPU device — and workers read/write it over the wire every step
(reference ``autodist/kernel/synchronization/ps_synchronizer.py:171-176``,
task placement ``:636-762``). The TPU-native equivalent keeps PS variables
(and their optimizer state — the Adam moments are usually 2x the weights)
resident in **host memory**, off the HBM:

- at step start the store **pulls**: PS values transfer host -> device and
  enter the SPMD step replicated (the reference's workers reading from the
  PS over gRPC);
- the step returns the mean-psum'd gradient for every PS variable instead
  of updating it on device (the reference's grad push to the PS
  accumulator);
- the store **pushes**: gradients transfer device -> host, are split by
  true shard ranges (honoring *uneven* ``shard_sizes`` exactly — host
  arrays need no XLA padding, reference
  ``strategy/uneven_partition_ps_strategy.py:128-137``), and the optimizer
  update is applied **on the host CPU** per shard (the reference's update
  op placed on the PS device).

The strategy's ``local_replication`` knob therefore changes the program:
``True`` (proxy, reference ``common/proxy_variable.py:74-191``) keeps the
variable device-resident and updates it on device — no per-step parameter
traffic; ``False`` routes it through this host path — 1/HBM residency in
exchange for PCIe traffic every step. ``reduction_destination`` assigns the
owning host; in synchronous mode every process holds a deterministic mirror
(the psum'd gradient is bit-identical everywhere, so replaying the update
locally IS the reference's "every worker transforms its own graph"
architecture with zero serving traffic), and the owner is the one whose
copy is authoritative for checkpoints and async serving.

Mechanically, PS variables are carved out of the device ``TrainState`` as
**holes** — empty pytree nodes that keep the tree structure (so optax
transformations, tree specs and donation all compose) while contributing no
device arrays.
"""
import dataclasses
import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu.model_item import _normalize_path
from autodist_tpu.telemetry import spans as tel
from autodist_tpu.utils import logging


# ------------------------------------------------------------------- holes


class PSHole:
    """An empty pytree node standing where a host-resident PS variable
    would be: flattening yields no leaves, so jit/optax/shard_map treat it
    as pure structure. The variable's flattened name rides in the treedef
    (aux data), so two states with the same PS plan unify under jit."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __repr__(self):
        return "PSHole(%s)" % self.name


jax.tree_util.register_pytree_node(
    PSHole, lambda h: ((), h.name), lambda name, _: PSHole(name))


def _is_hole(x) -> bool:
    return isinstance(x, PSHole)


def hole_out_params(params, ps_names) -> Any:
    """Replace leaves named in ``ps_names`` with PSHole nodes."""
    def repl(path, leaf):
        name = _normalize_path(path)
        return PSHole(name) if name in ps_names else leaf
    return jax.tree_util.tree_map_with_path(repl, params)


def fill_holes(tree, values: Dict[str, Any]) -> Any:
    """Replace every PSHole with ``values[hole.name]``."""
    return jax.tree_util.tree_map(
        lambda x: values[x.name] if _is_hole(x) else x, tree, is_leaf=_is_hole)


def fill_holes_with_path(tree, provider: Callable[[str, str], Any]) -> Any:
    """Replace every PSHole with ``provider(path, var_name)`` — used for
    optimizer-state reconstruction where the hole's tree position (the
    optimizer slot) matters."""
    def repl(path, x):
        if _is_hole(x):
            return provider(_normalize_path(path), x.name)
        return x
    return jax.tree_util.tree_map_with_path(repl, tree, is_leaf=_is_hole)


def hole_like(template, full):
    """Structure-align ``full`` to a holed ``template``: wherever the
    template has a PSHole, the corresponding subtree of ``full`` is dropped
    and the hole kept; everywhere else ``full``'s leaves win."""
    return jax.tree_util.tree_map(
        lambda t, f: t if _is_hole(t) else f, template, full, is_leaf=_is_hole)


def extract_holes(template, full) -> Dict[Tuple[str, str], Any]:
    """Inverse of :func:`hole_like`: ``{(hole_path, var_name): subtree}``
    for every hole position, pulling the subtree out of ``full``."""
    out: Dict[Tuple[str, str], Any] = {}

    def visit(path, t, f):
        if _is_hole(t):
            out[(_normalize_path(path), t.name)] = f
        return t
    jax.tree_util.tree_map_with_path(visit, template, full, is_leaf=_is_hole)
    return out


def holes_of(tree) -> List[str]:
    """Names of all PSHoles in a tree."""
    found: List[str] = []
    jax.tree_util.tree_map(
        lambda x: found.append(x.name) if _is_hole(x) else None,
        tree, is_leaf=_is_hole)
    return found


# -------------------------------------------------------------------- plans


@dataclasses.dataclass(frozen=True)
class PSVarPlan:
    """Host-residency plan for one PS variable.

    ``destinations`` has one owner device string per shard (length 1 for
    unpartitioned vars); ``shard_sizes`` are the TRUE sizes along ``axis``
    (uneven allowed — host storage is ragged, never padded).

    ``wire_dtype="int8"`` quantizes the host<->device step wire: pulls
    ship the value as blockwise int8 + f32 scales (dequantized in-graph),
    pushes ship the reduced gradient the same way (dequantized at the
    store boundary before the optimizer apply). The store itself always
    holds exact fp32 — only the wire is lossy."""
    var_name: str
    destinations: Tuple[str, ...]
    shard_sizes: Optional[Tuple[int, ...]] = None   # None = unpartitioned
    axis: int = 0
    sync: bool = True
    staleness: int = 0
    sparse: bool = False
    wire_dtype: str = "fp32"

    @property
    def partitioned(self) -> bool:
        return self.shard_sizes is not None and len(self.shard_sizes) > 1

    def shard_ranges(self) -> List[Tuple[int, int]]:
        if not self.shard_sizes:
            return [(0, -1)]
        ranges, off = [], 0
        for s in self.shard_sizes:
            ranges.append((off, off + s))
            off += s
        return ranges


def _even_or_given_sizes(node, info) -> Tuple[int, ...]:
    if node.shard_sizes:
        return tuple(node.shard_sizes)
    n = node.num_shards
    axis = node.partition_axis or 0
    dim = info.shape[axis]
    base, rem = divmod(dim, n)
    return tuple(base + (1 if i < rem else 0) for i in range(n))


def plan_host_ps(strategy, var_infos) -> Dict[str, PSVarPlan]:
    """Decide which variables are host-resident, from the compiled strategy.

    A variable routes to the host PS path when it is PS-synchronized with
    ``local_replication=False`` (no proxy — the reference's default, where
    every read hits the PS). Proxied PS vars stay device-resident; AllReduce
    vars never come here. The cached-vs-resident decision itself is owned by
    ``ProxyVariable.plan`` (single source — this function adds only the
    eligibility gating: trainable, non-model-parallel, uniform shard
    configs)."""
    from autodist_tpu.kernel.common.proxy_variable import ProxyVariable
    from autodist_tpu.strategy.base import PSSynchronizer as PSConfig

    def cached(cfg) -> bool:
        return ProxyVariable.plan("", cfg, None).cached

    def wire_for(info, syncs) -> str:
        """The plan's host-wire format: int8 only when EVERY shard config
        asks for it AND the variable is dense float — the same guard the
        linter enforces as ADT310 (sparse grads ship (ids, values) pairs,
        integer values have no absmax scale). No block-size floor here:
        the planner does what the plan says; ADT311 is the linter's
        advisory."""
        from autodist_tpu.parallel.collectives import wire_quantizable
        if not wire_quantizable(info):
            return "fp32"
        if all((getattr(s, "wire_dtype", "fp32") or "fp32") == "int8"
               for s in syncs):
            return "int8"
        return "fp32"

    plans: Dict[str, PSVarPlan] = {}
    for node in strategy.node_config:
        info = var_infos.get(node.var_name)
        if info is None or not info.trainable:
            continue
        if node.mp_axes:
            continue  # model-parallel storage owns these
        sync_cfg = node.synchronizer
        part_syncs = [p.synchronizer for p in node.part_configs
                      if p.synchronizer is not None]
        if node.partitioner and part_syncs:
            if not all(isinstance(s, PSConfig) for s in part_syncs):
                continue
            if any(cached(s) for s in part_syncs):
                continue  # proxied: device ZeRO path
            sizes = _even_or_given_sizes(node, info)
            plans[node.var_name] = PSVarPlan(
                var_name=node.var_name,
                destinations=tuple(s.reduction_destination for s in part_syncs),
                shard_sizes=sizes,
                axis=node.partition_axis or 0,
                sync=all(s.sync for s in part_syncs),
                staleness=max(s.staleness for s in part_syncs),
                sparse=info.sparse,
                wire_dtype=wire_for(info, part_syncs))
        elif isinstance(sync_cfg, PSConfig):
            if cached(sync_cfg):
                continue  # proxied: device-resident (cached) path
            plans[node.var_name] = PSVarPlan(
                var_name=node.var_name,
                destinations=(sync_cfg.reduction_destination,),
                sync=sync_cfg.sync,
                staleness=sync_cfg.staleness,
                sparse=info.sparse,
                wire_dtype=wire_for(info, [sync_cfg]))
    return plans


# -------------------------------------------------------------------- store


class PSStore:
    """Host-memory parameter server: values + optimizer state per shard.

    The store is the PS device of the reference — parameters rest here, the
    update op runs here (on the host CPU), and the training step only ever
    sees pulled copies. Updates run through the SAME optax optimizer the
    device path uses, one subtree per shard (the reference's per-PS
    optimizer placement; cross-variable optimizer coupling such as global
    gradient clipping decouples between the PS set and the device set,
    exactly as it did across reference PS shards).

    ``stats`` counts the wire: pulls/pushes and their bytes — the honest
    cost of the no-proxy PS path that tests and the simulator can assert
    on."""

    def __init__(self, plans: Dict[str, PSVarPlan], var_infos, optimizer):
        self.plans = dict(plans)
        self._var_infos = var_infos
        self._optimizer = optimizer
        # vars whose host<->device step wire ships blockwise int8 + scales
        # (PSVarPlan.wire_dtype): quantized at this store's boundary on
        # pull, dequantized at it on push — resident values stay exact f32
        self.wire_quant = sorted(n for n, p in self.plans.items()
                                 if p.wire_dtype == "int8")
        self._values: Dict[str, List[np.ndarray]] = {}
        self._opt: Dict[str, List[Any]] = {}
        try:
            self._cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError as e:
            raise RuntimeError(
                "host-PS strategies (PS / PSLoadBalancing, the default "
                "builder) keep parameters and run the optimizer update on "
                "the host, so jax needs a cpu backend beside the "
                "accelerator's and this process has none (JAX_PLATFORMS=%r)"
                " — add cpu to the list (JAX_PLATFORMS=tpu,cpu) or choose "
                "a device-resident strategy such as AllReduce"
                % os.environ.get("JAX_PLATFORMS")) from e
        self.stats = {"pulls": 0, "pushes": 0, "applies": 0,
                      "bytes_pulled": 0, "bytes_pushed": 0,
                      "degraded_pulls": 0}
        self._serve_groups: Optional[Dict[str, dict]] = None
        self._serve_config = None
        self._my_pushes = 0
        self._warned_sync_fallback = False
        # effective-LR scale applied to every optimizer update (sentinel
        # escalation ladder, runtime/sentinel.py): passed into the jitted
        # apply as an ARRAY argument, so changing it never retraces
        self.update_scale = 1.0
        # guards value/opt swaps vs concurrent reads: the async apply
        # thread must never expose a var whose shards span two versions
        import threading
        self._lock = threading.Lock()
        # ALL shards' updates traced into ONE program — one dispatch per
        # step instead of one per shard (a 100-var model pays ~100x less
        # host-dispatch latency). Compiled for CPU so PS updates never
        # touch HBM. NO donation: checkpoint readers (full_opt_leaf /
        # full_values) may hold references to the stored buffers while the
        # async apply thread runs; donating would invalidate them mid-read.
        self._apply_batch = jax.jit(self._apply_batch_impl)
        # shard updates are independent, so the apply fans out over a
        # thread pool (DLRM-scale tables: one CPU core running the whole
        # optimizer pass leaves the rest of the host idle). Deterministic
        # round-robin grouping -> stable jit cache AND bit-exact results.
        from autodist_tpu import const as _const
        n = _const.ENV.ADT_PS_APPLY_THREADS.val
        if n <= 0:
            n = min(4, os.cpu_count() or 1)
        self._apply_threads = n
        self._apply_pool = None  # lazily built on first parallel apply

    # ------------------------------------------------------------ lifecycle

    def _apply_impl(self, shard, opt_state, grad, scale=None):
        updates, new_opt = self._optimizer.update(
            {"v": grad}, opt_state, {"v": shard})
        if scale is not None:
            # sentinel LR escalation: exact lr semantics for linear-in-lr
            # transforms; `scale` is a traced array — no retrace on change
            updates = jax.tree_util.tree_map(
                lambda u: (u * scale).astype(u.dtype), updates)
        return optax.apply_updates({"v": shard}, updates)["v"], new_opt

    def _apply_batch_impl(self, shards, opt_states, grads, scale):
        """One traced program covering every (var, shard): per-key
        optimizer semantics identical to :meth:`_apply_impl` (each shard
        keeps its own little opt-state tree)."""
        new_vals, new_opts = {}, {}
        for key in shards:
            new_vals[key], new_opts[key] = self._apply_impl(
                shards[key], opt_states[key], grads[key], scale)
        return new_vals, new_opts

    def _apply_sharded(self, shards, opts, gshards):
        """Dispatch the per-shard updates — one jitted program when the
        pool is disabled or there is a single shard, else round-robin
        groups over the thread pool. Grouping is deterministic (sorted
        keys, fixed stride), so the jit cache is stable across steps and
        the per-shard math — hence the result — is identical to the
        single-dispatch baseline."""
        keys = sorted(shards)
        scale = jnp.float32(self.update_scale)
        n = min(self._apply_threads, len(keys))
        if n <= 1:
            return self._apply_batch(shards, opts, gshards, scale)
        if self._apply_pool is None:
            import concurrent.futures
            self._apply_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self._apply_threads,
                thread_name_prefix="adt-ps-apply")
        groups = [keys[i::n] for i in range(n)]

        def run(group):
            # jax.default_device is THREAD-local: without re-entering it,
            # pool workers would dispatch the host update onto the
            # accelerator
            with jax.default_device(self._cpu):
                return self._apply_batch({k: shards[k] for k in group},
                                         {k: opts[k] for k in group},
                                         {k: gshards[k] for k in group},
                                         scale)
        futures = [self._apply_pool.submit(run, g) for g in groups]
        new_vals, new_opts = {}, {}
        for f in futures:
            nv, no = f.result()
            new_vals.update(nv)
            new_opts.update(no)
        return new_vals, new_opts

    @staticmethod
    def _shard_slice(plan: PSVarPlan, si: int, full: np.ndarray) -> np.ndarray:
        """One shard's slice of a full array along the plan axis."""
        lo, hi = plan.shard_ranges()[si]
        idx = [slice(None)] * full.ndim
        idx[plan.axis] = slice(lo, hi)
        return np.ascontiguousarray(full[tuple(idx)])

    def _split(self, plan: PSVarPlan, full: np.ndarray) -> List[np.ndarray]:
        if not plan.partitioned:
            return [np.asarray(full)]
        return [self._shard_slice(plan, si, full)
                for si in range(len(plan.shard_ranges()))]

    def init_params(self, full_params) -> None:
        """Take ownership of the PS leaves of a host params tree."""
        from autodist_tpu.kernel.common import variable_utils
        names, leaves, _ = variable_utils.flatten_named(full_params)
        by_name = dict(zip(names, leaves))
        with jax.default_device(self._cpu):
            for name, plan in self.plans.items():
                full = np.asarray(jax.device_get(by_name[name]))
                self._values[name] = self._split(plan, full)
                self._opt[name] = [
                    self._optimizer.init({"v": jnp.asarray(s)})
                    for s in self._values[name]]
        if self._serve_config is not None:
            self._start_serving()

    def load_opt_from_full(self, full_opt_tree) -> None:
        """Rebuild per-shard optimizer state from a full-layout opt tree
        (checkpoint restore). Var-shaped leaves are sliced by shard range;
        everything else (step counts, factored-state leaves not along the
        split axis) is copied whole per shard."""
        from autodist_tpu.kernel.common import variable_utils
        flat_full = {}
        names, leaves, _ = variable_utils.flatten_named(full_opt_tree)
        for n, l in zip(names, leaves):
            flat_full[n] = np.asarray(jax.device_get(l))
        with jax.default_device(self._cpu):
            for name, plan in self.plans.items():
                info = self._var_infos[name]
                new_states = []
                for si in range(len(plan.shard_ranges())):
                    template = self._optimizer.init(
                        {"v": jnp.asarray(self._values[name][si])})
                    t_names, t_leaves, t_def = variable_utils.flatten_named(template)
                    out = []
                    for tn, tl in zip(t_names, t_leaves):
                        # little-tree leaf "0/mu/v" <-> full leaf "0/mu/<var>"
                        if tn.endswith("/v") or tn == "v":
                            src_name = (tn[:-2] + "/" + name) if tn.endswith("/v") else name
                        else:
                            src_name = tn
                        src = flat_full.get(src_name)
                        if src is None:
                            logging.warning(
                                "PS restore: opt leaf %r for %s not in "
                                "checkpoint; keeping fresh init", tn, name)
                            out.append(tl)
                            continue
                        if (plan.partitioned and src.ndim > plan.axis
                                and src.shape[plan.axis] == info.shape[plan.axis]):
                            src = self._shard_slice(plan, si, src)
                        out.append(jnp.asarray(src))
                    new_states.append(variable_utils.unflatten_named(t_def, out))
                self._opt[name] = new_states

    # ------------------------------------------------------------- step i/o

    def _local_full(self, names=None) -> Dict[str, np.ndarray]:
        out = {}
        for name in (names if names is not None else self.plans):
            plan = self.plans[name]
            with self._lock:
                shards = list(self._values[name])
            out[name] = (np.asarray(shards[0]) if len(shards) == 1
                         else np.concatenate([np.asarray(s) for s in shards],
                                             axis=plan.axis))
        return out

    def pull(self, wire: bool = True) -> Dict[str, np.ndarray]:
        """Current full values, host-side (the workers' per-step PS read).
        In serving (async) mode, values of groups owned by OTHER processes
        are fetched from the service — the latest published version, no
        barrier (the reference's async read-from-PS).

        ``wire=True`` (the step path) ships ``wire_dtype="int8"`` vars as
        their quantized wire container ``{"q", "s"}`` — the H2D transfer
        carries int8 + scales; the lowering dequantizes in-graph.
        ``wire=False`` (fused carry pull, checkpoints) returns exact f32;
        the fused scan body applies the codec per microstep itself, so
        its numerics still match the per-step wire exactly."""
        # step arg = this store's pull sequence: on a merged cluster
        # timeline the per-worker PS-wire spans line up per step, so
        # wire-time skew is visible per step, not just per run
        with tel.span("ps.pull", "ps",
                      serving=self._serve_groups is not None,
                      step=self.stats["pulls"]):
            out = self._pull_impl(wire=wire)
        tel.counter_add("ps.pulls")
        return out

    def _quantize_pull(self, out: Dict[str, np.ndarray],
                       count_bytes: bool) -> Dict[str, Any]:
        """Swap wire-quantized vars' values for their int8+scales wire
        containers, crediting the telemetry wire counters (and, on the
        mirror path, counting the TRUE wire bytes into ``bytes_pulled``
        — the serving path already counted its network blobs). Runs on
        whatever values the pull assembled — including a degraded pull's
        last-good snapshot, which therefore dequantizes on device exactly
        like a healthy one."""
        from autodist_tpu.parallel import collectives
        for name in self.wire_quant:
            full = np.asarray(out[name])
            w = collectives.quant_wire_np(full)
            qb = int(w["q"].nbytes + w["s"].nbytes)
            if count_bytes:
                self.stats["bytes_pulled"] += qb
            tel.counter_add("wire.bytes_quantized", qb)
            tel.counter_add("wire.bytes_saved", full.nbytes - qb)
            out[name] = w
        return out

    def _pull_impl(self, wire: bool = False) -> Dict[str, np.ndarray]:
        bytes0 = self.stats["bytes_pulled"]
        quant = frozenset(self.wire_quant) if wire else frozenset()
        if self._serve_groups is None:
            out = self._local_full()
            for name in out:
                if name in quant:
                    continue  # counted at its true wire width below
                self.stats["bytes_pulled"] += out[name].nbytes
        else:
            shard_vals: Dict[str, Dict[int, np.ndarray]] = {}
            for host, grp in self._serve_groups.items():
                if grp["owned"]:
                    blobs = self._local_shard_blobs(grp["pairs"])
                else:
                    from autodist_tpu.runtime import ps_service as pss
                    res, fetch_err = None, None
                    try:
                        deadline = time.monotonic() + 60.0
                        res = grp["service"].fetch()
                        while res is None:  # owner hasn't published yet
                            if time.monotonic() > deadline:
                                break
                            time.sleep(0.002)
                            res = grp["service"].fetch()
                    except OSError as e:
                        fetch_err = e
                    if fetch_err is not None:
                        # transport failure — the degraded-serve window
                        blobs = self._serve_stale(host, grp, fetch_err)
                        if blobs is None:
                            raise RuntimeError(
                                "async PS: owner %s unreachable and the "
                                "degraded-serve window is exhausted — "
                                "aborting instead of training on "
                                "unboundedly stale values (%s)"
                                % (host, fetch_err)) from fetch_err
                    elif res is None:
                        # service reachable but the owner never published:
                        # NOT a transport error — stale serving would hide
                        # a wedged owner behind frozen parameters
                        raise TimeoutError(
                            "async PS: owner %s never published" % host)
                    else:
                        _version, blob = res
                        blobs = pss.unpack_arrays(blob)
                        self.stats["bytes_pulled"] += len(blob)
                        # keep the last good fetch: the degraded-serve
                        # fallback for a transient service blip
                        grp["last_fetch"] = blobs
                        grp["degraded"] = 0
                for key, arr in blobs.items():
                    if "!" in key:
                        continue  # opt-state leaf (checkpoint wire)
                    name, si = key.rsplit("::", 1)
                    shard_vals.setdefault(name, {})[int(si)] = arr
            out = self._assemble(shard_vals)
        if wire and self.wire_quant:
            out = self._quantize_pull(out,
                                      count_bytes=self._serve_groups is None)
        self.stats["pulls"] += 1
        tel.counter_add("ps.bytes_pulled",
                        self.stats["bytes_pulled"] - bytes0)
        return out

    def _degraded_bound(self) -> int:
        """How many consecutive pulls may serve from the last fetch while
        the owner is unreachable: the strategy's staleness bound when one
        is declared, else the async pacing lag (``ADT_PS_MAX_LAG``) —
        past it the values are staler than anything the strategy ever
        promised, and the pull must fail instead."""
        from autodist_tpu import const as _const
        return max(self.max_staleness(), _const.ENV.ADT_PS_MAX_LAG.val)

    def _serve_stale(self, host: str, grp: dict, err: OSError):
        """Graceful degradation for a worker that cannot reach an owner:
        serve the LAST fetched values for up to ``_degraded_bound()``
        consecutive pulls — a service blip shorter than the window is
        invisible to training, and the resilient client reconnects on
        its own schedule. None = window exhausted (caller fails
        loudly)."""
        bound = self._degraded_bound()
        cached = grp.get("last_fetch")
        used = grp.get("degraded", 0)
        if cached is None or used >= bound:
            return None
        grp["degraded"] = used + 1
        self.stats["degraded_pulls"] += 1
        tel.counter_add("ps.degraded_pulls")
        tel.instant("ps.degraded_pull", "ps", host=host,
                    used=used + 1, bound=bound)
        # no service.reconnect() here: the resilient client reconnects
        # internally, and dropping it would discard its circuit-breaker
        # state — every degraded pull would re-pay the full retry budget
        # instead of failing fast into this window
        logging.warning(
            "async PS: owner %s unreachable (%s); serving last-fetched "
            "values (degraded pull %d/%d)", host, err, used + 1, bound)
        return cached

    def _assemble(self, shard_vals: Dict[str, Dict[int, np.ndarray]]
                  ) -> Dict[str, np.ndarray]:
        """Reassemble full variables from per-shard pieces (possibly
        published by different owners), in plan shard order. Missing
        shards fall back to the local mirror (pre-publish window)."""
        out = {}
        for name, plan in self.plans.items():
            n_shards = len(plan.shard_ranges()) if plan.partitioned else 1
            pieces = []
            for si in range(n_shards):
                arr = shard_vals.get(name, {}).get(si)
                if arr is None:
                    with self._lock:
                        arr = np.asarray(self._values[name][si])
                pieces.append(np.asarray(arr))
            out[name] = (pieces[0] if n_shards == 1
                         else np.concatenate(pieces, axis=plan.axis))
        return out

    def push(self, grads: Dict[str, Any]) -> None:
        """Hand mean-reduced gradients to the PS. Mirror (sync) mode applies
        locally — every process replays the identical deterministic update.
        Serving (async) mode packs each owner group's gradients into a blob
        and enqueues it on the owner's queue; the owner's apply thread
        applies gradients one at a time (no barrier)."""
        # epoch fence at the STORE boundary (runtime/elastic.py) — before
        # any D2H work, so a zombie's push is rejected at zero cost and
        # never reaches an owner queue its replacement is draining
        from autodist_tpu.runtime import elastic
        elastic.maybe_fence("ps.push")
        with tel.span("ps.push", "ps",
                      serving=self._serve_groups is not None,
                      step=self.stats["pushes"]):
            self._push_impl(grads)
        tel.counter_add("ps.pushes")

    def _grad_to_host(self, name: str, g, count_bytes: bool = True):
        """D2H one pushed gradient at the store boundary. Dense arrays and
        sparse (ids, values) pairs pass through; a wire-quantized gradient
        arrives as its ``{"q", "s"}`` container (int8 + scales — the D2H
        transfer the push actually paid), is counted at its true wire
        width, and dequantizes HERE — the store never sees int8."""
        if isinstance(g, dict):
            from autodist_tpu.parallel import collectives
            w = {k: np.asarray(jax.device_get(v)) for k, v in g.items()}
            qb = int(w["q"].nbytes + w["s"].nbytes)
            info = self._var_infos[name]
            host = collectives.dequant_wire_np(w, tuple(info.shape),
                                               np.dtype(info.dtype))
            if count_bytes:
                self.stats["bytes_pushed"] += qb
            tel.counter_add("wire.bytes_quantized", qb)
            tel.counter_add("wire.bytes_saved", host.nbytes - qb)
            return host
        if isinstance(g, tuple):
            pair = tuple(np.asarray(jax.device_get(x)) for x in g)
            if count_bytes:
                self.stats["bytes_pushed"] += sum(x.nbytes for x in pair)
            return pair
        arr = np.asarray(jax.device_get(g))
        if count_bytes:
            self.stats["bytes_pushed"] += arr.nbytes
        return arr

    def _push_impl(self, grads: Dict[str, Any]) -> None:
        bytes0 = self.stats["bytes_pushed"]
        drops0 = self.stats.get("dropped_pushes", 0)
        if self._serve_groups is None:
            if self.any_async() and not self._warned_sync_fallback:
                self._warned_sync_fallback = True
                logging.warning(
                    "async PS (sync=False) requested but serving is not "
                    "wired (no AutoDist async build); applying synchronously")
            host_grads = {name: self._grad_to_host(name, g)
                          for name, g in grads.items()}
            self.apply_local(host_grads)
        else:
            from autodist_tpu.runtime import ps_service as pss
            host_grads: Dict[str, Any] = {}  # one D2H transfer per var

            def fetch(name):
                if name not in host_grads:
                    # serving counts its network blobs below; the D2H leg
                    # only credits the wire counters
                    host_grads[name] = self._grad_to_host(
                        name, grads[name], count_bytes=False)
                return host_grads[name]

            for host, grp in self._serve_groups.items():
                payload = {}
                for name, si in grp["pairs"]:
                    if name not in grads:
                        continue
                    g = fetch(name)
                    plan = self.plans[name]
                    if isinstance(g, tuple):
                        # sparse (ids, values): one whole pair per owner
                        # group — the owner scatter-applies only into its
                        # own shard index ranges (shard_filter)
                        payload[name + "#idx"] = g[0]
                        payload[name + "#vals"] = g[1]
                    elif plan.partitioned:
                        # ship only this owner's slice of the gradient
                        payload["%s::%d" % (name, si)] = self._shard_slice(
                            plan, si, g)
                    else:
                        payload["%s::0" % name] = g
                if not payload:
                    continue
                blob = pss.pack_arrays(payload)
                # backpressure BEFORE the push: an unbounded queue lets a
                # fast worker stack gradients computed at ever-staler values
                # (and diverge), and a dead owner would grow its queue
                # without bound. The reference's async apply sat in the
                # step's critical path; here the bound is explicit: at most
                # ADT_PS_MAX_LAG blobs in flight (0 = unbounded, pure
                # async). On timeout the push is DROPPED (counted in
                # stats["dropped_pushes"]) — the watchdog/DEADLIST plane is
                # what kills the job if the owner is really gone.
                from autodist_tpu import const as _const
                max_lag = _const.ENV.ADT_PS_MAX_LAG.val
                try:
                    if max_lag > 0:
                        deadline = time.monotonic() + 60.0
                        stuck = False
                        while grp["service"].pending_grads() >= max_lag:
                            if time.monotonic() > deadline:
                                logging.warning(
                                    "async PS: owner %s queue stuck at max "
                                    "lag; dropping this push", host)
                                stuck = True
                                break
                            time.sleep(0.001)
                        if stuck:
                            self.stats["dropped_pushes"] = (
                                self.stats.get("dropped_pushes", 0) + 1)
                            continue
                    grp["service"].push_grads(blob)
                except OSError as e:
                    # transport blip: a dropped async gradient is legal
                    # (same semantics as backpressure drops) — but only
                    # within the degraded window; past it the owner is
                    # gone for real and the job must fail loudly
                    used = grp.get("push_failures", 0) + 1
                    bound = self._degraded_bound()
                    if used > bound:
                        raise RuntimeError(
                            "async PS: pushes to owner %s failed %d "
                            "consecutive times — aborting instead of "
                            "silently training without gradient exchange "
                            "(%s)" % (host, used, e)) from e
                    grp["push_failures"] = used
                    self.stats["dropped_pushes"] = (
                        self.stats.get("dropped_pushes", 0) + 1)
                    # no reconnect() kick: see _serve_stale — it would
                    # reset the resilient client's circuit breaker
                    logging.warning(
                        "async PS: push to owner %s failed (%s); dropped "
                        "this gradient (consecutive failure %d/%d)",
                        host, e, used, bound)
                    continue
                grp["push_failures"] = 0
                self.stats["bytes_pushed"] += len(blob)
            self._my_pushes += 1
        self.stats["pushes"] += 1
        tel.counter_add("ps.bytes_pushed",
                        self.stats["bytes_pushed"] - bytes0)
        dropped = self.stats.get("dropped_pushes", 0) - drops0
        if dropped:
            tel.counter_add("ps.dropped_pushes", dropped)

    def apply_local(self, grads: Dict[str, Any], shard_filter=None) -> None:
        """The PS-side update op: apply gradients to the resident shards
        through the optimizer, on the host CPU. Gradients arrive as full
        dense arrays (mirror mode), pre-sliced ``name::si`` shard slices
        (per-shard serving pushes), or sparse ``(indices, values)`` pairs
        — also their packed ``name#idx``/``name#vals`` wire form —
        scatter-added into the shard's index range (the reference's
        IndexedSlices split, ``kernel/partitioner.py:660-684``).
        ``shard_filter`` restricts the apply to the given (name, si) set
        — an owner loop touches only the shards it owns."""
        items: Dict[str, Any] = {}
        slices: Dict[str, Dict[int, Any]] = {}
        for name, g in grads.items():
            if name.endswith("#idx"):
                base = name[:-4]
                items[base] = (g, grads[base + "#vals"])
            elif name.endswith("#vals"):
                continue
            elif ("::" in name and name not in self.plans
                  and name.rsplit("::", 1)[0] in self.plans
                  and name.rsplit("::", 1)[1].isdigit()):
                # wire shard-slice key; a real variable literally named
                # "w::1" is in self.plans itself and takes the dense branch
                base, si = name.rsplit("::", 1)
                slices.setdefault(base, {})[int(si)] = g
            else:
                items[name] = g
        with jax.default_device(self._cpu):
            # collect every (var, shard) then apply in ONE jitted dispatch
            shards, opts, gshards, order = {}, {}, {}, []

            def add(name, si, gs):
                key = "%s::%d" % (name, si)
                shards[key] = jnp.asarray(self._values[name][si])
                opts[key] = self._opt[name][si]
                gshards[key] = jnp.asarray(gs)
                order.append((name, si, key))

            for name, g in items.items():
                plan = self.plans[name]
                if isinstance(g, tuple):
                    g = self._densify(name, plan, g)
                else:
                    g = np.asarray(g)
                for si in range(len(plan.shard_ranges())):
                    if shard_filter is not None \
                            and (name, si) not in shard_filter:
                        continue
                    gs = (self._shard_slice(plan, si, g)
                          if plan.partitioned else g)
                    add(name, si, gs)
            for name, by_si in slices.items():
                for si, gs in sorted(by_si.items()):
                    if shard_filter is not None \
                            and (name, si) not in shard_filter:
                        continue
                    add(name, si, np.asarray(gs))
            if not order:
                return
            with tel.span("ps.apply", "ps", shards=len(order)):
                new_vals, new_opts = self._apply_sharded(shards, opts,
                                                         gshards)
            tel.counter_add("ps.applies", len(order))
            per_var: Dict[str, Dict[int, Tuple]] = {}
            for name, si, key in order:
                per_var.setdefault(name, {})[si] = (
                    np.asarray(new_vals[key]), new_opts[key])
            for name, by_si in per_var.items():
                # swap the var's updated shards in one locked mutation;
                # shards owned by OTHER processes are left untouched
                # (per-shard ownership — their owners update them)
                with self._lock:
                    vlist = list(self._values[name])
                    olist = list(self._opt[name])
                    for si, (v, o) in by_si.items():
                        vlist[si], olist[si] = v, o
                    self._values[name] = vlist
                    self._opt[name] = olist
                self.stats["applies"] += 1

    # ---------------------------------------------------- async PS serving

    def enable_serving(self, service_for_host, my_host: str) -> None:
        """Switch to serving (async) mode: variables are grouped by owner
        host (``reduction_destination``); this process runs an apply loop
        for the groups it owns and fetches the rest over the service — the
        reference's sharded-PS deployment (one PS task per destination,
        ``ps_synchronizer.py:636-762``). May be called before
        ``init_params``; owner loops start once values exist."""
        self._serve_config = (service_for_host, my_host)
        if self._values:
            self._start_serving()

    def _start_serving(self) -> None:
        """Group by owner host PER SHARD (``reduction_destination`` is
        per-shard in the plan): a partitioned variable's shards can be
        owned — stored, applied, published — by different hosts, exactly
        the reference's sharded-PS task placement
        (``ps_synchronizer.py:636-762``). Pulls reassemble each variable
        across its owners' published blobs."""
        from autodist_tpu.runtime import ps_service as pss
        service_for_host, my_host = self._serve_config
        if self._serve_groups is not None:  # re-init: restart owner loops
            self.close()
        groups: Dict[str, list] = {}
        for name, plan in sorted(self.plans.items()):
            for si, dest in enumerate(plan.destinations):
                host = dest.split(":")[0] if dest else my_host
                groups.setdefault(host, []).append((name, si))
        self._serve_groups = {}
        for host, pairs in sorted(groups.items()):
            svc = service_for_host(host)
            owned = (host == my_host)
            grp = {"pairs": sorted(pairs), "service": svc, "owned": owned,
                   "worker": None}
            if owned:
                shard_set = frozenset(grp["pairs"])
                # values ride the HOT channel (fetched by every worker's
                # per-step pull); the optimizer moments publish on the
                # side channel, fetched only at checkpoint time — under
                # Adam this cuts the per-step serving wire ~3x
                grp["worker"] = pss.AsyncPSWorker(
                    svc,
                    functools.partial(self.apply_local,
                                      shard_filter=shard_set),
                    functools.partial(self._local_shard_blobs,
                                      grp["pairs"]),
                    opt_fn=functools.partial(self._local_opt_blobs,
                                             grp["pairs"])).start()
            self._serve_groups[host] = grp
        logging.info("async PS serving: %d owner groups, this process (%s) "
                     "owns %s", len(self._serve_groups), my_host,
                     [h for h, g in self._serve_groups.items() if g["owned"]])

    def _local_shard_blobs(self, pairs,
                           with_opt: bool = False) -> Dict[str, np.ndarray]:
        """{'name::si': shard value} for the given (name, si) pairs — the
        owner's publish payload (only the shards it owns). With
        ``with_opt``, the shard's optimizer-state leaves ride along as
        ``name::si!<leaf>`` (single-blob form; serving publishes them on
        the separate opt channel instead, see ``_local_opt_blobs``)."""
        from autodist_tpu.kernel.common import variable_utils
        out = {}
        with self._lock:
            for name, si in pairs:
                key = "%s::%d" % (name, si)
                out[key] = np.asarray(self._values[name][si])
                if with_opt:
                    names, leaves, _ = variable_utils.flatten_named(
                        self._opt[name][si])
                    for ln, leaf in zip(names, leaves):
                        out["%s!%s" % (key, ln)] = np.asarray(leaf)
        return out

    def _local_opt_blobs(self, pairs) -> Dict[str, np.ndarray]:
        """{'name::si!leaf': opt leaf} for the owned (name, si) pairs —
        the optimizer-state side channel a chief-side checkpoint reads to
        reconstruct a COMPLETE opt state for shards it does not own
        (per-shard ownership means no single process applies to every
        shard — without the wire, peer shards' moments would silently
        checkpoint as their frozen local init)."""
        from autodist_tpu.kernel.common import variable_utils
        out = {}
        with self._lock:
            for name, si in pairs:
                key = "%s::%d" % (name, si)
                names, leaves, _ = variable_utils.flatten_named(
                    self._opt[name][si])
                for ln, leaf in zip(names, leaves):
                    out["%s!%s" % (key, ln)] = np.asarray(leaf)
        return out

    @property
    def serving(self) -> bool:
        return self._serve_groups is not None

    def owner_health_errors(self) -> List[Tuple[str, str]]:
        """(host, error) for every owner apply loop of THIS process that
        is dead or past its reconnect budget. Non-empty means gradients
        pushed to those groups are never applied again — the Runner
        checks this every step and fails the job loudly (the silent-stall
        alternative is the one forbidden outcome)."""
        out: List[Tuple[str, str]] = []
        if self._serve_groups is None:
            return out
        for host, grp in self._serve_groups.items():
            w = grp["worker"]
            if w is not None and not w.healthy:
                out.append((host, str(w.last_error or
                                      "apply thread died unexpectedly")))
        return out

    def applied_total(self) -> int:
        """Gradient blobs applied by this process's owner loops."""
        if self._serve_groups is None:
            return self.stats["applies"]
        return sum(g["worker"].applied for g in self._serve_groups.values()
                   if g["worker"] is not None)

    def drain(self, timeout: float = 30.0) -> None:
        """Wait for this process's owner queues to empty (checkpoints)."""
        if self._serve_groups is None:
            return
        for grp in self._serve_groups.values():
            if grp["worker"] is not None:
                grp["worker"].drain(timeout)

    def close(self) -> None:
        # stop the owner apply loops BEFORE shutting the apply pool: a
        # still-running worker mid-apply_local would lazily rebuild a
        # fresh pool after its shutdown, leaking threads forever
        if self._serve_groups is not None:
            for grp in self._serve_groups.values():
                stopped = True
                if grp["worker"] is not None:
                    stopped = grp["worker"].stop()
                if stopped:
                    grp["service"].close()
                else:
                    # the apply thread is wedged (slow apply / stalled
                    # recv); leaking its socket beats yanking it out from
                    # under a live thread mid-publish
                    logging.warning("PS owner apply thread did not stop; "
                                    "leaving its service open")
        if self._apply_pool is not None:
            self._apply_pool.shutdown(wait=True)
            self._apply_pool = None

    def _densify(self, name: str, plan: PSVarPlan, pair) -> np.ndarray:
        """(indices, values) -> dense mean gradient for the full var.
        Wire accounting happens at the push site (idx+vals are what crossed
        the wire), not here."""
        idx, vals = pair
        idx = np.asarray(jax.device_get(idx)).reshape(-1)
        vals = np.asarray(jax.device_get(vals))
        vals = vals.reshape(idx.shape[0], -1)
        shape = tuple(self._var_infos[name].shape)
        dense = np.zeros(shape, vals.dtype).reshape(shape[0], -1)
        np.add.at(dense, idx, vals)
        return dense.reshape(shape)

    # ---------------------------------------------------------- checkpoints

    def full_values(self) -> Dict[str, np.ndarray]:
        """Like :meth:`pull` but for checkpoints — does not count as wire.
        In serving mode, non-owned groups come from the owner's latest
        published version (the authoritative copy); the local stale mirror
        is only the fallback when the owner has not published."""
        if self._serve_groups is None:
            return self._local_full()
        from autodist_tpu.runtime import ps_service as pss
        shard_vals: Dict[str, Dict[int, np.ndarray]] = {}
        for host, grp in self._serve_groups.items():
            if grp["owned"]:
                blobs = self._local_shard_blobs(grp["pairs"])
            else:
                res = grp["service"].fetch()
                if res is None:
                    continue  # pre-publish: _assemble falls back to mirror
                blobs = pss.unpack_arrays(res[1])
            for key, arr in blobs.items():
                if "!" in key:
                    continue  # opt-state leaf (checkpoint wire)
                name, si = key.rsplit("::", 1)
                shard_vals.setdefault(name, {})[int(si)] = arr
        return self._assemble(shard_vals)

    def checkpoint_pairs(self, is_chief: bool) -> List[Tuple[str, int]]:
        """(var, shard) pairs THIS process writes in a sharded checkpoint.
        Serving (async) mode: the shards this process owns — its local
        state is the authoritative copy for exactly those. Mirror (sync)
        mode: every process holds identical state, so the chief writes all
        of them and everyone else none."""
        if self._serve_groups is not None:
            out: List[Tuple[str, int]] = []
            for grp in self._serve_groups.values():
                if grp["owned"]:
                    out.extend(grp["pairs"])
            return sorted(out)
        if not is_chief:
            return []
        out = []
        for name, plan in sorted(self.plans.items()):
            n = len(plan.shard_ranges()) if plan.partitioned else 1
            out.extend((name, si) for si in range(n))
        return out

    def shard_state(self, name: str, si: int
                    ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """(value, flattened opt-state leaves) of one shard — an atomic
        snapshot vs the async apply thread."""
        from autodist_tpu.kernel.common import variable_utils
        with self._lock:
            value = np.asarray(self._values[name][si])
            names, leaves, _ = variable_utils.flatten_named(
                self._opt[name][si])
            opt_flat = {n: np.asarray(l) for n, l in zip(names, leaves)}
        return value, opt_flat

    def load_shard_states(self, provider) -> None:
        """Reload every shard from ``provider(name, si) -> (value,
        opt_flat)`` — the sharded-checkpoint restore. All shards load in
        every process (owned ones authoritative; the rest seed the mirror
        that pre-publish pulls fall back to). Unknown opt leaves keep the
        fresh init with a warning, matching :meth:`load_opt_from_full`.

        In serving mode the owner apply loops are PAUSED across the swap:
        an apply interleaved with the reload would mutate a mix of
        restored and pre-restore shards. Gradients queued meanwhile stay
        queued and land after resume — stale-but-legal async grads."""
        from autodist_tpu.kernel.common import variable_utils
        workers = []
        if self._serve_groups is not None:
            workers = [g["worker"] for g in self._serve_groups.values()
                       if g["worker"] is not None]
        for w in workers:
            w.pause()
        try:
            with jax.default_device(self._cpu):
                for name, plan in sorted(self.plans.items()):
                    n = len(plan.shard_ranges()) if plan.partitioned else 1
                    new_vals, new_opts = [], []
                    for si in range(n):
                        value, opt_flat = provider(name, si)
                        value = np.asarray(value)
                        template = self._optimizer.init(
                            {"v": jnp.asarray(value)})
                        t_names, t_leaves, t_def = (
                            variable_utils.flatten_named(template))
                        out = []
                        for tn, tl in zip(t_names, t_leaves):
                            src = opt_flat.get(tn)
                            if src is None:
                                logging.warning(
                                    "PS sharded restore: opt leaf %r for "
                                    "%s[%d] not in checkpoint; keeping "
                                    "fresh init", tn, name, si)
                                out.append(tl)
                            else:
                                out.append(jnp.asarray(np.asarray(src)))
                        new_vals.append(value)
                        new_opts.append(
                            variable_utils.unflatten_named(t_def, out))
                    with self._lock:
                        self._values[name] = new_vals
                        self._opt[name] = new_opts
            # republish so peers' first post-restore pull sees the restored
            # values instead of the owner's pre-restore published blob
            for w in workers:
                w.publish_now()
        finally:
            for w in workers:
                w.resume()
        if self._serve_config is not None and self._serve_groups is None:
            # serving was requested before any values existed (the
            # ADT_AUTO_RESUME path restores through the sharded format
            # BEFORE init_params ever runs): activate it now, or the job
            # would silently train disconnected local mirrors — no owner
            # loops, no cross-process exchange — with only the
            # "serving is not wired" warning as a symptom
            self._start_serving()

    def full_little_opt(self, name: str):
        """One variable's optimizer state as a FULL-variable little tree
        (the ``optimizer.init({'v': full_value})`` structure) assembled
        from the per-shard states: var-shaped leaves concatenate along the
        plan axis, shared (count-like) leaves come from shard 0. This is
        the fused engine's device carry — the inverse direction of
        :meth:`absorb_device_state`."""
        plan = self.plans[name]
        with self._lock:  # atomic snapshot vs the apply thread's swap
            states = list(self._opt[name])
        if not plan.partitioned:
            return jax.tree_util.tree_map(np.asarray, states[0])
        shard_dims = plan.shard_sizes

        def merge(*leaves):
            arrs = [np.asarray(l) for l in leaves]
            if (arrs[0].ndim > plan.axis
                    and tuple(a.shape[plan.axis] for a in arrs) == shard_dims):
                return np.concatenate(arrs, axis=plan.axis)
            return arrs[0]
        return jax.tree_util.tree_map(merge, *states)

    def absorb_device_state(self, values: Dict[str, Any],
                            opt_states: Dict[str, Any]) -> None:
        """Take ownership of post-superstep state computed ON DEVICE by the
        fused multi-step engine: full values split by true shard ranges,
        full little-tree optimizer states sliced per shard (var-shaped
        leaves along the plan axis; shared leaves copied whole — the same
        slicing rule as :meth:`load_opt_from_full`). One writeback replaces
        k per-microstep pushes; the wire accounting reflects that."""
        bytes0 = self.stats["bytes_pushed"]
        with tel.span("ps.absorb", "ps", vars=len(values)), \
                jax.default_device(self._cpu):
            for name, full in values.items():
                plan = self.plans[name]
                info = self._var_infos[name]
                full = np.asarray(jax.device_get(full))
                new_vals = self._split(plan, full)
                self.stats["bytes_pushed"] += full.nbytes
                new_opts = []
                for si in range(len(plan.shard_ranges())):
                    def slice_leaf(leaf, _si=si):
                        a = np.asarray(jax.device_get(leaf))
                        if (plan.partitioned and a.ndim > plan.axis
                                and a.shape[plan.axis]
                                == info.shape[plan.axis]):
                            a = self._shard_slice(plan, _si, a)
                        return jnp.asarray(a)
                    new_opts.append(jax.tree_util.tree_map(
                        slice_leaf, opt_states[name]))
                with self._lock:
                    self._values[name] = new_vals
                    self._opt[name] = new_opts
                self.stats["applies"] += 1
        if values:
            self.stats["pushes"] += 1
            tel.counter_add("ps.pushes")
            tel.counter_add("ps.bytes_pushed",
                            self.stats["bytes_pushed"] - bytes0)

    def full_opt_leaf(self, slot_path: str, var_name: str):
        """Reconstruct one optimizer-state subtree in the var's full layout
        (for original-layout checkpoints): concat var-sliced leaves across
        shards, take shard 0 for shared leaves. ``slot_path`` is the hole's
        position in the full opt tree, e.g. ``0/mu/<var_name>``."""
        plan = self.plans[var_name]
        with self._lock:  # atomic snapshot vs the apply thread's swap
            states = list(self._opt[var_name])
        if self._serve_groups is not None:
            # per-shard ownership: this process's local opt state is only
            # authoritative for the shards it owns; peer-owned shards'
            # moments come off the owner's opt side channel (the
            # ::si!leaf keys published with every apply, fetched only
            # here — never by the per-step value pulls)
            states = [self._remote_opt_state(var_name, si, st)
                      for si, st in enumerate(states)]
        # the per-shard little trees hold the same subtree under ".../v"
        prefix = slot_path[: -len(var_name)].rstrip("/")
        sub0 = self._subtree_at(states[0], prefix)
        if sub0 is None:
            raise KeyError("PS store has no opt slot %r for %s"
                           % (slot_path, var_name))
        if not plan.partitioned:
            return jax.tree_util.tree_map(lambda x: np.asarray(x), sub0)
        subs = [self._subtree_at(s, prefix) for s in states]
        shard_dims = plan.shard_sizes

        def merge(*leaves):
            arrs = [np.asarray(l) for l in leaves]
            a0 = arrs[0]
            if (a0.ndim > plan.axis
                    and tuple(a.shape[plan.axis] for a in arrs) == shard_dims):
                return np.concatenate(arrs, axis=plan.axis)
            return a0  # shared (count-like) leaf
        return jax.tree_util.tree_map(merge, *subs)

    def _remote_opt_state(self, var_name: str, si: int, local_state):
        """The authoritative little-tree opt state for one shard: local
        when this process owns the shard, else rebuilt from the owner's
        latest published ``name::si!leaf`` entries (falling back to the
        local state pre-publish). The local state provides the tree
        structure; leaves are filled by flattened name."""
        from autodist_tpu.kernel.common import variable_utils
        from autodist_tpu.runtime import ps_service as pss
        for grp in self._serve_groups.values():
            if (var_name, si) not in grp["pairs"]:
                continue
            if grp["owned"]:
                return local_state
            res = grp["service"].fetch_opt()
            if res is None:
                return local_state  # owner pre-publish
            blobs = pss.unpack_arrays(res[1])
            want = "%s::%d!" % (var_name, si)
            remote = {k[len(want):]: v for k, v in blobs.items()
                      if k.startswith(want)}
            if not remote:
                return local_state  # older publish without opt leaves
            names, leaves, treedef = variable_utils.flatten_named(local_state)
            filled = [remote.get(n, leaf) for n, leaf in zip(names, leaves)]
            return variable_utils.unflatten_named(treedef, filled)
        return local_state

    @staticmethod
    def _subtree_at(little_tree, slot_prefix: str):
        """The subtree of a per-shard opt state at a slot path, where the
        little tree's var key is ``v``. slot_prefix '' means the leaf 'v'
        itself (optimizers whose whole state is var-shaped)."""
        from autodist_tpu.kernel.common import variable_utils
        # collect (name, leaf) then rebuild the subtree under prefix + "/v"
        target = (slot_prefix + "/v") if slot_prefix else "v"
        names, leaves, _ = variable_utils.flatten_named(little_tree)
        # exact leaf hit
        for n, l in zip(names, leaves):
            if n == target:
                return l
        # subtree hit: leaves under target/
        picked = [(n[len(target) + 1:], l) for n, l in zip(names, leaves)
                  if n.startswith(target + "/")]
        if not picked:
            return None
        return {n: l for n, l in picked}

    # ------------------------------------------------------------ accounting

    def mirror_digest(self) -> str:
        """Digest of all resident values — the sync multi-process
        consistency check. Every process's mirror must stay bit-identical
        (deterministic jitted CPU applies of the identical psum'd
        gradient); the Runner compares digests across processes via the
        coordination service every ``ADT_PS_MIRROR_CHECK_EVERY`` steps and
        fails fast on divergence (heterogeneous host codegen would
        otherwise silently fork the replicas). Mirror mode only: a serving
        store has one authoritative owner copy, so there is nothing to
        cross-check (and no consistent snapshot to hash under the apply
        thread)."""
        if self.serving:  # not an assert: must hold under python -O too
            raise RuntimeError("mirror_digest is for sync (mirror) mode")
        import hashlib
        h = hashlib.md5()
        for name in sorted(self._values):
            h.update(name.encode())
            for s in self._values[name]:
                h.update(np.ascontiguousarray(s).tobytes())
        return h.hexdigest()

    def resident_bytes(self) -> int:
        """Host bytes resident in this store (values only)."""
        return sum(int(s.nbytes) for shards in self._values.values()
                   for s in shards)

    def resident_bytes_by_destination(self) -> Dict[str, int]:
        """Per-owner byte loads (the PS load-balancing accounting)."""
        out: Dict[str, int] = {}
        for name, plan in self.plans.items():
            for dest, shard in zip(plan.destinations, self._values[name]):
                out[dest] = out.get(dest, 0) + int(shard.nbytes)
        return out

    @property
    def var_names(self):
        return sorted(self.plans)

    def max_staleness(self) -> int:
        return max((p.staleness for p in self.plans.values()), default=0)

    def any_async(self) -> bool:
        return any(not p.sync for p in self.plans.values())


# ----------------------------------------------------------------- pipeline


class PSPipeline:
    """Overlap the host-PS data path with compute (a TPU-native stand-in
    for the reference's TF dataflow runtime, which scheduled PS send/recv
    against compute implicitly, ``ps_synchronizer.py:171-176``).

    The serial baseline runs pull -> step -> device_get(grads) -> host
    apply, so a transfer-bound config pays compute + 2x PCIe per step.
    Here the push (D2H + optimizer apply) and the NEXT step's pull staging
    (H2D) run on one background worker:

    - **sync PS (exact)**: each step's job is get -> apply -> prefetch, and
      the next step's :meth:`values` waits for it — numerics are
      bit-identical to the serial path (same calls, same order). The whole
      job overlaps the main thread's dispatch latency, feed building, and
      user host code.
    - **staleness >= 1 or async serving**: the prefetch is issued BEFORE
      the apply, so the H2D rides alongside this step's compute and the
      apply + D2H ride alongside the next step's: step time ~=
      max(compute, transfer). Reads lag applies by exactly one — inside
      the declared staleness bound (and unordered-by-design under async).

    ``ADT_PS_OVERLAP=0`` restores the serial path.
    """

    def __init__(self, store: PSStore, mesh, stale_ok: bool):
        import concurrent.futures
        self._store = store
        self._mesh = mesh
        self._stale_ok = stale_ok
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="adt-ps-pipe")
        # stale mode runs pulls on their OWN lane so the next step's H2D
        # overlaps the previous push's D2H+apply (max(pull, push) instead
        # of pull+push); exact mode keeps one lane (strict order)
        self._pull_exec = (concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="adt-ps-pull")
            if stale_ok else self._exec)
        self._pending = None  # Future -> staged device values for next step
        self._push_pending = None  # stale mode: the push/apply future
        # staleness window: a read may lag at most this many applies (the
        # pull for step N+1 waits for push N-s before reading)
        self._window = max(1, store.max_staleness())
        import collections
        self._push_hist = collections.deque(maxlen=max(self._window, 1))

    def _pull_staged(self):
        from autodist_tpu.parallel.mesh import tree_to_mesh
        from jax.sharding import PartitionSpec as P
        return tree_to_mesh(self._mesh, self._store.pull(), P())

    def values(self):
        """Device-staged PS values for the step about to run. Consumes the
        prefetch when one is pending (in exact mode the prefetch job also
        carries the push, so waiting keeps sync semantics exact); cold
        start / post-eval does a fresh pull."""
        if self._pending is None:
            return self._pull_staged()
        fut, self._pending = self._pending, None
        return fut.result()

    def submit(self, ps_grads: Dict[str, Any], ok=None) -> None:
        """Queue this step's push and the next step's pull.

        Exact (sync) mode: one job, get -> apply -> prefetch, and the next
        ``values()`` waits for all of it — bit-identical to serial.

        Stale mode (staleness >= 1 / async serving): the pull rides its own
        lane and may read PRE-apply values (stale-by-one, and per-variable
        rather than tree-atomic — the store's per-var lock means a pull
        concurrent with an apply can see var A pre-apply and var B post-
        apply, exactly the per-variable consistency the reference's
        per-var PS queues gave).

        ``ok`` is the sentinel verdict device scalar riding the same
        dispatch as ``ps_grads``: the push job reads it (the one D2H a
        push pays anyway, in the worker thread — never blocking the main
        thread) and SUPPRESSES the apply when the step was judged
        unhealthy, so a poisoned gradient never reaches the store."""

        def _push_allowed() -> bool:
            if ok is None:
                return True
            if bool(np.asarray(jax.device_get(ok))):
                return True
            tel.counter_add("sentinel.ps_suppressed")
            logging.warning("sentinel: PS push suppressed (bad verdict)")
            return False

        if self._stale_ok:
            # bounded lag: the prefetched read may trail the newest apply
            # by at most the staleness window — the pull waits for the
            # push submitted `window` steps ago (None in the ramp-up)
            barrier = (self._push_hist[0]
                       if len(self._push_hist) >= self._window else None)

            def pull_job():
                if barrier is not None:
                    barrier.result()
                return self._pull_staged()
            self._pending = self._pull_exec.submit(pull_job)
            prev = self._push_pending

            def push_job():
                if prev is not None:
                    prev.result()        # pushes stay ordered
                if _push_allowed():
                    self._store.push(ps_grads)
            self._push_pending = self._exec.submit(push_job)
            self._push_hist.append(self._push_pending)
        else:
            def job():
                if _push_allowed():
                    self._store.push(ps_grads)
                return self._pull_staged()
            self._pending = self._exec.submit(job)

    def flush(self) -> None:
        """Wait for the in-flight push (checkpoints / gathers / digests
        read the store and must see every submitted gradient applied).
        The staged values stay pending for the next :meth:`values`."""
        if self._push_pending is not None:
            self._push_pending.result()
        if self._pending is not None and not self._stale_ok:
            self._pending.result()

    def invalidate(self) -> None:
        """Flush, then DISCARD the staged prefetch — the store's state was
        replaced out of band (checkpoint restore / re-init) and the staged
        values no longer reflect it."""
        self.flush()
        if self._pending is not None:
            self._pending.result()  # never abandon a running pull mid-flight
        self._pending = None

    def close(self) -> None:
        self.flush()
        self._exec.shutdown(wait=True)
        if self._pull_exec is not self._exec:
            self._pull_exec.shutdown(wait=True)
