"""GraphTransformer — lowers a compiled Strategy to an SPMD train step.

Analog of reference ``autodist/kernel/graph_transformer.py:28-92``. The
reference's pipeline — partition variables, replicate the graph, run each
variable's synchronizer ``in_graph_apply`` then ``between_graph_apply`` —
becomes, on TPU:

1. **Partition** (``kernel/partitioner.py``): assign per-variable storage
   layouts on the mesh.
2. **Replicate** (``kernel/replicator.py``): trivial under SPMD — the data
   axis of the mesh *is* the replica set; the batch is sharded along it.
3. **Synchronize**: each variable's synchronizer contributes the gradient
   collective (bucketed/compressed psum, or reduce-scatter for partitioned
   vars) inside one ``shard_map``-wrapped, jitted step function.

Everything is traced once and compiled by XLA — the whole "transformed
graph" is a single SPMD program per process, identical across processes
because every input to this lowering (strategy bytes, mesh order, bucket
order) is deterministic.
"""
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.kernel.partitioner import VariablePartitioner, VarLayout
from autodist_tpu.model_item import _normalize_path, shapes_of
from autodist_tpu.kernel.common import variable_utils
from autodist_tpu.kernel.synchronization.synchronizer import Synchronizer
from autodist_tpu.parallel import collectives
from autodist_tpu.parallel import ps as ps_lib
from autodist_tpu.strategy.base import Strategy
from autodist_tpu.telemetry import scopes as sc
from autodist_tpu.telemetry import spans as tel
from autodist_tpu.train_state import TrainState
from autodist_tpu.utils import logging


def _tree_map_layouts(f, tree, layout_tree):
    return jax.tree_util.tree_map(f, tree, layout_tree,
                                  is_leaf=lambda x: isinstance(x, VarLayout))


class ForwardProgram:
    """A compiled forward-only fetch program plus its per-leaf sharding
    classification (``DistributedStep.predict_program``).

    ``batch_mask`` mirrors the fetch tree with one bool per leaf: True
    for leaves the lowering sharded over the batch axes (per-example
    rows), False for replicated/reduced leaves. Serving's padded-row
    masking and per-request fan-out MUST consult it rather than compare
    output shapes — a replicated leaf whose leading dim happens to equal
    the bucket size would otherwise be sliced like per-example rows.

    Callable with the same ``(state, ps_vals, batch)`` signature as the
    underlying jitted function; ``_cache_size()`` exposes the jit
    cache's compiled-specialization count for the zero-recompile
    serving contract."""

    def __init__(self, fn: Callable, batch_mask):
        self.fn = fn
        self.batch_mask = batch_mask

    def __call__(self, state, ps_vals, batch):
        return self.fn(state, ps_vals, batch)

    def _cache_size(self) -> int:
        return self.fn._cache_size()


class DistributedStep:
    """The compiled distributed program (the reference's transformed
    GraphItem + WrappedSession rolled into one callable)."""

    def __init__(self, *, mesh: Mesh, step_fn: Callable, layouts: Dict[str, VarLayout],
                 layout_tree, strategy: Strategy, model_item, mesh_axis: str,
                 sync_state_init: Callable, metadata: Optional[dict] = None,
                 step_fn_nodonate: Optional[Callable] = None,
                 eval_fn: Optional[Callable] = None,
                 ps_store=None, holed_params_template=None,
                 fused_builder: Optional[Callable] = None,
                 forward_builder: Optional[Callable] = None,
                 decode_builder: Optional[Callable] = None,
                 zero_syncs: Optional[dict] = None):
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.all_axes = tuple(mesh.axis_names)
        self.seq_axis = strategy.graph_config.seq_axis
        self.seq_feed_keys = strategy.graph_config.seq_feed_keys
        self.batch_axes = tuple(strategy.graph_config.batch_axes or (mesh_axis,))
        self._step_fn = step_fn
        self._step_fn_nodonate = step_fn_nodonate or step_fn
        self._eval_fn = eval_fn
        self.layouts = layouts
        self._layout_tree = layout_tree
        self.strategy = strategy
        self.model_item = model_item
        self._sync_state_init = sync_state_init
        self.metadata = metadata or {}
        self.num_replicas = mesh.shape[mesh_axis]
        # host-offloaded PS: values + optimizer state for no-proxy PS vars
        # rest in the store (parallel/ps.py); the device state carries holes
        self.ps_store = ps_store
        self._holed_template = (holed_params_template
                                if holed_params_template is not None
                                else model_item.params)
        # fused multi-step engine: ``fused_builder(donate)`` returns a
        # jitted program scanning k microsteps over a stacked [k, ...]
        # batch (k is implicit in the input shape; XLA specializes per k)
        self._fused_builder = fused_builder
        self._fused_jits: Dict[bool, Callable] = {}
        # serving: ``forward_builder(serve_fn, donate_batch)`` lowers a
        # forward-only FETCH program (user-named per-example outputs, no
        # loss/grad/optimizer) — the inference engine's compile target;
        # jitted programs cache per (serve_fn, donate) so steady-state
        # serving re-dispatches, never re-lowers
        self._forward_builder = forward_builder
        self._predict_jits: Dict[tuple, Callable] = {}
        # decode serving: ``decode_builder(decode_fn, example_dstate)``
        # lowers ONE donated fixed-shape decode-step program (params + KV
        # caches + cursors -> next tokens + updated caches) — the
        # continuous-batching engine's compile target (serving/decode.py)
        self._decode_builder = decode_builder
        self._decode_jits: Dict[tuple, Callable] = {}
        # device-resident PS carry for the fused engine: full values +
        # per-var little-tree optimizer states, written back to the host
        # store only at sync points (flush_ps) instead of every step
        self._fused_ps_vals = None
        self._fused_ps_opt = None
        self._fused_ps_dirty = False
        # jitted-dispatch counter: one per __call__ / run_multi — the
        # honest "host round-trips per training job" number the
        # fused-parity tests assert on
        self.dispatches = 0
        # static per-microstep quantized-AR wire bytes (int8 payload +
        # scale sidecar, and their fp32 equivalent) from the lowering —
        # credited to the wire.* counters at each dispatch
        self._wire_q_step = float(
            self.metadata.get("wire_quant_bytes_per_step", 0.0))
        self._wire_fp_step = float(
            self.metadata.get("wire_fp32_bytes_per_step", 0.0))
        # ZeRO-sharded update: per-variable kernels (shard math shared by
        # the lowering, the checkpoint re-shard, and the byte
        # accounting), static per-step rs/ag payloads for the zero.*
        # counters, and the projected opt-state HBM saving as a gauge
        self.zero_syncs = dict(zero_syncs or {})
        self._zero_rs_step = float(
            self.metadata.get("zero_rs_bytes_per_step", 0.0))
        self._zero_ag_step = float(
            self.metadata.get("zero_ag_bytes_per_step", 0.0))
        saved = float(self.metadata.get("zero_hbm_saved_bytes", 0.0))
        if saved:
            tel.gauge_set("zero.hbm_saved_bytes", saved)
        # the groups of the gradient exchange, credited once per program
        # build where the program compiled with asynchronous collectives
        # (a program without them overlaps nothing; the counter is
        # pre-registered at zero, so scrapers see the key either way)
        if self.metadata.get("async_collectives"):
            tel.counter_add("overlap.buckets",
                            len(self.metadata.get("grad_sync_groups", ())))

    def _count_wire(self, microsteps: int = 1) -> None:
        if self._wire_q_step:
            tel.counter_add("wire.bytes_quantized",
                            self._wire_q_step * microsteps)
            tel.counter_add("wire.bytes_saved",
                            (self._wire_fp_step - self._wire_q_step)
                            * microsteps)
        if self._zero_rs_step or self._zero_ag_step:
            tel.counter_add("zero.rs_bytes", self._zero_rs_step * microsteps)
            tel.counter_add("zero.ag_bytes", self._zero_ag_step * microsteps)

    # ---------------------------------------------------------- ps data path

    @property
    def _ps_pipe(self):
        """Lazy PSPipeline (parallel/ps.py): overlaps the PS push (D2H +
        host apply) and the next pull's H2D staging with compute. None when
        there is no host-PS store or ``ADT_PS_OVERLAP=0`` (serial
        baseline)."""
        if not hasattr(self, "_ps_pipe_obj"):
            self._ps_pipe_obj = None
            if self.ps_store is not None and const.ENV.ADT_PS_OVERLAP.val:
                stale_ok = (self.ps_store.max_staleness() >= 1
                            or self.ps_store.any_async())
                self._ps_pipe_obj = ps_lib.PSPipeline(
                    self.ps_store, self.mesh, stale_ok)
        return self._ps_pipe_obj

    def pull_ps(self) -> dict:
        """Host -> device transfer of the current PS values (the per-step
        parameter read from the PS; empty when no var is host-resident).
        Public: eval loops pull once and reuse the snapshot across batches
        (``Runner.evaluate``). A dirty fused-superstep carry is written
        back to the store first, so the pull always reflects every
        microstep that ran."""
        if self.ps_store is None:
            return {}
        with tel.span("dstep.pull_ps", "dstep"):
            tel.counter_add("dstep.ps_pulls")
            self._flush_fused_ps()
            if self._ps_pipe is not None:
                return self._ps_pipe.values()
            from autodist_tpu.parallel.mesh import tree_to_mesh
            return tree_to_mesh(self.mesh, self.ps_store.pull(), P())

    # back-compat spelling (promoted to the public name above)
    _pull_ps = pull_ps

    def _push_ps(self, ps_grads: dict, ok=None) -> None:
        """Device -> host transfer of the reduced PS gradients + host-side
        optimizer apply (the PS update op). Pipelined when overlap is on.

        ``ok`` is the sentinel verdict riding the SAME dispatch the
        gradients came from (a device scalar): a bad verdict suppresses
        the push entirely — the PS never sees the poisoned gradient, and
        its optimizer state stays untouched. Reading the scalar costs
        nothing extra: the push path device_gets the gradients anyway,
        and the check runs in the pipeline's worker thread."""
        if self.ps_store is not None and ps_grads:
            if self._ps_pipe is not None:
                self._ps_pipe.submit(ps_grads, ok=ok)
            elif ok is not None and not bool(np.asarray(jax.device_get(ok))):
                tel.counter_add("sentinel.ps_suppressed")
                logging.warning("sentinel: PS push suppressed (bad verdict)")
            else:
                self.ps_store.push(ps_grads)

    @property
    def _ps_pipe_existing(self):
        """The pipeline ONLY if one is already constructed — flush/
        invalidate/close must never build a fresh pipeline (two executor
        threads + a staged pull) just to tear it down; only stepping
        (``_pull_ps`` via ``__call__``) constructs lazily."""
        return getattr(self, "_ps_pipe_obj", None)

    def flush_ps(self) -> None:
        """Wait for any in-flight pipelined push AND write back the fused
        engine's device-resident PS carry — every store read (checkpoint,
        gather, mirror digest) must see all submitted gradients applied."""
        if self.ps_store is None:
            return
        with tel.span("dstep.flush_ps", "dstep"):
            tel.counter_add("dstep.ps_flushes")
            if self._ps_pipe_existing is not None:
                self._ps_pipe_existing.flush()
            self._flush_fused_ps()

    def invalidate_ps(self) -> None:
        """Flush and discard the pipeline's staged values and the fused
        carry — call whenever the store's contents are replaced out of
        band (restore/re-init). The carry is DROPPED, not written back:
        out-of-band replacement means the store, not the carry, is now
        authoritative."""
        if self.ps_store is None:
            return
        self._fused_ps_vals = self._fused_ps_opt = None
        self._fused_ps_dirty = False
        if self._ps_pipe_existing is not None:
            self._ps_pipe_existing.invalidate()

    # ------------------------------------------------- fused multi-step

    def _ensure_fused_ps_carry(self):
        """Device-resident (values, opt-states) carry for the fused
        engine. First superstep (or first after a flush): land in-flight
        per-step pushes, then pull full values and per-var little-tree
        optimizer states from the store — ONE H2D transfer per fused run
        sequence instead of one per step."""
        if self.ps_store is None:
            return {}, {}
        if self._fused_ps_vals is None:
            with tel.span("dstep.pull_ps", "dstep", fused=True):
                tel.counter_add("dstep.ps_pulls")
                self.flush_ps()
                from autodist_tpu.parallel.mesh import tree_to_mesh
                # raw (unquantized) carry: the scan body applies the wire
                # codec per microstep itself, so the fused numerics match
                # the per-step quantized loop
                self._fused_ps_vals = tree_to_mesh(
                    self.mesh, self.ps_store.pull(wire=False), P())
                self._fused_ps_opt = tree_to_mesh(
                    self.mesh,
                    {n: self.ps_store.full_little_opt(n)
                     for n in self.ps_store.var_names}, P())
        return self._fused_ps_vals, self._fused_ps_opt

    def _flush_fused_ps(self) -> None:
        """Write the fused carry back to the host store (values + per-shard
        optimizer states) and drop it — the store is authoritative again.
        The per-step pipeline's staged pull predates the writeback, so it
        is invalidated too."""
        if not self._fused_ps_dirty:
            return
        vals, opt = self._fused_ps_vals, self._fused_ps_opt
        self._fused_ps_vals = self._fused_ps_opt = None
        self._fused_ps_dirty = False
        self.ps_store.absorb_device_state(jax.device_get(vals),
                                          jax.device_get(opt))
        if self._ps_pipe_existing is not None:
            self._ps_pipe_existing.invalidate()

    def _fused_fn(self, donate: bool = True) -> Callable:
        if self._fused_builder is None:
            raise NotImplementedError(
                "this DistributedStep was built without a fused-scan "
                "lowering path")
        if self.ps_store is not None and (
                self.ps_store.serving or self.ps_store.any_async()
                or self.ps_store.max_staleness() > 0):
            raise ValueError(
                "fused multi-step requires synchronous host-PS: async "
                "serving / staleness>0 let peers' applies land BETWEEN "
                "microsteps, which a scan compiled around a superstep-"
                "start snapshot cannot observe. Run per-step, or use "
                "sync=True staleness=0 PS (or an AllReduce strategy).")
        if (self.ps_store is not None and not self._fused_jits
                and any(p.partitioned for p in self.ps_store.plans.values())):
            # the host store applies the optimizer PER SHARD; the fused
            # device emulation applies it per FULL variable. Identical for
            # elementwise transforms (sgd/adam/...), but a shard-shape-
            # sensitive transform (per-tree norm clipping) would diverge —
            # say so once instead of silently changing numerics.
            logging.warning(
                "fused multi-step with a PARTITIONED host-PS store: the "
                "device emulation applies the optimizer per full variable "
                "while the per-step host path applies it per shard — "
                "identical for elementwise optimizers, but norm-based "
                "transforms (e.g. clip_by_global_norm) may differ from "
                "the per-step loop; verify parity for your optimizer")
        if donate not in self._fused_jits:
            self._fused_jits[donate] = self._fused_builder(donate)
        return self._fused_jits[donate]

    def multi_step(self, k: int, donate: bool = True) -> Callable:
        """The fused k-microstep program: ONE donated jitted dispatch
        running ``k`` steps under ``lax.scan`` over a stacked ``[k, ...]``
        batch. Gradient collectives, PS pull/push (device-emulated against
        the superstep-start snapshot, exact for sync PS), and optimizer
        applies all stay inside the program; metrics come back stacked
        ``[k, ...]`` once per superstep.

        Returns ``fused(state, ps_vals, ps_opt, stacked_batch) ->
        (new_state, new_ps_vals, new_ps_opt, stacked_metrics)``. Most
        callers want :meth:`run_multi`, which also manages the PS carry."""
        if k < 1:
            raise ValueError("multi_step needs k >= 1, got %d" % k)
        fn = self._fused_fn(donate)

        def fused(state, ps_vals, ps_opt, stacked_batch):
            lead = {int(np.shape(l)[0])
                    for l in jax.tree_util.tree_leaves(stacked_batch)}
            if lead and lead != {k}:
                raise ValueError(
                    "multi_step(k=%d) fed a stacked batch with leading "
                    "dim(s) %s" % (k, sorted(lead)))
            return fn(state, ps_vals, ps_opt, stacked_batch)
        return fused

    def run_multi(self, state: TrainState, stacked_batch,
                  donate: bool = True, step: Optional[int] = None):
        """Run one superstep (k = the stacked batch's leading dim) and
        manage the PS carry: pull once before the first superstep, keep
        values/opt device-resident across supersteps, write back only at
        ``flush_ps`` sync points. Returns ``(new_state, stacked_metrics)``
        with metrics still device-resident — the caller decides when to
        pay the readback."""
        fn = self._fused_fn(donate)  # validates BEFORE any carry pull
        lead = {int(np.shape(l)[0])
                for l in jax.tree_util.tree_leaves(stacked_batch)}
        if len(lead) > 1:
            # catch ragged hand-built stacks here (the main execution
            # path), not only in the multi_step() accessor — lax.scan's
            # own shape error would be cryptic
            raise ValueError(
                "stacked batch has mismatched leading (microstep) dims %s"
                % sorted(lead))
        with tel.span("dstep.dispatch", "dstep", fused=True, step=step):
            ps_vals, ps_opt = self._ensure_fused_ps_carry()
            new_state, new_vals, new_opt, metrics = fn(
                state, ps_vals, ps_opt, stacked_batch)
            if self.ps_store is not None:
                self._fused_ps_vals, self._fused_ps_opt = new_vals, new_opt
                self._fused_ps_dirty = True
            self.dispatches += 1
            tel.counter_add("dstep.dispatches")
            self._count_wire(next(iter(lead), 1))
            return new_state, metrics

    def close_ps(self) -> None:
        """Flush the pipeline, land the fused carry, and shut the
        executors down (Runner.close); a fresh pipeline is lazily created
        if stepping resumes. The carry writeback matters here for the
        same reason the pipeline flush does: a close right after fused
        supersteps must not silently discard their PS updates."""
        if self.ps_store is None:
            return
        if self._ps_pipe_existing is not None:
            self._ps_pipe_existing.close()
            # ``del`` (not ``= None``): the lazy property only constructs a
            # pipeline when the attribute is *missing*, so assigning None
            # would pin the serial path forever after a close.
            del self._ps_pipe_obj
        self._flush_fused_ps()

    def __call__(self, state: TrainState, batch, donate: bool = True,
                 step: Optional[int] = None):
        """Run one step. ``donate=True`` (default) consumes ``state``'s
        buffers — callers holding their own reference to the input state must
        pass ``donate=False``. ``step`` (the Runner's microstep index) only
        labels the dispatch span."""
        fn = self._step_fn if donate else self._step_fn_nodonate
        with tel.span("dstep.dispatch", "dstep", fused=False, step=step):
            ps_vals = self.pull_ps()
            new_state, ps_grads, metrics = fn(state, ps_vals, batch)
            # sentinel-guarded programs ship the verdict in the metrics;
            # it gates the PS push (the one update that happens host-side)
            ok = (metrics["sentinel"]["ok"]
                  if isinstance(metrics, dict) and "sentinel" in metrics
                  else None)
            self._push_ps(ps_grads, ok=ok)
            self.dispatches += 1
            tel.counter_add("dstep.dispatches")
            self._count_wire()
            return new_state, metrics

    def evaluate(self, state: TrainState, batch, ps_vals=None):
        """Forward-only metrics: no grads, no optimizer, no gradient
        collectives — ~3x cheaper than a train step. ``ps_vals`` lets an
        eval LOOP pull the host-PS values once and reuse them across
        batches (no push happens between eval batches, so per-batch
        re-pulls would be pure PCIe waste — 1 GB of store-resident
        params x 100 batches is 100 GB of transfer for unchanged
        values)."""
        if ps_vals is None:
            ps_vals = self.pull_ps()
        if self._eval_fn is None:
            _, _, metrics = self._step_fn_nodonate(state, ps_vals, batch)
            return metrics
        return self._eval_fn(state, ps_vals, batch)

    def predict_program(self, serve_fn: Callable,
                        donate_batch: bool = True,
                        example_batch=None) -> Callable:
        """The compiled forward-only FETCH program behind the serving
        engine (``autodist_tpu/serving/``): derived from the same
        gather-params + fill-PS-holes path :meth:`evaluate` runs, but
        returning ``serve_fn(full_params, batch)`` — the user's named
        per-example outputs — instead of aggregate metrics. No grads, no
        optimizer, no gradient collectives.

        ``donate_batch=True`` donates the batch buffers (the one input a
        serving dispatch truly consumes — the params/state are shared
        across every request), so XLA reuses the request's own memory for
        activations; callers that keep a reference to the placed batch
        must pass ``donate_batch=False`` (``Runner.predict`` does).

        Returns ``fn(state, ps_vals, batch) -> outputs``; outputs with a
        leading (local-)batch dim come back sharded over the batch axes
        — ``Remapper.remap_fetch`` reassembles the global batch — and
        scalar outputs come back pmean-reduced like eval metrics. The
        program is cached per ``(serve_fn, donate_batch, feed
        structure)``: XLA additionally specializes per batch shape, which
        is exactly the bucketed-shape discipline serving relies on for
        zero steady-state recompiles.

        ``example_batch`` fixes the FEED STRUCTURE (serving feeds are
        usually the training batch minus its labels); defaults to the
        model item's training batch structure."""
        if self._forward_builder is None:
            raise NotImplementedError(
                "this DistributedStep was built without a forward-program "
                "lowering path (step_fn capture mode hides the forward "
                "pass) — serving needs loss_fn mode")
        treedef = jax.tree_util.tree_structure(
            example_batch if example_batch is not None
            else self.model_item.example_batch)
        key = (serve_fn, bool(donate_batch), treedef)
        if key not in self._predict_jits:
            self._predict_jits[key] = self._forward_builder(
                serve_fn, bool(donate_batch), example_batch)
        return self._predict_jits[key]

    def decode_program(self, decode_fn: Callable,
                       example_dstate) -> Callable:
        """The compiled decode-STEP program behind continuous batching
        (``autodist_tpu/serving/decode.py``): like
        :meth:`predict_program` it gathers params and fills PS holes, but
        the second operand is the engine's slot-major decode state (KV
        caches ``[slots, ...]``, per-slot token/cursor/alive) rather than
        a request feed, and the state is ALWAYS donated — the returned
        caches alias the previous step's buffers, so steady-state decode
        holds one cache allocation regardless of slot churn.

        ``example_dstate`` fixes the state's structure and (fixed!)
        shapes; the program is cached per ``(decode_fn, structure)`` and
        XLA sees exactly one shape — the zero-recompile contract the
        decode engine asserts after warmup."""
        if self._decode_builder is None:
            raise NotImplementedError(
                "this DistributedStep was built without a decode-program "
                "lowering path (step_fn capture mode hides the forward "
                "pass) — continuous-batching decode needs loss_fn mode")
        treedef = jax.tree_util.tree_structure(example_dstate)
        key = (decode_fn, treedef)
        if key not in self._decode_jits:
            self._decode_jits[key] = self._decode_builder(
                decode_fn, example_dstate)
        return self._decode_jits[key]

    def snapshot_lowered(self, state: TrainState, batch):
        """Dump the transformed program's StableHLO (the reference's
        '3-transformed' TensorBoard snapshot, ``graph_transformer.py:90``)."""
        from autodist_tpu.utils import visualization_util
        try:
            text = self.lowered_text(state, batch)
            visualization_util.log_program("3-transformed-stablehlo", text,
                                           force=True)
        except Exception as e:  # noqa: BLE001 — diagnostics must not break runs
            logging.warning("snapshot_lowered failed: %s", e)

    def _ps_avals(self, with_opt: bool = False, wire: bool = True):
        """(value avals, little-tree optimizer-state avals) for the
        host-resident PS vars — lowering inputs that must not cost a real
        pull. The opt avals (one ``optimizer.init`` trace per var) are
        only materialized when asked for — the per-step lowering path
        never consumes them. ``wire=True`` mirrors the step path's entry
        structure (quantized vars enter as their {"q", "s"} containers);
        the fused program's carry is raw f32 (``wire=False``)."""
        if self.ps_store is None:
            return {}, {}
        infos = self.model_item.var_infos
        raw_avals = {n: jax.ShapeDtypeStruct(tuple(infos[n].shape),
                                             np.dtype(infos[n].dtype))
                     for n in self.ps_store.var_names}
        opt_avals = {}
        if with_opt:
            opt_avals = {n: jax.eval_shape(
                lambda a: self.model_item.optimizer.init({"v": a}), aval)
                for n, aval in raw_avals.items()}
        ps_avals = raw_avals
        if wire:
            quant = set(self.metadata.get("ps_wire_int8", ()))
            if quant:
                from autodist_tpu.parallel import collectives
                ps_avals = {
                    n: (collectives.wire_avals(tuple(infos[n].shape))
                        if n in quant else a)
                    for n, a in raw_avals.items()}
        return ps_avals, opt_avals

    def lowered_text(self, state: TrainState, batch, fuse_steps: int = 1,
                     program: str = "train", donate: bool = False) -> str:
        """StableHLO text of the compiled step (used by snapshots, tests
        asserting on collective structure, and the static analyzers in
        ``analysis/hlo.py``/``analysis/memory.py``). PS values enter as
        avals — lowering must not cost a real pull.

        ``program="eval"`` lowers the forward-only eval program (falling
        back to the train step when no eval lowering exists, e.g. step_fn
        mode). With ``fuse_steps=k > 1``, lowers the fused k-microstep
        scan program instead; ``batch`` must then be the stacked
        ``[k, ...]`` feed (real arrays or avals). ``donate=True`` lowers
        the donated variant — the one that actually runs in steady state
        — whose entry carry aliases its outputs (what the ADT503
        donation check and honest peak-HBM estimates need)."""
        if program not in ("train", "eval"):
            raise ValueError("program must be 'train' or 'eval', got %r"
                             % (program,))
        if program == "eval":
            ps_avals, _ = self._ps_avals()
            fn = (self._eval_fn if self._eval_fn is not None
                  else self._step_fn_nodonate)
            return fn.lower(state, ps_avals, batch).as_text()
        if fuse_steps > 1:
            ps_avals, opt_avals = self._ps_avals(with_opt=True, wire=False)
            return self._fused_fn(donate=donate).lower(
                state, ps_avals, opt_avals, batch).as_text()
        ps_avals, _ = self._ps_avals()
        fn = self._step_fn if donate else self._step_fn_nodonate
        return fn.lower(state, ps_avals, batch).as_text()

    # ------------------------------------------------------------- state mgmt

    def _put(self, value, pspec: P):
        from autodist_tpu.parallel.mesh import host_to_mesh
        return host_to_mesh(self.mesh, value, pspec)

    def place_sync_state(self, sync_state):
        """Compressor state onto the mesh in its storage layout (leading
        device axis over all mesh axes) — the ONE placement rule, shared
        by init_state and the cross-topology restore's reset path."""
        return jax.tree_util.tree_map(
            lambda arr: self._put(arr, P(self.all_axes)), sync_state)

    def init_state(self, params, opt_state=None, sync_state=None) -> TrainState:
        """Shard initial params/optimizer state into storage layout: PS
        leaves go to the host store; device leaves are padded (partitioned
        vars) and placed on the mesh. ``params``/``opt_state`` arrive in the
        ORIGINAL full layout (the checkpoint layout)."""
        item = self.model_item
        self.invalidate_ps()  # re-init replaces the store's contents
        if self.ps_store is not None and not ps_lib.holes_of(params):
            # host-resident leaves: values + per-shard optimizer state
            # (an already-holed input means re-init from a live state — the
            # store then keeps its current contents)
            self.ps_store.init_params(params)
            params = ps_lib.hole_like(self._holed_template, params)
            if opt_state is not None:
                self.ps_store.load_opt_from_full(opt_state)
                holed_opt_template = jax.eval_shape(item.optimizer.init,
                                                    self._holed_template)
                opt_state = ps_lib.hole_like(holed_opt_template, opt_state)
        if self.zero_syncs and item.optimizer is not None \
                and opt_state is not None:
            # ZeRO-sharded vars have no slot in the device optimizer tree
            # (their state lives sharded in sync_state['zero']); a full
            # (checkpoint-layout) opt_state is holed down to the device
            # basis — idempotent when already holed
            basis = ps_lib.hole_out_params(self._holed_template,
                                           frozenset(self.zero_syncs))
            opt_state = ps_lib.hole_like(
                jax.eval_shape(item.optimizer.init, basis), opt_state)
        if opt_state is None:
            # step_fn mode has no framework-owned optimizer: whatever
            # optimizer state exists lives inside the user's opaque state
            opt_state = (item.optimizer.init(
                ps_lib.hole_out_params(params, frozenset(self.zero_syncs))
                if self.zero_syncs else params)
                if item.optimizer is not None else {})
        # pad + place params. Device-resident leaves stay on device the
        # whole way: jnp.pad pads in an on-device op and _put reshards
        # device-side — np.pad would download every leaf first.
        def place_var(leaf, lay: VarLayout):
            padded = False
            # already-padded leaves (state re-initialized from a live placed
            # TrainState) must not be padded a second time
            if lay.partitioned and np.shape(leaf)[lay.axis] == lay.orig_dim:
                pad = [(0, 0)] * np.ndim(leaf)
                pad[lay.axis] = (0, lay.padded_dim - lay.orig_dim)
                if isinstance(leaf, jax.Array):
                    leaf = jnp.pad(leaf, pad)
                else:
                    leaf = np.pad(np.asarray(leaf), pad)
                padded = True
            if (not padded and isinstance(leaf, jax.Array)
                    and jax.process_count() == 1):
                # the TrainState must OWN fresh buffers: the step donates
                # them, and device_put may alias the caller's buffer —
                # not only on a matching-sharding no-op but ALSO when a
                # reshard reuses the source buffer as one of the output
                # shards (observed: SingleDevice -> 8-way replicated kept
                # the source as shard 0, and donation deleted the user's
                # params). No reliable aliasing predicate exists, so copy
                # unconditionally: jnp.copy is device-side (no host trip)
                # and transient per-leaf, not a whole-tree spike. Padding
                # and the multi-process callback path already copy.
                leaf = jnp.copy(leaf)
            return self._put(leaf, lay.pspec)
        params_placed = _tree_map_layouts(place_var, params, self._layout_tree)
        # optimizer state: match each leaf to its variable's layout
        opt_layout_tree = variable_utils.map_state_layouts(
            opt_state, item.var_infos, self.layouts, VarLayout(name=""))
        opt_placed = _tree_map_layouts(place_var, opt_state, opt_layout_tree)
        if sync_state is None:
            sync_state = self._sync_state_init()
        sync_placed = self.place_sync_state(sync_state)
        step0 = self._put(np.zeros((), np.int32), P())
        return TrainState(step=step0, params=params_placed,
                          opt_state=opt_placed, sync_state=sync_placed)

    def release_initial_params(self):
        """Keep the shapes of the initial parameters the build captured and
        let their arrays go (``ModelItem.params`` and the holed template,
        which is the same tree where nothing is host-resident)."""
        self.model_item.release_params()
        self._holed_template = shapes_of(self._holed_template)

    def gather_params(self, state: TrainState):
        """Params back in the original (full, unpadded) layout, on host —
        the reference's 'checkpoints load in vanilla TF' property
        (reference ``checkpoint/saver.py:50-57``). Host-resident PS values
        come straight from the store (the authoritative copy)."""
        gathered = self._gather_tree(state.params, self._layout_tree)
        if self.ps_store is not None:
            # flush the pipelined push, then apply any queued gradients this
            # process owns before reading (peers' in-flight grads are, by
            # async semantics, allowed to land after)
            self.flush_ps()
            self.ps_store.drain()
            gathered = ps_lib.fill_holes(gathered, self.ps_store.full_values())
        return gathered

    def gather_opt_state(self, state: TrainState):
        """Optimizer state in the original (full, unpadded) layout; PS
        vars' slots are reconstructed from the store's per-shard states."""
        from autodist_tpu.kernel.common import variable_utils
        layout_tree = variable_utils.map_state_layouts(
            state.opt_state, self.model_item.var_infos, self.layouts,
            VarLayout(name=""))
        gathered = self._gather_tree(state.opt_state, layout_tree)
        if self.ps_store is not None:
            # flush+drain before reading so the opt snapshot pairs with the
            # value snapshot gather_params takes (not torn across an apply)
            self.flush_ps()
            self.ps_store.drain()

            def ps_leaf(slot_path, var_name):
                if var_name in self.zero_syncs:
                    return ps_lib.PSHole(var_name)  # the zero pass fills it
                return self.ps_store.full_opt_leaf(slot_path, var_name)
            gathered = ps_lib.fill_holes_with_path(gathered, ps_leaf)
        if self.zero_syncs:
            # ZeRO-sharded slots reconstruct from the per-replica shards
            # in sync_state['zero'] (gathered host-side with the leading
            # device axis), concatenated in data-axis order — checkpoints
            # keep the reference's 'original full layout' property
            zero_host = self.gather_sync_state(state).get("zero", {})

            def zero_leaf(slot_path: str, var_name: str):
                zs = self.zero_syncs[var_name]
                little = zero_host[var_name]
                names, leaves, _ = variable_utils.flatten_named(little)
                flat = dict(zip(names, leaves))
                prefix = slot_path[: -len(var_name)].rstrip("/")
                key = (prefix + "/v") if prefix else "v"
                if key not in flat:
                    raise KeyError(
                        "sync_state['zero'] has no opt slot %r for %s"
                        % (slot_path, var_name))
                return zs.unshard_host(flat[key])
            gathered = ps_lib.fill_holes_with_path(gathered, zero_leaf)
        return gathered

    def gather_sync_state(self, state: TrainState):
        """Compressor state to host, keeping the leading device axis."""
        rep = jax.tree_util.tree_map(
            lambda _: NamedSharding(self.mesh, P()), state.sync_state)
        gathered = jax.jit(lambda s: s, out_shardings=rep)(state.sync_state)
        return jax.device_get(gathered)

    def _gather_tree(self, tree, layout_tree):
        rep = jax.tree_util.tree_map(lambda _: NamedSharding(self.mesh, P()), tree)
        gathered = jax.jit(
            lambda t: _tree_map_layouts(lambda leaf, lay: lay.unpad(leaf),
                                        t, layout_tree),
            out_shardings=rep)(tree)
        return jax.device_get(gathered)

    def shard_batch(self, batch):
        """Place a host-global batch onto the mesh, split along the data axis
        (delegates to the Remapper's validated feed path)."""
        from autodist_tpu.remapper import Remapper
        return Remapper(self.mesh, self.mesh_axis, seq_axis=self.seq_axis,
                        batch_axes=self.batch_axes,
                        seq_keys=self.seq_feed_keys).remap_feed(batch)


class GraphTransformer:
    """Builds the DistributedStep from (compiled strategy, mesh, model item)."""

    def __init__(self, compiled_strategy: Strategy, mesh: Mesh, model_item,
                 mesh_axis: str = const.DATA_AXIS, donate: bool = True,
                 sentinel=None):
        self._strategy = compiled_strategy
        self._mesh = mesh
        self._item = model_item
        # training health sentinel (runtime/sentinel.py SentinelPolicy):
        # when active, per-step health guards — global grad norm,
        # any-NaN/Inf over grads and post-update params, loss finiteness
        # — are compiled INTO the step and a bad verdict discards the
        # update in-graph; only ``grad_norm_limit`` is consumed here
        # (a trace-time constant), the rest drives the Runner's policy
        self._sentinel = sentinel
        # the data axis carries batch dim 0 and partitioned-var shards; any
        # further mesh axes (seq/...) replicate params and also reduce grads
        self._axis = mesh_axis if mesh_axis in mesh.axis_names else mesh.axis_names[0]
        self._axes = tuple(mesh.axis_names)
        self._donate = donate
        self.num_replicas = int(mesh.shape[self._axis])
        self.total_devices = int(np.prod([mesh.shape[a] for a in self._axes]))
        self._seq_axis = compiled_strategy.graph_config.seq_axis
        if self._seq_axis and self._seq_axis not in self._axes:
            raise ValueError("strategy seq_axis %r not in mesh axes %s"
                             % (self._seq_axis, self._axes))

    # ---------------------------------------------------------------- helpers

    def _replica_info(self):
        """Replication bookkeeping via the Replicator kernel (the
        reference's Partitioner -> Replicator -> Synchronizer pipeline)."""
        from autodist_tpu.kernel.replicator import Replicator
        batch_axes = tuple(
            self._strategy.graph_config.batch_axes or (self._axis,))
        return Replicator.apply(self._mesh, batch_axes, self._seq_axis,
                                self._strategy.graph_config.seq_feed_keys)

    def _local_batch_avals(self):
        """The example batch as one device sees it inside the step's
        shard_map. ReplicaInfo is the SAME source the shard_map in_specs
        use, so these shapes cannot disagree with the actual split."""
        rep = self._replica_info()

        def local_aval(path, leaf):
            return jax.ShapeDtypeStruct(
                rep.local_shape(np.shape(leaf), _normalize_path(path)),
                np.asarray(leaf).dtype
                if not hasattr(leaf, "dtype") else leaf.dtype)
        return jax.tree_util.tree_map_with_path(
            local_aval, self._item.example_batch)

    def _grad_ready_order(self, grad_jaxpr=None) -> Dict[str, int]:
        """Where the backward pass completes each variable's gradient
        (``collectives.grad_readiness``), on more than one replica only.
        ``grad_jaxpr``: the loss's gradient jaxpr where the lowering has
        traced one already (the sparse-wire safety check); otherwise one
        is traced here on the per-device shapes. Where the loss cannot
        be traced outside the step, the reverse position in the params
        tree stands in (later layers' gradients come first): the order
        only places collectives, it changes no value."""
        item = self._item
        names, _, _ = variable_utils.flatten_named(item.params)
        if grad_jaxpr is None:
            loss = ((lambda p, b: item.loss_fn(p, b)[0]) if item.has_aux
                    else item.loss_fn)
            from autodist_tpu.utils.axis_env import bound_axes
            try:
                with bound_axes():
                    grad_jaxpr = jax.make_jaxpr(jax.grad(loss))(
                        jax.eval_shape(lambda t: t, item.params),
                        self._local_batch_avals()).jaxpr
            except Exception as e:  # noqa: BLE001 — the order is best-effort
                logging.warning("gradient readiness not read from the "
                                "loss (%s); using reverse tree order", e)
                return {n: -i for i, n in enumerate(names)}
        return collectives.grad_readiness(grad_jaxpr, names)

    def _build_synchronizers(self, layouts, ps_names=frozenset(),
                             sparse_wire=frozenset(),
                             zero_names=frozenset()) -> Dict[str, Synchronizer]:
        """Per-variable synchronizer kernels from strategy node configs
        (reference ``graph_transformer.py:94-130``). Host-resident PS vars
        (``ps_names``) have no in-SPMD synchronizer — their gradient leaves
        the device and the store applies the update. Sparse-wire vars sync
        via the (ids, values) all-gather path in the lowering
        (``ops/embedding.py``), not a dense collective. ZeRO-sharded vars
        (``zero_names``) own their whole update path through the
        ZeroSynchronizer kernels; a ZeroSharded node NOT in that set
        (single data replica) degrades to a plain AllReduce kernel."""
        from autodist_tpu.strategy.base import (
            AllReduceSynchronizer as ARConfig)
        syncs = {}
        for node in self._strategy.node_config:
            info = self._item.var_infos.get(node.var_name)
            if info is None:
                continue
            if node.var_name in zero_names:
                continue
            if node.var_name in sparse_wire:
                comp = getattr(node.synchronizer, "compressor",
                               "NoneCompressor")
                if comp and comp != "NoneCompressor":
                    logging.warning(
                        "var %s: compressor %s ignored — sparse-wire "
                        "gradients ship as (ids, values) pairs, already "
                        "batch-sized", node.var_name, comp)
                continue
            if node.var_name in ps_names:
                continue
            if not info.trainable:
                # frozen vars never sync (their grads are zeroed in the
                # step); their node may still carry an mp_axes layout
                continue
            if layouts[node.var_name].mp_axes:
                # model-parallel vars (resolved layout — a size-1 model axis
                # degenerates to replicated and takes the normal path) sync
                # via the complement-axes psum in the lowering, not a
                # synchronizer kernel; a configured compressor cannot apply
                # to them — say so rather than silently dropping it
                comp = getattr(node.synchronizer, "compressor", "NoneCompressor")
                if comp != "NoneCompressor":
                    logging.warning(
                        "var %s: compressor %s ignored — model-parallel "
                        "(mp_axes) gradients reduce uncompressed over the "
                        "complement axes", node.var_name, comp)
                continue
            cfg = node.synchronizer
            if cfg is None and node.part_configs:
                cfg = node.part_configs[0].synchronizer
            if cfg is None:
                raise ValueError("no synchronizer for var %s" % node.var_name)
            if cfg.kind == "ZeroSharded":
                # only reachable when the zero path is disarmed (one data
                # replica): a plain mean all-reduce is the exact same
                # update with nothing to shard
                cfg = ARConfig()
            kind = ("AllReduceSynchronizer" if cfg.kind == "AllReduce"
                    else "PSSynchronizer")
            extra = tuple(a for a in self._axes if a != self._axis)
            from autodist_tpu.parallel import mesh as mesh_lib
            syncs[node.var_name] = Synchronizer.create(
                kind, node.var_name, cfg, self.total_devices, self._axis,
                layouts[node.var_name], extra, mesh_lib.dcn_axes(self._mesh))
        return syncs

    # ------------------------------------------------------- step_fn mode

    def _transform_step_fn(self) -> DistributedStep:
        """Opaque-step lowering (``ModelItem.step_fn`` mode): the strategy
        decides STORAGE shardings only — each state leaf gets its
        ``VarLayout.pspec``, the batch splits over the data axis — and the
        user's ``step_fn(state, batch) -> (new_state, metrics)`` is jitted
        with those in/out_shardings. GSPMD inserts the collectives the
        global-semantics program implies: the gradient psum falls out of
        the batch sharding, ZeRO-style gathers out of partitioned leaf
        storage, tensor-parallel collectives out of mp-sharded weights.

        This is the analog of the reference's distribute-any-graph
        generality (reference ``tests/integration/cases/c4.py:31`` rewrites
        arbitrary captured graphs); here the escape hatch is sharding
        assignment rather than graph surgery, so the gradient-interception
        machinery (compressors, host-PS, sparse wire, pipeline schedules)
        requires loss_fn mode and is refused loudly below."""
        import dataclasses as _dc
        from autodist_tpu.runtime import faultinject as fi
        if self._sentinel is not None:
            # the opaque step hides the gradients the guards inspect —
            # the lowered program carries NO health checks (ADT420); the
            # Runner's sentinel degrades to loss-only monitoring
            logging.warning(
                "sentinel requested but step_fn capture mode lowers the "
                "program WITHOUT in-graph health guards (the opaque step "
                "hides its gradients) — detection degrades to host-side "
                "loss monitoring; use loss_fn mode for full guards")
        if fi.GradFaultPlan.from_env().rules:
            logging.warning(
                "ADT_GRAD_FAULT_PLAN ignored in step_fn capture mode — "
                "no gradient interception on the opaque path")
        item = self._item
        var_infos = item.var_infos
        layouts = VariablePartitioner.apply(
            self._strategy, var_infos, self.num_replicas, self._axis,
            mesh_axis_sizes={a: int(self._mesh.shape[a])
                             for a in self._axes})
        ps_plans = ps_lib.plan_host_ps(self._strategy, var_infos)
        if ps_plans:
            raise ValueError(
                "step_fn capture mode cannot lower host-PS strategies "
                "(vars %s): the opaque step hides the gradients the PS "
                "path intercepts. Use loss_fn mode, or an AllReduce/"
                "Partitioned-family strategy." % sorted(ps_plans))
        for node in self._strategy.node_config:
            for leaf_cfg in (node.part_configs or [node]):
                sync = leaf_cfg.synchronizer or node.synchronizer
                comp = getattr(sync, "compressor", None)
                if comp and comp != "NoneCompressor":
                    logging.warning(
                        "step_fn mode ignores compressor %s on %s — no "
                        "gradient interception on the opaque path",
                        comp, node.var_name)
                if getattr(sync, "kind", "") == "ZeroSharded":
                    logging.warning(
                        "step_fn mode ignores ZeroSharded on %s — the "
                        "opaque step owns its optimizer, so storage "
                        "stays replicated (no sharded update)",
                        node.var_name)

        # storage shardings WITHOUT padding: the user's math must see the
        # original shapes (GSPMD shards uneven dims transparently); padding
        # is loss_fn mode's explicit gather/scatter trick
        layouts = {n: (_dc.replace(l, padded_dim=l.orig_dim)
                       if l.partitioned else l)
                   for n, l in layouts.items()}
        names, _, treedef = variable_utils.flatten_named(item.params)
        layout_tree = variable_utils.unflatten_named(
            treedef, [layouts[n] for n in names])
        state_specs = _tree_map_layouts(lambda _leaf, lay: lay.pspec,
                                        item.params, layout_tree)
        rep = self._replica_info()
        batch_specs = jax.tree_util.tree_map_with_path(
            lambda path, leaf: rep.batch_spec(np.ndim(leaf),
                                              _normalize_path(path)),
            item.example_batch)

        out_aval = jax.eval_shape(item.step_fn, item.params,
                                  item.example_batch)
        if not (isinstance(out_aval, tuple) and len(out_aval) == 2):
            raise ValueError(
                "step_fn must return (new_state, metrics); got structure %s"
                % (jax.tree_util.tree_structure(out_aval),))
        want = jax.tree_util.tree_structure(item.params)
        got = jax.tree_util.tree_structure(out_aval[0])
        if got != want:
            raise ValueError(
                "step_fn's new_state structure %s does not match the state "
                "template %s" % (got, want))
        metric_specs = jax.tree_util.tree_map(lambda _: P(), out_aval[1])

        def shardings(spec_tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(self._mesh, s), spec_tree,
                is_leaf=lambda x: isinstance(x, P))
        rep_sh = NamedSharding(self._mesh, P())
        state_sh = TrainState(step=rep_sh, params=shardings(state_specs),
                              opt_state={}, sync_state={})
        in_sh = (state_sh, {}, shardings(batch_specs))
        out_sh = (state_sh, {}, shardings(metric_specs))

        def _step(state: TrainState, ps_vals, batch):
            del ps_vals  # no host-PS on the opaque path
            new_user, metrics = item.step_fn(state.params, batch)
            return (TrainState(step=state.step + 1, params=new_user,
                               opt_state=state.opt_state,
                               sync_state=state.sync_state), {}, metrics)

        step_fn = jax.jit(_step, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=(0,) if self._donate else ())
        step_fn_nodonate = (jax.jit(_step, in_shardings=in_sh,
                                    out_shardings=out_sh)
                            if self._donate else step_fn)

        def stacked(spec_tree):
            # prepend an unsharded k (microstep) dim to every leaf spec
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(self._mesh, P(None, *s)), spec_tree,
                is_leaf=lambda x: isinstance(x, P))

        def fused_builder(donate: bool):
            def _multi(state: TrainState, ps_vals, ps_opt, batches):
                del ps_vals, ps_opt  # no host-PS on the opaque path

                def body(st, batch):
                    new_st, _, metrics = _step(st, {}, batch)
                    return new_st, metrics
                st, stacked_metrics = jax.lax.scan(body, state, batches)
                return st, {}, {}, stacked_metrics
            return jax.jit(
                _multi,
                in_shardings=(state_sh, {}, {}, stacked(batch_specs)),
                out_shardings=(state_sh, {}, {}, stacked(metric_specs)),
                donate_argnums=(0,) if donate else ())

        logging.info("GraphTransformer: lowered opaque step_fn over %d "
                     "replicas (%d state leaves, %d partitioned)",
                     self.num_replicas, len(layouts),
                     sum(1 for l in layouts.values() if l.partitioned))
        return DistributedStep(
            mesh=self._mesh, step_fn=step_fn,
            step_fn_nodonate=step_fn_nodonate, layouts=layouts,
            layout_tree=layout_tree, strategy=self._strategy,
            model_item=item, mesh_axis=self._axis,
            sync_state_init=lambda: {}, metadata={}, eval_fn=None,
            ps_store=None, holed_params_template=item.params,
            fused_builder=fused_builder)

    # ---------------------------------------------------------------- main

    def transform(self) -> DistributedStep:
        from autodist_tpu.utils import visualization_util
        item = self._item
        if item.loss_fn is None:
            return self._transform_step_fn()
        var_infos = item.var_infos
        if visualization_util.enabled():
            # stage 0: the user's original program (reference writes
            # '0-original' TensorBoard graphs, graph_transformer.py:62)
            visualization_util.log_jaxpr("0-original-loss", item.loss_fn,
                                         item.params, item.example_batch)
        layouts = VariablePartitioner.apply(
            self._strategy, var_infos, self.num_replicas, self._axis,
            mesh_axis_sizes={a: int(self._mesh.shape[a]) for a in self._axes})

        # Host-offloaded PS: no-proxy PS vars leave the device state entirely
        # (parallel/ps.py). Their device-side layout is moot (they enter the
        # step as replicated pulled values), so any partitioned layout the
        # partitioner assigned is dropped — host storage honors the TRUE
        # (possibly uneven) shard sizes instead of the padded device split.
        ps_plans = ps_lib.plan_host_ps(self._strategy, var_infos)
        ps_names = frozenset(ps_plans)
        # host-PS vars on the quantized wire (PSVarPlan.wire_dtype, guarded
        # to dense float by plan_host_ps): their pulled values enter the
        # step as {"q", "s"} int8+scales containers (dequantized in-graph)
        # and their reduced gradients exit the same way (dequantized at the
        # store boundary) — the PCIe wire carries ~1/4 the bytes
        ps_quant = frozenset(n for n, p in ps_plans.items()
                             if p.wire_dtype == "int8")
        if ps_plans:
            # the host store applies the optimizer PER VARIABLE (one
            # little {"v": shard} tree each). A structure-sensitive
            # optimizer (optax.multi_transform / masked wrappers) decides
            # its transform from the tree it sees — on a little tree the
            # label function resolves wrong and a variable would SILENTLY
            # train under the wrong transform. Refuse loudly instead.
            spec_repr = str(jax.tree_util.tree_structure(
                item.opt_state_spec)) if item.optimizer is not None else ""
            if any(s in spec_repr for s in (
                    "MaskedState", "PartitionState",
                    "MultiTransformState")):  # optax<0.2 name for the same
                raise ValueError(
                    "structure-sensitive optimizers (optax.multi_transform"
                    "/masked) are not supported on the host-resident PS "
                    "path: the store applies updates per variable, so "
                    "tree-structure-based labels would resolve incorrectly."
                    " Use local_proxy_variable=True (device-resident PS), "
                    "an AllReduce family strategy, or per-variable "
                    "optimizers without masking.")
        for n in ps_names:
            layouts[n] = VarLayout(name=n)
        ps_store = (ps_lib.PSStore(ps_plans, var_infos, item.optimizer)
                    if ps_plans else None)
        holed_params = (ps_lib.hole_out_params(item.params, ps_names)
                        if ps_names else item.params)

        # ----- ZeRO-sharded weight update (arXiv 2004.13336, stage 1):
        # params stay stored FULL; the gradient reduce-scatters over the
        # data axis, the optimizer applies to each replica's owned flat
        # shard against sync_state-resident sharded opt state (created
        # sharded, never materialized whole), and the update all-gathers
        # back onto the replicated params. The same invalid combinations
        # the linter reports as ADT312 raise here, so compile time and
        # lint time agree.
        from autodist_tpu.kernel.synchronization.zero_synchronizer import (
            ZeroSynchronizer)
        zero_syncs: Dict[str, ZeroSynchronizer] = {}
        zero_stride = int(np.prod(
            [self._mesh.shape[a] for a in self._axes[
                self._axes.index(self._axis) + 1:]] or [1]))
        for node in self._strategy.node_config:
            cfg = node.synchronizer
            if cfg is None or getattr(cfg, "kind", "") != "ZeroSharded":
                continue
            info = var_infos.get(node.var_name)
            if info is None or not info.trainable:
                continue
            if getattr(info, "sparse", False):
                raise ValueError(
                    "var %s: ZeroSharded on a sparse (gather-indexed) "
                    "variable — the reduce-scatter would densify its "
                    "batch-row-sized gradient to the full table every "
                    "step (ADT312); route it to PS or plain AllReduce"
                    % node.var_name)
            if node.mp_axes or node.partitioner:
                raise ValueError(
                    "var %s: ZeroSharded cannot combine with %s storage "
                    "(ADT312) — the sharded update owns the whole flat "
                    "variable" % (node.var_name,
                                  "mp_axes" if node.mp_axes
                                  else "partitioner"))
            if self.num_replicas <= 1:
                # one data replica: nothing to shard — the node degrades
                # to plain AllReduce in _build_synchronizers below
                logging.info(
                    "var %s: ZeroSharded on a single data replica "
                    "degrades to plain AllReduce sync", node.var_name)
                continue
            zero_syncs[node.var_name] = ZeroSynchronizer(
                node.var_name, cfg, tuple(info.shape), info.dtype,
                self._axis, self.num_replicas,
                tuple(a for a in self._axes if a != self._axis),
                self.total_devices, zero_stride)
        zero_names = frozenset(zero_syncs)
        # ZeRO-sharded vars have no slot in the device optimizer tree —
        # the main optimizer.update runs on the holed basis, and their
        # little-tree shard applies run against sync_state['zero']
        opt_basis = (ps_lib.hole_out_params(holed_params, zero_names)
                     if zero_names else holed_params)
        zero_basis_template = (jax.eval_shape(lambda t: t, opt_basis)
                               if zero_names else None)

        names, _, treedef = variable_utils.flatten_named(holed_params)
        layout_tree = variable_utils.unflatten_named(
            treedef, [layouts[n] for n in names])

        # Model-parallel vars (tensor/pipeline/expert sharded storage) bypass
        # the synchronizer machinery: their gradient reduces only over the
        # complement mesh axes (the forward's own collectives — psum in a
        # row-parallel matmul, ppermute in a pipeline, all_to_all in MoE —
        # already account for the model-parallel axes).
        mp_names = frozenset(n for n, l in layouts.items() if l.mp_axes)
        mp_complement = {
            n: tuple(a for a in self._axes
                     if a not in set(layouts[n].mp_axis_names))
            for n in mp_names}

        # Sparse wire path (ops/embedding.py): gather-indexed vars whose
        # lookups carry a matching name synchronize as (ids, values) pairs
        # — batch-shaped wire instead of vocab-shaped (the reference's
        # IndexedSlices all-gather, all_reduce_synchronizer.py:132-173).
        from autodist_tpu.ops import embedding as embedding_lib
        # AR sparse wire only exists ACROSS devices (it replaces the dense
        # gradient collective); on a single replica there is nothing to
        # save and the explicit scatter path only costs compile time. The
        # host-PS path keeps it regardless: (ids, values) still beats a
        # vocab-sized dense push over PCIe.
        sparse_candidates = {
            n for n, v in var_infos.items()
            if v.sparse and v.trainable
            and (n in ps_names
                 or (self.total_devices > 1
                     and not layouts[n].partitioned
                     and not layouts[n].mp_axes))}
        sparse_specs = {}
        grad_jaxpr = None  # the backward pass, where a trace of it exists
        if sparse_candidates and item.loss_fn is not None:
            loss_plain = (lambda p, b: item.loss_fn(p, b)[0]) if item.has_aux \
                else item.loss_fn
            # taps live INSIDE shard_map: discover against the per-device
            # (local) batch shape, not the host-global one. ReplicaInfo is
            # the SAME source the shard_map in_specs use below, so the tap
            # shapes cannot disagree with the actual batch split.
            local_batch = self._local_batch_avals()
            discovered = set()
            # the taps/safety traces run OUTSIDE the step's shard_map but
            # the loss may use mesh collectives (ring attention, Megatron
            # psum); bind the axis names at size 1 so those traces run.
            # Size 1 — not the real sizes — because the trace feeds FULL
            # (unsharded) params: under size-1 axes "local = global", so
            # model-parallel compute (expert splits, column/row matmuls)
            # sees consistent shapes. Only SHAPES are read off these
            # traces, and batch dims are pre-divided by local_aval, so
            # axis-size-dependent VALUES (mean weights, offsets) are
            # irrelevant.
            from autodist_tpu.utils.axis_env import bound_axes
            try:
                with bound_axes():
                    sparse_specs = embedding_lib.discover(
                        loss_plain, item.params, local_batch,
                        sparse_candidates)
                discovered = set(sparse_specs)
                if sparse_specs:
                    # a table with OTHER differentiable uses (tied output
                    # embedding, weight sharing) gets a real dense gradient
                    # the sparse wire would drop — keep those dense
                    full_names, _, _ = variable_utils.flatten_named(
                        item.params)
                    with bound_axes():
                        grad_jaxpr = embedding_lib.tap_grad_jaxpr(
                            loss_plain, item.params, local_batch,
                            sparse_specs)
                    safe = embedding_lib.safe_sparse_names(
                        grad_jaxpr, sparse_specs, full_names)
                    tied = sorted(set(sparse_specs) - safe)
                    if tied:
                        # info, not warning: a deliberate, correct routing
                        # decision (the dense head gradient would be lost
                        # on the sparse wire), not a degradation
                        logging.info(
                            "sparse vars %s have dense gradient paths "
                            "besides their lookups (tied embeddings); "
                            "keeping them on the dense sync path", tied)
                    sparse_specs = {n: s for n, s in sparse_specs.items()
                                    if n in safe}
                # the wire only pays when the gathered (ids, values)
                # payload undercuts the dense gradient (batch << vocab);
                # small tables with large batches stay dense
                keep = {}
                for n, specs in sparse_specs.items():
                    info = var_infos[n]
                    feat = max(1, int(np.prod(info.shape[1:] or (1,))))
                    rows = sum(int(np.prod(ids_shape or (1,)))
                               for ids_shape, _d, _f, _fd in specs)
                    sparse_bytes = rows * self.total_devices * (feat + 1)
                    dense_bytes = int(info.shape[0]) * feat
                    if sparse_bytes < dense_bytes:
                        keep[n] = specs
                    else:
                        logging.debug(
                            "var %s: sparse wire (%d) >= dense (%d) "
                            "elements; keeping dense sync", n,
                            sparse_bytes, dense_bytes)
                sparse_specs = keep
            except Exception as e:  # noqa: BLE001 — discovery is best-effort
                # ... except when it must not be: an exception here silently
                # degrades every sparse var to dense sync (>10x wire on
                # embedding models). Strict when the builder demanded the
                # sparse wire (require_sparse) or under test invariants.
                if (self._strategy.graph_config.require_sparse
                        or const.ENV.ADT_IS_TESTING.val):
                    raise RuntimeError(
                        "sparse-wire discovery failed and the strategy "
                        "requires the sparse gradient path (vars: %s)"
                        % sorted(sparse_candidates)) from e
                sparse_specs = {}
                logging.warning("sparse-wire discovery failed (%s); dense "
                                "sync for all sparse vars", e)
            uncaptured = sparse_candidates - discovered
            if uncaptured:
                if self._strategy.graph_config.require_sparse:
                    raise ValueError(
                        "strategy requires the sparse gradient wire but "
                        "vars %s are not routed through "
                        "ops.embedding.embedding_lookup(name=...) — their "
                        "gradients would sync DENSE (vocab-sized wire). "
                        "Route the lookups through ops.embedding, or build "
                        "with require_sparse=False." % sorted(uncaptured))
                logging.warning(
                    "sparse vars %s not routed through "
                    "ops.embedding.embedding_lookup(name=...); their "
                    "gradients sync DENSE (vocab-sized wire)",
                    sorted(uncaptured))
        sparse_wire = frozenset(sparse_specs)

        # ----- device counters (telemetry/device_counters.py): scalars
        # the loss adds while it is traced (a routed layer's expert load)
        # leave the step beside the loss. A loss that counts says so
        # (``loss_fn.device_counters``, the names); one that does not
        # (most) pays nothing and lowers exactly as before.
        from autodist_tpu.telemetry import device_counters
        counter_names = tuple(getattr(item.loss_fn, "device_counters", ()))
        counted = bool(counter_names)

        # ----- training health sentinel + gradient fault layer
        # Guards (and injected faults) are COMPILED INTO the step: both
        # read their configuration here, at transform time, so the clean
        # path stays byte-identical when neither is active.
        from autodist_tpu.runtime import faultinject as fi
        guard = self._sentinel is not None
        grad_norm_limit = (getattr(self._sentinel, "grad_norm_limit", None)
                          if guard else None)
        grad_plan = fi.GradFaultPlan.from_env()
        if grad_plan.rules:
            unknown = sorted({r.var for r in grad_plan.rules
                              if r.var not in var_infos})
            if unknown:
                logging.warning(
                    "ADT_GRAD_FAULT_PLAN names unknown variables %s — "
                    "those rules never fire", unknown)
            on_wire = sorted({r.var for r in grad_plan.rules
                              if r.var in sparse_wire})
            if on_wire:
                logging.warning(
                    "ADT_GRAD_FAULT_PLAN targets sparse-wire vars %s: the "
                    "fault lands on the (unused) dense gradient — route "
                    "those vars dense to observe the fault", on_wire)
            logging.warning("gradient fault plan compiled into the step: %s",
                            grad_plan.describe())
        # per-var squared-norm / nonfinite-count scaling for sharded
        # storage: a leaf sharded over mesh axes of total size S is
        # replicated N/S times, so psum(local * S/N) == the global value;
        # replicated leaves (scale None) are already global on every
        # device and skip the psum entirely
        def _shard_frac(lay: VarLayout):
            axes = []
            for part in tuple(lay.pspec or ()):
                if part is None:
                    continue
                axes.extend(part if isinstance(part, (tuple, list))
                            else [part])
            prod = 1
            for a in axes:
                prod *= int(self._mesh.shape[a])
            return (prod / float(self.total_devices)) if prod > 1 else None
        shard_frac = {n: f for n, lay in layouts.items()
                      if (f := _shard_frac(lay)) is not None}
        # ZeRO-sharded gradients enter the verdict as the owned shard:
        # sharded over the data axis (replicated over any extra axes),
        # so the same local*S/N stacked-psum accounting applies
        for n in zero_names:
            shard_frac[n] = self.num_replicas / float(self.total_devices)

        syncs = self._build_synchronizers(layouts, ps_names, sparse_wire,
                                          zero_names)
        # Route unpartitioned AllReduce vars with an *active* compressor into
        # concat buckets (payload transform needs the merged vector).
        # NoneCompressor vars psum individually — XLA's all-reduce combiner
        # merges those on the wire without materializing a concat, so an
        # explicit bucket would only add two full-gradient copies.
        ar_unpart = {n: s for n, s in syncs.items()
                     if s.__class__.__name__ == "AllReduceSynchronizer"
                     and not layouts[n].partitioned
                     and n not in mp_names
                     and s.compressor.name != "NoneCompressor"}
        buckets, per_var_comp = collectives.make_buckets(ar_unpart, var_infos)
        bucketed_names = {n for b in buckets for n in b.var_names}

        # ----- sync_state initialization (host-side zeros w/ leading dev axis)
        N = self.total_devices
        def sync_state_init():
            st = {"bucket": {}, "var": {}}
            for b in buckets:
                comp = b.make_compressor()
                s = comp.state_init((b.total_size,), np.dtype(b.dtype))
                if s is not None:
                    st["bucket"][b.key] = np.broadcast_to(
                        np.asarray(s)[None], (N,) + np.asarray(s).shape).copy()
            for n, s in syncs.items():
                if n in bucketed_names or n in mp_names:
                    continue
                if layouts[n].partitioned:
                    continue  # partitioned vars reduce-scatter; no compressor state
                info = var_infos[n]
                init = s.state_init(tuple(info.shape), np.dtype(info.dtype))
                if init is not None:
                    st["var"][n] = jax.tree_util.tree_map(
                        lambda a: np.broadcast_to(
                            np.asarray(a)[None], (N,) + np.asarray(a).shape).copy(),
                        init)
            if not st["bucket"]:
                st.pop("bucket")
            if not st["var"]:
                st.pop("var")
            if zero_syncs:
                # per-replica optimizer-state shards, created sharded:
                # every replica's shard inits identically (optax inits are
                # shape functions — zeros/counters), so the leading-
                # device-axis broadcast IS the correct sharded init; the
                # full state is never materialized
                zst = {}
                for n, zs in sorted(zero_syncs.items()):
                    init = zs.opt_state_init(optimizer)
                    zst[n] = jax.tree_util.tree_map(
                        lambda a: np.broadcast_to(
                            np.asarray(a)[None],
                            (N,) + np.asarray(a).shape).copy(), init)
                st["zero"] = zst
            if guard:
                # effective-LR scale for the sentinel's escalation ladder:
                # rides the sync_state (same leading-device-axis layout as
                # the compressor states) so halving it is a host-side
                # state edit, never a recompile; updates are multiplied by
                # it in-graph — exact LR semantics for linear-in-lr optax
                # transforms (sgd, adam, ...)
                st["sentinel"] = {"lr_scale": np.ones((N,), np.float32)}
            return st

        # ----- the local (per-device) step executed under shard_map
        # gradient rematerialization (graph_config.remat): compute grads
        # through jax.checkpoint so the backward recomputes activations
        # instead of storing them — the HBM-for-FLOPs trade
        remat = self._strategy.graph_config.remat

        def remat_wrap(f):
            if not remat:
                return f
            from autodist_tpu.strategy.remat import remat_transform
            return remat_transform(remat)(f)

        # ----- managed bf16 compute tier (graph_config.compute_dtype):
        # cast f32 params and float batch leaves down INSIDE the loss, so
        # grads w.r.t. the f32 master come back f32 (the convert's
        # transpose casts up) and every gradient psum accumulates in f32;
        # cast the loss (and bf16 aux) back up so the pmean and the
        # sentinel verdict judge full-precision values — exactly the
        # shape the ADT601/602/603 numerics rules certify
        compute_dtype = (getattr(self._strategy.graph_config,
                                 "compute_dtype", "f32") or "f32")
        if compute_dtype == "bf16":
            def _cd_down(x):
                x = jnp.asarray(x)
                return (x.astype(jnp.bfloat16)
                        if x.dtype == jnp.float32 else x)

            def _cd_up(x):
                x = jnp.asarray(x)
                return (x.astype(jnp.float32)
                        if x.dtype == jnp.bfloat16 else x)

            def loss_fn_cd(params, batch):
                out = item.loss_fn(
                    jax.tree_util.tree_map(_cd_down, params),
                    jax.tree_util.tree_map(_cd_down, batch))
                if item.has_aux:
                    loss, aux = out
                    return (_cd_up(loss),
                            jax.tree_util.tree_map(_cd_up, aux))
                return _cd_up(out)
        else:
            loss_fn_cd = item.loss_fn
        if counted:
            loss_fn_uncounted = loss_fn_cd

            def loss_fn_cd(params, batch):
                """(loss, (the user's aux or None, {counter: scalar}))."""
                with device_counters.collect(counter_names) as got:
                    out = loss_fn_uncounted(params, batch)
                loss, aux = out if item.has_aux else (out, None)
                return loss, (aux, dict(got))
        # what the differentiated function returns beside the loss
        aux_out = item.has_aux or counted

        # the loss scope sits OUTSIDE remat and INSIDE the differentiated
        # function: JAX then names forward ops jvp(loss)/..., backward
        # ops transpose(jvp(loss))/... and recomputed ones
        # .../rematted_computation/...
        under_loss_scope = sc.scoped(sc.LOSS)

        grad_fn = jax.value_and_grad(
            under_loss_scope(remat_wrap(loss_fn_cd)), has_aux=aux_out)
        if sparse_wire:
            def loss_with_taps(full_params, taps, batch):
                with embedding_lib.capture(taps) as cap:
                    out = loss_fn_cd(full_params, batch)
                loss, aux = (out if aux_out else (out, None))
                return loss, (aux, cap.ids)
            sparse_grad_fn = jax.value_and_grad(
                under_loss_scope(remat_wrap(loss_with_taps)),
                argnums=(0, 1), has_aux=True)
        optimizer = item.optimizer
        has_aux = item.has_aux
        axis = self._axis
        all_axes = self._axes
        frozen_names = frozenset(n for n, v in var_infos.items() if not v.trainable)
        from autodist_tpu.parallel import mesh as mesh_lib
        dcn = tuple(a for a in mesh_lib.dcn_axes(self._mesh) if a in all_axes)
        ici = tuple(a for a in all_axes if a not in dcn)
        # int8 quantized rings: one ring per reduced mesh axis, in order
        ring_axes = tuple((a, int(self._mesh.shape[a])) for a in all_axes)

        # ----- the exchange under the rest of the step: the default path
        # on more than one replica. Variables whose sync is a plain
        # mean-psum are summed in the order the backward pass completes
        # their gradients, and the step compiles with the options that
        # let the TPU run each all-reduce beside a matmul of the backward
        # pass or an update of the optimizer (collectives.py, "the
        # exchange under the rest of the step"). Compressed, quantized,
        # routed, partitioned and ZeRO units keep their own kernels and
        # their place behind the plain sums.
        plain, sync_order, sync_groups = {}, [], []
        if N > 1:
            plain = {n: axes for n, s in syncs.items()
                     if n not in bucketed_names
                     and (axes := s.plain_sum_axes()) is not None}
            ready = (self._grad_ready_order(grad_jaxpr) if len(plain) > 1
                     else {})
            entries = sorted(
                ((n, ready.get(n, 0), int(var_infos[n].byte_size),
                  (str(var_infos[n].dtype), axes))
                 for n, axes in plain.items()), key=lambda e: (e[1], e[0]))
            sync_order = [e[0] for e in entries]
            sync_groups = collectives.plan_grad_sync_groups(entries)
        train_options = collectives.async_collective_options(
            self._mesh.devices.flat[0].platform, N)
        # (no ``compiler_options`` argument at all where there are none:
        # one replica, the CPU: those steps compile as they always did)
        train_jit = (functools.partial(jax.jit,
                                       compiler_options=train_options)
                     if train_options else jax.jit)

        def _health_verdict(synced, ps_grads, new_params, global_loss):
            """The in-graph sentinel verdict: global gradient L2 norm,
            nonfinite counts over the synced gradients (incl. the PS
            wire) and the post-update device params, and loss
            finiteness. Replicated quantities are already global on
            every device; sharded leaves contribute ``local * S/N``
            through ONE stacked psum (exact — see ``shard_frac``), so a
            program with no sharded storage pays no extra collective.
            Every input is replica-identical, so the ``ok`` branch is
            taken uniformly across the whole (multi-process) program."""
            zero = jnp.float32(0.0)
            local_sq, bad_g_local, bad_p_local = zero, zero, zero
            shared = [zero, zero, zero]  # sharded parts: sq, bad_g, bad_p

            def _stats(arr):
                a = jnp.asarray(arr).astype(jnp.float32)
                return (jnp.sum(jnp.square(a)),
                        jnp.sum(~jnp.isfinite(a)).astype(jnp.float32))
            for n in sorted(synced):
                v = synced[n]
                if not jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact):
                    continue
                sq, bad = _stats(v)
                f = shard_frac.get(n)
                if f is not None:
                    shared[0] += sq * f
                    shared[1] += bad * f
                else:
                    local_sq += sq
                    bad_g_local += bad
            for n in sorted(ps_grads):
                gv = ps_grads[n]
                if isinstance(gv, dict):
                    # wire-quantized PS grad: judge the dequantized image
                    # (what the store will apply). A NaN gradient poisons
                    # its block scales by construction, so the nonfinite
                    # count still fires.
                    vals = collectives.dequant_wire(
                        gv, tuple(var_infos[n].shape))
                else:
                    vals = gv[1] if isinstance(gv, tuple) else gv
                sq, bad = _stats(vals)
                local_sq += sq
                bad_g_local += bad
            p_names, p_leaves, _ = variable_utils.flatten_named(new_params)
            for n, leaf in zip(p_names, p_leaves):
                if (getattr(leaf, "dtype", None) is None
                        or not jnp.issubdtype(jnp.asarray(leaf).dtype,
                                              jnp.inexact)):
                    continue
                _, bad = _stats(leaf)
                f = shard_frac.get(n)
                if f is not None:
                    shared[2] += bad * f
                else:
                    bad_p_local += bad
            red = jnp.stack(shared)
            if N > 1 and shard_frac:
                red = jax.lax.psum(red, all_axes)
            grad_norm = jnp.sqrt(local_sq + red[0])
            bad_g = bad_g_local + red[1]
            bad_p = bad_p_local + red[2]
            ok = ((bad_g == 0) & (bad_p == 0)
                  & jnp.isfinite(global_loss) & jnp.isfinite(grad_norm))
            if grad_norm_limit is not None:
                ok = ok & (grad_norm <= jnp.float32(grad_norm_limit))
            return {"ok": ok.astype(jnp.int32), "grad_norm": grad_norm,
                    "bad_grads": bad_g, "bad_params": bad_p}

        def _ps_dewire(ps_vals):
            """Quantized PS values arrive as {"q", "s"} wire containers
            (that is what crossed PCIe); dequantize in-graph before the
            loss sees them — the device-side half of the store-boundary
            codec."""
            if not ps_quant:
                return ps_vals
            out = dict(ps_vals)
            for n in ps_quant:
                info = var_infos[n]
                out[n] = collectives.dequant_wire(
                    out[n], tuple(info.shape), np.dtype(info.dtype))
            return out

        def _full_params(state: TrainState, ps_vals):
            """The prologue of every compiled program: stored leaves
            gathered into the full layout, host-resident PS values
            (pulled + replicated) filled into the holes, so the user's
            function sees the full original params tree."""
            with sc.scope(sc.PARAMS):
                ps_vals = _ps_dewire(ps_vals)
                gathered = _tree_map_layouts(
                    lambda leaf, lay: lay.gather_full(leaf), state.params,
                    layout_tree)
                return (ps_lib.fill_holes(gathered, ps_vals)
                        if ps_names else gathered)

        def _over_replicas(tree):
            """A loss's extra outputs as one value per step: floats are
            averaged over the replicas, integers take the largest."""
            return jax.tree_util.tree_map(
                lambda a: (jax.lax.pmean(a, all_axes)
                           if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)
                           else jax.lax.pmax(a, all_axes)), tree)

        def local_step(state: TrainState, ps_vals, batch):
            full_params = _full_params(state, ps_vals)
            if sparse_wire:
                taps = embedding_lib.make_taps(sparse_specs)
                (loss, (aux, ids_seen)), (grads, tap_grads) = sparse_grad_fn(
                    full_params, taps, batch)
            elif aux_out:
                (loss, aux), grads = grad_fn(full_params, batch)
            else:
                loss, grads = grad_fn(full_params, batch)
                aux = None
            counters = None
            if counted:
                aux, counters = aux
            g_names, g_leaves, _ = variable_utils.flatten_named(grads)
            g = dict(zip(g_names, g_leaves))
            if grad_plan.rules:
                # chaos harness: deterministic step-keyed corruption of a
                # named variable's LOCAL gradient, pre-collective — NaN
                # spreads through the psum so every replica sees (and the
                # all-reduced verdict judges) the same poisoned value
                g = fi.apply_grad_faults(grad_plan, state.step, g)

            with sc.scope(sc.GRAD_SYNC):
                # sparse wire: per-var (ids, values) pairs, all-gathered across
                # the mesh — batch-shaped payload instead of vocab-shaped
                sparse_pairs = {}
                for n in sorted(sparse_wire):
                    flat_ids, flat_vals = embedding_lib.flatten_pairs(
                        ids_seen.get(n, []), tap_grads.get(n, []))
                    if N > 1:
                        flat_ids, flat_vals = embedding_lib.gather_pairs(
                            flat_ids, flat_vals, all_axes)
                    sparse_pairs[n] = (flat_ids, flat_vals / N)

                # PS gradients exit the device: mean-reduced, replicated, pushed
                # to the host store by the caller (the reference's grad push to
                # the PS accumulator, ps_synchronizer.py:556-633); sparse PS
                # vars ship the (ids, values) pair itself — the store
                # scatter-adds into each owner shard's index range
                ps_grads = {}
                for n in sorted(ps_names):
                    if n in sparse_pairs:
                        ps_grads[n] = sparse_pairs[n]
                    elif N == 1:
                        ps_grads[n] = g[n]
                    else:
                        ps_grads[n] = jax.lax.psum(g[n], all_axes) / N
                    if n in ps_quant:
                        # quantize ON DEVICE: the D2H transfer (the PS push
                        # wire) carries int8 + scales; the store dequantizes
                        # at its boundary before the optimizer apply
                        ps_grads[n] = collectives.quant_wire(ps_grads[n])

                sync_state = (dict(state.sync_state)
                              if isinstance(state.sync_state, dict) else {})
                new_bucket_state = dict(sync_state.get("bucket", {}))
                new_var_state = dict(sync_state.get("var", {}))
                synced: Dict[str, Any] = {}
                psum = lambda x: jax.lax.psum(x, all_axes)  # noqa: E731

                if N == 1:
                    # single replica: gradients are already global; collectives
                    # would only insert degenerate all-reduces that block fusion
                    # (compressor states pass through unchanged)
                    synced = {n: (jnp.zeros_like(v) if n in frozen_names else v)
                              for n, v in g.items()
                              if n not in ps_names and n not in sparse_wire}

                # model-parallel vars: mean over the complement axes only; the /N
                # (total devices) normalization is exact — shard_map AD transposes
                # the forward psum/all_to_all into a sum over the model axes, and
                # that inflation cancels against the model-axis factor in N
                # (verified numerically in tests/test_tensor_parallel.py)
                for n in (mp_names if N > 1 else ()):
                    if n in frozen_names:
                        synced[n] = jnp.zeros_like(g[n])
                        continue
                    comp = mp_complement[n]
                    synced[n] = (jax.lax.psum(g[n], comp) if comp else g[n]) / N

                # sparse AllReduce vars: densify AFTER the wire (local
                # scatter-add of the gathered pairs — reference
                # all_reduce_synchronizer.py:132-173's conversion back)
                for n in sorted(sparse_wire):
                    if n in ps_names:
                        continue
                    info = var_infos[n]
                    s_ids, s_vals = sparse_pairs[n]
                    synced[n] = embedding_lib.scatter_add_dense(
                        s_ids, s_vals, int(info.shape[0]),
                        tuple(info.shape[1:]))

                # the three gradient-sync unit kernels: a ZeRO
                # reduce-scatter, a concat bucket, a per-variable sync
                def _run_zero(n, gin):
                    synced[n] = zero_syncs[n].reduce_scatter(gin)
                    return synced[n]

                def _run_bucket(b, gin):
                    bst = new_bucket_state.get(b.key)
                    bst_local = bst[0] if bst is not None else None
                    bucket_psum = psum
                    sched = getattr(b, "schedule", "auto")
                    if (b.spec == "DCN" or sched == "hier") and dcn:
                        bucket_psum = lambda x: collectives.hierarchical_psum(  # noqa: E731
                            x, ici, dcn)
                    elif sched == "rhd":
                        bucket_psum = lambda x: collectives.rhd_psum(  # noqa: E731
                            x, all_axes)
                    out, nst = collectives.bucket_reduce(
                        b, gin, bst_local, bucket_psum, N, ring_axes=ring_axes)
                    synced.update(out)
                    if nst is not None:
                        new_bucket_state[b.key] = jnp.expand_dims(nst, 0)
                    return out

                def _run_var(n, gin):
                    s = syncs[n]
                    vst = new_var_state.get(n)
                    vst_local = (jax.tree_util.tree_map(lambda a: a[0], vst)
                                 if vst is not None else None)
                    synced[n], nst = s.sync(gin, vst_local)
                    if nst is not None:
                        new_var_state[n] = jax.tree_util.tree_map(
                            lambda a: jnp.expand_dims(a, 0), nst)
                    return synced[n]

                # the plain sums in the order the backward pass completes
                # their gradients (no value depends on the order; on a TPU
                # the compiled schedule runs them beside the compute that
                # is left), then ZeRO reduce-scatters, concat buckets and
                # the remaining per-var syncs
                for n in sync_order:
                    _run_var(n, g[n])
                for n in sorted(zero_names):
                    _run_zero(n, g[n])
                for b in (buckets if N > 1 else []):
                    _run_bucket(b, g)
                for n in (syncs if N > 1 else ()):
                    if n in bucketed_names or n in synced:
                        continue
                    _run_var(n, g[n])
                # non-trainable vars: zero gradient so optimizer state stays
                # clean and the value never moves; remaining unconfigured vars
                # (shouldn't happen post-compile) get a plain mean-psum
                for n in g_names:
                    if n in synced or n in ps_names:
                        continue
                    if n in var_infos and not var_infos[n].trainable:
                        synced[n] = jnp.zeros_like(g[n])
                    else:
                        synced[n] = psum(g[n]) / N

            with sc.scope(sc.OPTIMIZER):
                # device-side update covers only device-resident leaves (the
                # holed structure); PS leaves update on the host, ZeRO-sharded
                # leaves per-shard against sync_state['zero'] below
                h_names, h_leaves, h_treedef = variable_utils.flatten_named(
                    state.params)
                grads_storage = variable_utils.unflatten_named(
                    h_treedef, [synced[n] for n in h_names])
                if zero_names:
                    grads_basis = ps_lib.hole_like(zero_basis_template,
                                                   grads_storage)
                    params_basis = ps_lib.hole_like(zero_basis_template,
                                                    state.params)
                else:
                    grads_basis, params_basis = grads_storage, state.params
                updates, new_opt = optimizer.update(
                    grads_basis, state.opt_state, params_basis)
                lr_scale = (sync_state["sentinel"]["lr_scale"][0] if guard
                            else None)
                if guard:
                    # sentinel escalation: effective-LR scale from sync_state
                    # (local slice of the leading-device-axis layout) — the
                    # zero deltas below scale pre-gather to the same value
                    updates = jax.tree_util.tree_map(
                        lambda u: (u * lr_scale).astype(u.dtype), updates)
                new_zero_state = {}
                if zero_names:
                    # the sharded weight update: optimizer on the owned 1/P
                    # shard only (per-var little trees, the SAME per-variable
                    # apply shape the host-PS store runs), then all-gather the
                    # UPDATE so every replica applies the identical delta to
                    # its full-precision replicated param copy
                    p_map = dict(zip(h_names, h_leaves))
                    zstate = sync_state["zero"]
                    zero_deltas = {}
                    for n in sorted(zero_names):
                        zs = zero_syncs[n]
                        opt_local = jax.tree_util.tree_map(
                            lambda a: a[0], zstate[n])
                        upd, nopt = optimizer.update(
                            {"v": synced[n]}, opt_local,
                            {"v": zs.local_shard(p_map[n])})
                        d = upd["v"]
                        if lr_scale is not None:
                            d = (d * lr_scale).astype(d.dtype)
                        zero_deltas[n] = zs.gather_update(d)
                        new_zero_state[n] = jax.tree_util.tree_map(
                            lambda a: jnp.expand_dims(a, 0), nopt)
                    updates = ps_lib.fill_holes(updates, zero_deltas)
                # mask non-trainable updates (guards vs. weight decay etc.)
                if frozen_names:
                    u_names, u_leaves, u_treedef = \
                        variable_utils.flatten_named(updates)
                    u = [jnp.zeros_like(leaf) if n in frozen_names else leaf
                         for n, leaf in zip(u_names, u_leaves)]
                    updates = variable_utils.unflatten_named(u_treedef, u)
                new_params = optax.apply_updates(state.params, updates)

            global_loss = jax.lax.pmean(loss, all_axes)
            metrics = {"loss": global_loss}
            if aux is not None:
                metrics["aux"] = _over_replicas(aux)
            if counters is not None:
                metrics["counters"] = _over_replicas(counters)
            new_sync = {}
            if new_bucket_state:
                new_sync["bucket"] = new_bucket_state
            if new_var_state:
                new_sync["var"] = new_var_state
            if new_zero_state:
                new_sync["zero"] = new_zero_state
            if guard:
                new_sync["sentinel"] = sync_state["sentinel"]
                with sc.scope(sc.SENTINEL):
                    verdict = _health_verdict(synced, ps_grads, new_params,
                                              global_loss)
                metrics["sentinel"] = verdict
                # in-graph SKIP: a bad verdict discards the whole update —
                # params, optimizer state and compressor residuals carry
                # unchanged through the select, so the step costs its
                # compute but poisons nothing. The verdict's inputs are
                # all-reduced, so every replica (and every process in a
                # multi-process SPMD program) takes the same branch.
                okb = verdict["ok"].astype(bool)

                def _sel(new, old):
                    return jax.tree_util.tree_map(
                        lambda a, b: jnp.where(okb, a, b), new, old)
                with sc.scope(sc.SENTINEL):
                    new_params = _sel(new_params, state.params)
                    new_opt = _sel(new_opt, state.opt_state)
                    new_sync = _sel(new_sync, dict(state.sync_state)
                                    if isinstance(state.sync_state, dict)
                                    else state.sync_state)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt, sync_state=new_sync)
            return new_state, ps_grads, metrics

        # ----- spec trees for shard_map
        param_specs = _tree_map_layouts(lambda _leaf, lay: lay.pspec,
                                        holed_params, layout_tree)
        opt_state_spec = (jax.eval_shape(item.optimizer.init, opt_basis)
                          if (ps_names or zero_names)
                          else item.opt_state_spec)
        # quantized-wire PS values enter (and their grads leave) as the
        # {"q", "s"} container — both replicated, like the f32 values
        ps_specs = {n: ({"q": P(), "s": P()} if n in ps_quant else P())
                    for n in sorted(ps_names)}
        # sparse PS grads leave as (ids, values) pairs, both replicated
        ps_out_specs = {n: ((P(), P()) if n in sparse_wire else
                            {"q": P(), "s": P()} if n in ps_quant else P())
                        for n in sorted(ps_names)}
        opt_layout_tree = variable_utils.map_state_layouts(
            opt_state_spec, var_infos, layouts, VarLayout(name=""))
        opt_specs = _tree_map_layouts(lambda _leaf, lay: lay.pspec,
                                      opt_state_spec, opt_layout_tree)
        sync_specs = jax.tree_util.tree_map(lambda _: P(all_axes),
                                            sync_state_init())
        state_specs = TrainState(step=P(), params=param_specs,
                                 opt_state=opt_specs, sync_state=sync_specs)
        # replication bookkeeping (replica count, batch specs, local
        # shapes) has a single owner: the Replicator kernel
        rep = self._replica_info()
        batch_specs = jax.tree_util.tree_map_with_path(
            lambda path, leaf: rep.batch_spec(np.ndim(leaf),
                                              _normalize_path(path)),
            item.example_batch)

        # metrics out-structure from an abstract eval of the loss (may fail
        # for SP losses that need a bound axis; scalar-loss fallback)
        metric_specs = {"loss": P()}
        if has_aux:
            loss_spec = jax.eval_shape(item.loss_fn, item.params,
                                       item.example_batch)
            metric_specs["aux"] = jax.tree_util.tree_map(lambda _: P(), loss_spec[1])
        if counted:
            metric_specs["counters"] = {n: P() for n in counter_names}
        if guard:
            # the verdict rides the existing metrics readback (replicated
            # scalars): zero extra dispatches, zero extra D2H
            metric_specs["sentinel"] = {"ok": P(), "grad_norm": P(),
                                        "bad_grads": P(), "bad_params": P()}

        # forward-only metrics (Runner.evaluate): same param gather, no
        # grad/optimizer/collective-sync cost
        def local_eval(state: TrainState, ps_vals, batch):
            full_params = _full_params(state, ps_vals)
            with sc.scope(sc.LOSS):
                out = loss_fn_cd(full_params, batch)
            loss, aux = (out if aux_out else (out, None))
            if counted:
                aux = aux[0]  # an evaluation counts nothing
            metrics = {"loss": jax.lax.pmean(loss, all_axes)}
            if aux is not None:
                metrics["aux"] = _over_replicas(aux)
            return metrics

        # check_vma=False: with the check on, differentiating w.r.t. a
        # replicated param auto-inserts a psum during transpose, which would
        # double-count with the synchronizers' explicit collectives — this
        # framework owns the gradient collective (compression, bucketing,
        # reduce-scatter), so the automatic one must stay off.
        sharded = jax.shard_map(
            local_step, mesh=self._mesh,
            in_specs=(state_specs, ps_specs, batch_specs),
            out_specs=(state_specs, ps_out_specs, metric_specs),
            check_vma=False)
        step_fn = train_jit(sharded,
                            donate_argnums=(0,) if self._donate else ())
        step_fn_nodonate = train_jit(sharded) if self._donate else step_fn
        eval_fn = jax.jit(jax.shard_map(
            local_eval, mesh=self._mesh,
            in_specs=(state_specs, ps_specs, batch_specs),
            out_specs={k: spec for k, spec in metric_specs.items()
                       if k != "counters"}, check_vma=False))

        # ----- serving forward-only lowering (DistributedStep.
        # predict_program): the SAME per-device gather-params +
        # fill-PS-holes path the eval program runs, but returning the
        # user's ``serve_fn(full_params, batch)`` fetches. The
        # out-structure comes from an abstract eval against the
        # per-device LOCAL batch shapes (axes bound so forward-pass mesh
        # collectives trace): leaves with a leading local-batch dim ship
        # sharded over the batch axes — remap_fetch reassembles the
        # global batch — and scalar leaves reduce like eval metrics.
        serve_batch_axes = tuple(
            self._strategy.graph_config.batch_axes or (axis,))

        def forward_builder(serve_fn: Callable, donate_batch: bool,
                            serve_batch=None):
            from autodist_tpu.utils.axis_env import bound_axes
            # serving feeds are usually a SUB-structure of the training
            # batch (features only, no labels) — the program's feed specs
            # come from the serve batch's own structure, by the same
            # per-leaf rule the train step uses
            if serve_batch is None:
                serve_batch = item.example_batch
            serve_specs = jax.tree_util.tree_map_with_path(
                lambda path, leaf: rep.batch_spec(np.ndim(leaf),
                                                  _normalize_path(path)),
                serve_batch)

            def local_aval(path, leaf):
                return jax.ShapeDtypeStruct(
                    rep.local_shape(np.shape(leaf), _normalize_path(path)),
                    leaf.dtype if hasattr(leaf, "dtype")
                    else np.asarray(leaf).dtype)
            local_batch = jax.tree_util.tree_map_with_path(
                local_aval, serve_batch)
            lead = [np.shape(l)[0]
                    for l in jax.tree_util.tree_leaves(local_batch)
                    if np.ndim(l) >= 1]
            local_rows = lead[0] if lead else 0
            param_avals = shapes_of(item.params)
            with bound_axes():
                out_aval = jax.eval_shape(serve_fn, param_avals,
                                          local_batch)
            out_leaves, out_treedef = jax.tree_util.tree_flatten(out_aval)
            # P is a tuple subclass, so spec trees are built by explicit
            # unflatten (tree_map would descend INTO the specs)
            flat_specs = [
                P(serve_batch_axes)
                if (np.ndim(a) >= 1 and local_rows
                    and np.shape(a)[0] == local_rows) else P()
                for a in out_leaves]
            out_specs = jax.tree_util.tree_unflatten(out_treedef,
                                                     flat_specs)

            def local_predict(state: TrainState, ps_vals, batch):
                full_params = _full_params(state, ps_vals)
                with sc.scope(sc.PREFILL):
                    out = serve_fn(full_params, batch)
                if N > 1:
                    # non-batch (replicated-spec) leaves must actually BE
                    # replicated on exit: reduce them the way eval
                    # metrics reduce
                    leaves = out_treedef.flatten_up_to(out)
                    leaves = [
                        v if len(s) else
                        (jax.lax.pmean(v, all_axes)
                         if jnp.issubdtype(jnp.asarray(v).dtype,
                                           jnp.inexact)
                         else jax.lax.pmax(v, all_axes))
                        for v, s in zip(leaves, flat_specs)]
                    out = jax.tree_util.tree_unflatten(out_treedef, leaves)
                return out

            sharded_predict = jax.shard_map(
                local_predict, mesh=self._mesh,
                in_specs=(state_specs, ps_specs, serve_specs),
                out_specs=out_specs, check_vma=False)
            # the per-leaf batch/replicated classification travels WITH
            # the program: serving's padded-row masking and per-request
            # fan-out must follow the sharding this lowering actually
            # applied, not re-derive it from output shapes (a replicated
            # leaf whose leading dim happens to equal the bucket size
            # would otherwise be sliced like per-example rows)
            batch_mask = jax.tree_util.tree_unflatten(
                out_treedef, [len(s) > 0 for s in flat_specs])
            return ForwardProgram(
                jax.jit(sharded_predict,
                        donate_argnums=(2,) if donate_batch else ()),
                batch_mask)

        def decode_builder(decode_fn: Callable, example_dstate):
            from autodist_tpu.utils.axis_env import bound_axes
            # decode state leaves are SLOT-major, not feed-path-shaped:
            # every array leaf leads with the slot dim and shards over the
            # batch axes (the per-path rules the train feed uses — seq
            # sharding for seq_feed_keys etc. — must not apply to KV
            # caches whose second dim is the sequence)
            n_batch = int(np.prod([self._mesh.shape[a]
                                   for a in serve_batch_axes] or [1]))
            state_leaves, dstate_treedef = jax.tree_util.tree_flatten(
                example_dstate)
            for leaf in state_leaves:
                if np.ndim(leaf) >= 1 and np.shape(leaf)[0] % n_batch:
                    raise ValueError(
                        "decode slot count %d is not divisible by the "
                        "batch-axes mesh extent %d — pick slots as a "
                        "multiple of the data-parallel degree"
                        % (np.shape(leaf)[0], n_batch))
            dstate_specs = jax.tree_util.tree_unflatten(
                dstate_treedef,
                [P(serve_batch_axes) if np.ndim(l) >= 1 else P()
                 for l in state_leaves])
            local_dstate = jax.tree_util.tree_unflatten(
                dstate_treedef,
                [jax.ShapeDtypeStruct(
                    ((np.shape(l)[0] // n_batch,) + tuple(np.shape(l)[1:])
                     if np.ndim(l) >= 1 else ()),
                    l.dtype if hasattr(l, "dtype")
                    else np.asarray(l).dtype)
                 for l in state_leaves])
            local_slots = ([np.shape(l)[0] // n_batch for l in state_leaves
                            if np.ndim(l) >= 1] or [0])[0]
            param_avals = shapes_of(item.params)
            with bound_axes():
                out_aval = jax.eval_shape(decode_fn, param_avals,
                                          local_dstate)
            out_leaves, out_treedef = jax.tree_util.tree_flatten(out_aval)
            flat_specs = [
                P(serve_batch_axes)
                if (np.ndim(a) >= 1 and local_slots
                    and np.shape(a)[0] == local_slots) else P()
                for a in out_leaves]
            out_specs = jax.tree_util.tree_unflatten(out_treedef,
                                                     flat_specs)

            def local_decode(state: TrainState, ps_vals, dstate):
                full_params = _full_params(state, ps_vals)
                with sc.scope(sc.DECODE):
                    out = decode_fn(full_params, dstate)
                if N > 1:
                    leaves = out_treedef.flatten_up_to(out)
                    leaves = [
                        v if len(s) else
                        (jax.lax.pmean(v, all_axes)
                         if jnp.issubdtype(jnp.asarray(v).dtype,
                                           jnp.inexact)
                         else jax.lax.pmax(v, all_axes))
                        for v, s in zip(leaves, flat_specs)]
                    out = jax.tree_util.tree_unflatten(out_treedef, leaves)
                return out

            sharded_decode = jax.shard_map(
                local_decode, mesh=self._mesh,
                in_specs=(state_specs, ps_specs, dstate_specs),
                out_specs=out_specs, check_vma=False)
            batch_mask = jax.tree_util.tree_unflatten(
                out_treedef, [len(s) > 0 for s in flat_specs])
            # the decode state is ALWAYS donated: the step's whole point
            # is mutating the KV cache in place, and the engine feeds the
            # previous step's output straight back in. Output shardings
            # are pinned to the slot specs: jit would otherwise
            # canonicalize them (e.g. to replicated on a 1-extent mesh),
            # and the fed-back caches would re-specialize the program —
            # one recompile per step, the exact failure this path exists
            # to rule out
            out_shardings = jax.tree_util.tree_unflatten(
                out_treedef,
                [NamedSharding(self._mesh, s) for s in flat_specs])
            return ForwardProgram(
                jax.jit(sharded_decode, donate_argnums=(2,),
                        out_shardings=out_shardings), batch_mask)

        # ----- fused multi-step lowering (DistributedStep.multi_step):
        # k microsteps under lax.scan over a stacked [k, ...] batch in ONE
        # jitted dispatch. Host-PS updates are device-emulated inside the
        # scan against the superstep-start snapshot: the SAME per-variable
        # little-tree optimizer apply the store runs on host
        # (``PSStore._apply_impl``), so sync-PS numerics match the
        # per-step loop exactly — the carry writes back at flush_ps sync
        # points instead of paying a D2H round-trip per microstep.
        ps_opt_aval = {
            n: jax.eval_shape(
                lambda a: optimizer.init({"v": a}),
                jax.ShapeDtypeStruct(tuple(var_infos[n].shape),
                                     np.dtype(var_infos[n].dtype)))
            for n in sorted(ps_names)}
        ps_opt_specs = jax.tree_util.tree_map(lambda _: P(), ps_opt_aval)
        stacked_batch_specs = jax.tree_util.tree_map(
            lambda s: P(None, *s), batch_specs,
            is_leaf=lambda x: isinstance(x, P))

        def _ps_apply_device(vals, opts, ps_grads, lr_scale=None):
            new_vals, new_opts = {}, {}
            for n in sorted(vals):
                g = ps_grads[n]
                if isinstance(g, tuple):
                    # sparse (ids, values) pair: densify exactly as the
                    # host store does before its apply (np.add.at there,
                    # scatter-add here — same sum)
                    info = var_infos[n]
                    g = embedding_lib.scatter_add_dense(
                        g[0], g[1], int(info.shape[0]),
                        tuple(info.shape[1:]))
                updates, nopt = optimizer.update(
                    {"v": g}, opts[n], {"v": vals[n]})
                if lr_scale is not None:
                    # mirror of PSStore.update_scale on the host path
                    updates = jax.tree_util.tree_map(
                        lambda u: (u * lr_scale).astype(u.dtype), updates)
                new_vals[n] = optax.apply_updates({"v": vals[n]}, updates)["v"]
                new_opts[n] = nopt
            return new_vals, new_opts

        def local_multi(state: TrainState, ps_vals, ps_opt, batches):
            def body(carry, batch):
                st, vals, opts = carry
                # quantized-wire emulation: the carry holds EXACT f32
                # values (like the host store), so each microstep applies
                # the same codec the per-step wire pays — values round-trip
                # quantize->dequantize before the loss (the pull wire) and
                # the reduced gradient round-trips before the emulated
                # apply (the push wire). Fused numerics therefore match
                # the per-step quantized loop, while the actual host wire
                # is crossed once per superstep instead of once per step.
                wire_vals = {n: (collectives.quant_wire(v)
                                 if n in ps_quant else v)
                             for n, v in vals.items()}
                new_st, ps_grads, metrics = local_step(st, wire_vals, batch)
                if ps_quant:
                    ps_grads = {
                        n: (collectives.dequant_wire(
                            g, tuple(var_infos[n].shape),
                            np.dtype(var_infos[n].dtype))
                            if isinstance(g, dict) else g)
                        for n, g in ps_grads.items()}
                if ps_names:
                    scale = (st.sync_state["sentinel"]["lr_scale"][0]
                             if guard else None)
                    with sc.scope(sc.OPTIMIZER):
                        new_vals, new_opts = _ps_apply_device(
                            vals, opts, ps_grads, scale)
                    if guard:
                        # the microstep's verdict gates the device-
                        # emulated PS apply exactly like it gates the
                        # per-step host push: a bad microstep's PS update
                        # is discarded, the carry flows on unchanged
                        okb = metrics["sentinel"]["ok"].astype(bool)
                        sel = lambda a, b: jnp.where(okb, a, b)  # noqa: E731
                        new_vals = jax.tree_util.tree_map(sel, new_vals,
                                                          vals)
                        new_opts = jax.tree_util.tree_map(sel, new_opts,
                                                          opts)
                    vals, opts = new_vals, new_opts
                return (new_st, vals, opts), metrics
            (st, vals, opts), stacked_metrics = jax.lax.scan(
                body, (state, ps_vals, ps_opt), batches)
            return st, vals, opts, stacked_metrics

        # the fused carry holds RAW f32 PS values (the store's exact
        # copy); only the per-step path's entry values are wire-form
        ps_raw_specs = {n: P() for n in sorted(ps_names)}

        def fused_builder(donate: bool):
            sharded_multi = jax.shard_map(
                local_multi, mesh=self._mesh,
                in_specs=(state_specs, ps_raw_specs, ps_opt_specs,
                          stacked_batch_specs),
                out_specs=(state_specs, ps_raw_specs, ps_opt_specs,
                           metric_specs),
                check_vma=False)
            return train_jit(sharded_multi,
                             donate_argnums=(0, 1, 2) if donate else ())

        ps_syncs = [s for s in syncs.values()
                    if s.__class__.__name__ == "PSSynchronizer"]
        # static per-microstep AR wire accounting for the quantized
        # buckets: payload bytes (int8 body + f32 scale sidecar) vs the
        # full-width bytes the same payload would have cost — bumped into
        # the wire.* telemetry counters once per dispatch (x k fused), so
        # the measured reduction is visible without any D2H. The SAME
        # formula prices the cost model and the drift tests
        # (collectives.int8_wire_payload_bytes).
        wire_q_step = wire_fp_step = 0.0
        if N > 1:
            for b in buckets:
                if b.compressor_name in ("Int8Compressor",
                                         "Int8CompressorEF"):
                    q_b, f_b = collectives.int8_wire_payload_bytes(
                        b.total_size, np.dtype(b.dtype).itemsize)
                    wire_q_step += q_b
                    wire_fp_step += f_b
        # ZeRO-sharded static accounting: per-step rs/ag payload bytes
        # (zero.rs_bytes / zero.ag_bytes counters — same formula the cost
        # model prices) and the projected per-chip opt-state saving
        # ((P-1)/P of each zero var's share of the full optimizer state —
        # the zero.hbm_saved_bytes gauge, and what the ADT501 plan gate
        # stops charging)
        zero_rs_step = sum(zs.rs_payload_bytes()
                           for zs in zero_syncs.values())
        zero_ag_step = sum(zs.ag_payload_bytes()
                           for zs in zero_syncs.values())
        zero_saved = 0.0
        if zero_syncs and item.optimizer is not None:
            opt_total = float(sum(
                int(np.prod(tuple(l.shape) or (1,)))
                * np.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(item.opt_state_spec)))
            params_total = float(item.total_bytes()) or 1.0
            zero_saved = sum(
                opt_total * var_infos[n].byte_size / params_total
                * (self.num_replicas - 1) / self.num_replicas
                for n in zero_names)
        exchange = []
        if N > 1:
            exchange = (
                [{"kind": "pack" if len(grp.var_names) > 1 else "var",
                  "vars": list(grp.var_names), "bytes": grp.nbytes}
                 for grp in sync_groups]
                + [{"kind": "zero", "vars": [n],
                    "bytes": int(var_infos[n].byte_size)}
                   for n in sorted(zero_names)]
                + [{"kind": "bucket", "vars": list(b.var_names),
                    "bytes": b.total_size * np.dtype(b.dtype).itemsize}
                   for b in buckets]
                + [{"kind": "sync", "vars": [n],
                    "bytes": int(var_infos[n].byte_size)}
                   for n in syncs
                   if n not in bucketed_names and n not in plain])
        metadata = {
            # proxied (device-cached) PS vars keep a single destination;
            # host-resident plans carry one owner per shard
            "ps_assignments": dict(
                {s.var_name: s.reduction_destination for s in ps_syncs},
                **{n: list(p.destinations) for n, p in ps_plans.items()}),
            "ps_host_resident": sorted(ps_names),
            "ps_wire_int8": sorted(ps_quant),
            "sparse_wire": sorted(sparse_wire),
            "buckets": [b.key for b in buckets],
            "per_var_compressors": per_var_comp,
            "wire_quant_bytes_per_step": wire_q_step,
            "wire_fp32_bytes_per_step": wire_fp_step,
            "zero_sharded": sorted(zero_names),
            "zero_wire_int8": sorted(n for n, zs in zero_syncs.items()
                                     if zs.wire_dtype == "int8"),
            "zero_rs_bytes_per_step": zero_rs_step,
            "zero_ag_bytes_per_step": zero_ag_step,
            "zero_hbm_saved_bytes": zero_saved,
            # staleness window for the runner's cross-process pacing
            "staleness": max(
                [s.staleness for s in ps_syncs]
                + [ps_store.max_staleness() if ps_store else 0]),
            "async": (any(not s.sync_mode for s in ps_syncs)
                      or (ps_store.any_async() if ps_store else False)),
            # health guards compiled into the program? (the ADT420 lint
            # and the Runner's policy both consult this)
            "sentinel_guards": guard,
            # "f32" | "bf16" — the compute tier this program lowered with
            # (f32 master params/opt-state/accumulation either way; the
            # ADT60x numerics lints and step_stats report it)
            "compute_dtype": compute_dtype,
            "grad_fault_plan": grad_plan.describe(),
            # the default path on more than one replica: the option names
            # the training programs compile with (empty on one replica
            # and off the TPU), and every collective the gradient
            # exchange issues, in program order, with its payload
            "async_collectives": sorted(train_options),
            "grad_sync_groups": exchange,
        }
        logging.info("GraphTransformer: lowered %d vars (%d partitioned, "
                     "%d host-PS-resident, %d ZeRO-sharded, %d buckets) "
                     "over %d replicas",
                     len(layouts),
                     sum(1 for l in layouts.values() if l.partitioned),
                     len(ps_names), len(zero_names), len(buckets), N)
        return DistributedStep(
            mesh=self._mesh, step_fn=step_fn, step_fn_nodonate=step_fn_nodonate,
            layouts=layouts, layout_tree=layout_tree, strategy=self._strategy,
            model_item=item, mesh_axis=axis, sync_state_init=sync_state_init,
            metadata=metadata, eval_fn=eval_fn, ps_store=ps_store,
            holed_params_template=holed_params,
            fused_builder=fused_builder, forward_builder=forward_builder,
            decode_builder=decode_builder,
            zero_syncs=zero_syncs)
