"""Checkpoint saver — original-layout, framework-free restore.

Analog of reference ``autodist/checkpoint/saver.py:28-133``. The reference's
defining property (``saver.py:50-57``, ``docs/usage/tutorials/save-restore.md``):
checkpoints are written in the *original single-device namespace*, so they
load in vanilla TF with no AutoDist installed. Here the same contract:
``Saver.save`` gathers partitioned variables back to their full unpadded
shapes (``DistributedStep.gather_params``) and writes plain ``.npz`` files
keyed by the slash-joined variable names — loadable with ``numpy.load``
alone. Optimizer state is saved alongside (the reference saves slot
variables through the same saver), so training resumes exactly; a vanilla
consumer can ignore it.

Chief-only saving for shared filesystems mirrors the ``IS_AUTODIST_CHIEF``
gate (reference ``autodist/autodist.py:40-41``).
"""
import json
import os
import threading
import time
import zipfile
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from autodist_tpu import const
from autodist_tpu.checkpoint import integrity
from autodist_tpu.checkpoint.integrity import CheckpointDamaged
from autodist_tpu.kernel.common import variable_utils
from autodist_tpu.runtime.faultinject import checkpoint_fault
from autodist_tpu.telemetry import spans as tel
from autodist_tpu.utils import logging


def _tree_to_flat(tree) -> Dict[str, np.ndarray]:
    names, leaves, _ = variable_utils.flatten_named(tree)
    return {n: np.asarray(jax.device_get(l)) for n, l in zip(names, leaves)}


def _read_npz(path: str) -> Dict[str, np.ndarray]:
    """Fully read one npz, converting every read-path failure — vanished
    file, I/O error, zip/npy corruption — to :class:`CheckpointDamaged`,
    so the restore fallback loop can catch exactly that and configuration
    errors (template mismatch in ``_flat_to_tree``) stay loud. In
    particular a mid-read ``FileNotFoundError`` must NOT escape: the
    caller's no-valid-checkpoint sentinel shares that type, and
    ``Runner.init`` would misread the error as "start fresh"."""
    try:
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    except (OSError, ValueError, zipfile.BadZipFile) as e:
        raise CheckpointDamaged("%s unreadable: %s" % (path, e)) from e


def _flat_to_tree(template, flat: Dict[str, np.ndarray]):
    names, leaves, treedef = variable_utils.flatten_named(template)
    out = []
    for n, leaf in zip(names, leaves):
        if n not in flat:
            raise KeyError("checkpoint missing variable %r" % n)
        arr = flat[n]
        want = tuple(getattr(leaf, "shape", ()))
        if tuple(arr.shape) != want:
            raise ValueError("checkpoint var %r has shape %s, model wants %s"
                             % (n, arr.shape, want))
        out.append(arr)
    return variable_utils.unflatten_named(treedef, out)


import re as _re


def _skip_unhealthy(status) -> bool:
    """Automatic restore paths (``latest()``, fallback ``restore()``,
    auto-resume) must never load a checkpoint stamped ``healthy: false``
    — it was committed while the sentinel's verdict was bad, i.e. it IS
    the poisoned state rollback exists to escape. Pre-stamp checkpoints
    (``healthy`` absent → None) stay resumable: healthy-unknown, logged."""
    if status.healthy is False:
        logging.warning("checkpoint step %d is stamped UNHEALTHY "
                        "(committed under a bad sentinel verdict); "
                        "skipping", status.step)
        tel.counter_add("ckpt.unhealthy_skipped")
        return True
    if status.healthy is None:
        logging.info("checkpoint step %d predates the health stamp "
                     "(healthy-unknown); treating as resumable",
                     status.step)
    return False


def scan_checkpoint_metas(directory: str, pattern) -> list:
    """Sorted (step, filename) pairs for meta files matching ``pattern``
    (a compiled regex whose group 1 is the step). Foreign files in a
    shared directory are ignored, not crashed on. Shared by
    :class:`Saver` and :class:`ShardedSaver` so retention/discovery
    semantics cannot drift apart."""
    out = []
    for f in os.listdir(directory):
        m = pattern.match(f)
        if m:
            out.append((int(m.group(1)), f))
    return sorted(out)


def sentinel_save_vetoed(runner_or_step) -> bool:
    """Quarantine gate shared by both savers: a Runner with an active
    sentinel vetoes saves while the health verdict is bad — the poisoned
    state must never become the newest committed checkpoint (it would be
    exactly what last-good fallback and auto-resume restore).

    The veto returns BEFORE the cross-process gather collectives, so it
    is only taken when every process provably reaches the same decision:
    in-graph verdicts are all-reduced, so guarded programs qualify; a
    LOSS-ONLY sentinel (step_fn mode, ADT420) watches user metrics that
    need not be replica-uniform, so in a multi-process job it must not
    veto — a divergent early return would strand the peers inside the
    gather. There the save proceeds and the ``healthy`` stamp (written
    by the chief alone, hence consistent) records the suspicion
    instead."""
    veto = getattr(runner_or_step, "sentinel_save_veto", None)
    if not (callable(veto) and veto()):
        return False
    if jax.process_count() > 1:
        dstep = getattr(runner_or_step, "distributed_step", None)
        metadata = getattr(dstep, "metadata", None) or {}
        if not metadata.get("sentinel_guards", False):
            logging.warning(
                "sentinel quarantine NOT vetoing this save: loss-only "
                "monitoring is not replica-uniform in a multi-process "
                "job (a divergent veto would deadlock the gather "
                "collectives) — the checkpoint will carry its honest "
                "healthy stamp instead")
            return False
    tel.counter_add("sentinel.save_vetoes")
    logging.warning("checkpoint save vetoed: sentinel quarantine "
                    "(health verdict is bad)")
    return True


def sentinel_health_stamp(runner_or_step) -> bool:
    """The ``healthy`` stamp this save should carry. True when no
    sentinel is armed (an unguarded run has no evidence of ill health —
    its checkpoints stay resumable); False only when a sentinel judged
    the state bad yet the save proceeded (quarantine disabled)."""
    fn = getattr(runner_or_step, "sentinel_healthy", None)
    return bool(fn()) if callable(fn) else True


class BackgroundWriter:
    """At most one background checkpoint write in flight. ``wait()`` joins
    the pending write and re-raises any error it hit — a failed checkpoint
    must never look like a success. Shared by :class:`Saver` and
    :class:`~autodist_tpu.checkpoint.sharded.ShardedSaver`."""

    def __init__(self, name: str):
        self._name = name
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def submit(self, fn):
        self.wait()  # serialize: at most one write in flight
        self._error = None

        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — re-raised in wait()
                self._error = e

        self._thread = threading.Thread(target=run, name=self._name,
                                        daemon=False)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
            err, self._error = self._error, None
            if err is not None:
                raise err


class Saver:
    """Save/restore distributed training state in the original layout.

    ``async_save=True`` moves the file writes to a background thread: the
    collective gathers (which every process must join) still happen inside
    ``save()``, but the host-side npz serialization — the slow part for
    large models — overlaps subsequent training steps. At most one write is
    in flight; a new ``save()`` joins the previous one first, and
    ``wait()`` joins explicitly (call before reading ``latest()``)."""

    def __init__(self, directory: Optional[str] = None, max_to_keep: int = 5,
                 chief_only: bool = True, async_save: bool = False):
        self.directory = directory or const.DEFAULT_CHECKPOINT_DIR
        self.max_to_keep = max_to_keep
        self.chief_only = chief_only
        self.async_save = async_save
        self._writer = BackgroundWriter("adt-ckpt-writer")
        os.makedirs(self.directory, exist_ok=True)

    # ------------------------------------------------------------------ save

    def save(self, runner_or_step, state=None, step: Optional[int] = None) -> Optional[str]:
        """Write a checkpoint. Accepts a Runner (uses its state) or a
        DistributedStep + explicit TrainState. The gathers are collectives —
        EVERY process must call save(); only the file writes are
        chief-gated."""
        if hasattr(runner_or_step, "distributed_step"):  # Runner
            dstep = runner_or_step.distributed_step
            state = state if state is not None else runner_or_step.state
        else:
            dstep = runner_or_step
        if state is None:
            raise ValueError("no state to save")
        # epoch fence BEFORE any work (and any file): a zombie worker's
        # late save must leave the checkpoint directory byte-identical to
        # a run where it never woke (runtime/elastic.py)
        from autodist_tpu.runtime import elastic
        elastic.maybe_fence("ckpt.save")
        if sentinel_save_vetoed(runner_or_step):
            return None
        healthy = sentinel_health_stamp(runner_or_step)
        # cross-process collectives: run on all processes before any gating
        with tel.span("ckpt.gather", "ckpt"):
            params = dstep.gather_params(state)
            opt_state_host = dstep.gather_opt_state(state)
            sync_state_host = dstep.gather_sync_state(state)
        if step is None:
            step = int(jax.device_get(state.step))
        checkpoint_fault("collect", step=step)
        if self.chief_only and not const.is_chief():
            return None
        path = os.path.join(self.directory, "ckpt-%d" % step)
        meta = {"step": step, "format": "autodist_tpu.v1",
                "strategy_id": dstep.strategy.id, "healthy": healthy}

        def write():
            t_begin = time.monotonic()
            with tel.span("ckpt.write", "ckpt", step=int(step)):
                trees = [(".params.npz", _tree_to_flat(params)),
                         (".opt.npz", _tree_to_flat(opt_state_host))]
                sync_flat = _tree_to_flat(sync_state_host)
                if sync_flat:
                    trees.append((".sync.npz", sync_flat))
                # every data file goes to a .tmp sibling first and is
                # os.replace'd into place — a crash mid-serialization can
                # never leave a truncated npz under the FINAL name (the
                # torn write numpy.load would fail on with no indication
                # of why); the meta records each file's crc32+bytes so
                # post-commit damage is detectable (integrity.py)
                file_meta: Dict[str, dict] = {}
                finals = []
                for suffix, flat in trees:
                    final = path + suffix
                    tmp = final + ".tmp"
                    with open(tmp, "wb") as f:
                        # the non-seekable proxy digests the stream as it
                        # is written (zipfile falls back to data-descriptor
                        # mode, so the digest IS the bytes on disk) — no
                        # second read pass over a multi-GB checkpoint
                        w = integrity.Crc32Writer(f)
                        np.savez(w, **flat)
                    file_meta[os.path.basename(final)] = w.digest
                    finals.append((tmp, final))
                checkpoint_fault("write", path=path, step=int(step))
                for tmp, final in finals:
                    os.replace(tmp, final)
                meta["files"] = file_meta
                # meta last, atomically: a checkpoint only becomes visible
                # to _own_metas / latest() once all its data files exist.
                # Re-fenced at the COMMIT point: an epoch can change
                # between an async save's submit and its write landing
                elastic.maybe_fence("ckpt.commit")
                checkpoint_fault("meta", path=path, step=int(step))
                with open(path + ".meta.json.tmp", "w") as f:
                    json.dump(meta, f)
                os.replace(path + ".meta.json.tmp", path + ".meta.json")
                checkpoint_fault("committed", path=path, step=int(step))
            with tel.span("ckpt.gc", "ckpt"):
                self._gc()
            tel.counter_add("ckpt.saves")
            tel.hist_observe("ckpt.save_ms",
                             (time.monotonic() - t_begin) * 1e3)
            logging.info("saved checkpoint %s (step %d)", path, step)

        if not self.async_save:
            write()
            return path
        self._writer.submit(write)
        return path

    def wait(self):
        """Join a pending async write; re-raises any error the writer hit —
        a failed checkpoint must not look like a success."""
        self._writer.wait()

    _META_RE = _re.compile(r"^ckpt-(\d+)\.meta\.json$")

    def _own_metas(self):
        return scan_checkpoint_metas(self.directory, self._META_RE)

    def _gc(self):
        metas = self._own_metas()
        while len(metas) > self.max_to_keep:
            _, fname = metas.pop(0)
            victim = fname.replace(".meta.json", "")
            for suffix in (".meta.json", ".params.npz", ".opt.npz", ".sync.npz"):
                try:
                    os.remove(os.path.join(self.directory, victim + suffix))
                except FileNotFoundError:
                    pass
        # failed-attempt debris (.tmp siblings, data files whose meta —
        # the commit point — never landed) below the newest commit
        victims, _ = integrity.gc_candidates(self.directory, "plain")
        for f in victims:
            try:
                os.remove(os.path.join(self.directory, f))
                tel.counter_add("ckpt.gc_orphans")
            except FileNotFoundError:
                pass
        if victims:
            logging.info("checkpoint gc: removed %d failed-attempt files "
                         "(%s)", len(victims), ", ".join(victims[:6]))

    # --------------------------------------------------------------- restore

    def latest(self) -> Optional[str]:
        """Base path of the newest COMMITTED checkpoint — fast validation
        skips torn save attempts and structurally damaged steps with a
        logged reason."""
        self.wait()  # an in-flight async write must be visible to readers
        for status in integrity.committed_newest_first(self.directory,
                                                       "plain"):
            if status.committed:
                if _skip_unhealthy(status):
                    continue
                return status.base
            logging.warning("checkpoint step %d is %s, skipping: %s",
                            status.step, status.state,
                            "; ".join(status.problems[:3]))
        return None

    def restore_params(self, params_template, path: Optional[str] = None):
        """Params pytree in the original layout — usable with or without the
        framework (the vanilla-restore property)."""
        self.wait()  # the path from an async save() is valid only post-write
        path = path or self.latest()
        if path is None:
            raise FileNotFoundError("no checkpoint in %s" % self.directory)
        flat = _read_npz(path + ".params.npz")
        return _flat_to_tree(params_template, flat)

    def restore(self, runner, path: Optional[str] = None) -> Tuple[Any, int]:
        """Restore a Runner's distributed state; returns (state, step).

        **Last-good fallback**: with no explicit ``path``, checkpoints are
        tried newest-first, skipping torn attempts and damaged steps (fast
        validation up front, read-time zip-CRC failures during the load)
        with a logged reason and ``ckpt.fallback``/``ckpt.corrupt_shards``
        counters; hard-fails only when no valid checkpoint exists. An
        explicit ``path`` is validated and refused when damaged."""
        self.wait()  # the path from an async save() is valid only post-write
        if path is not None:
            # validate where the path POINTS — it need not live in this
            # saver's directory (restoring someone else's export)
            status = integrity.validate_plain(*integrity.parse_base(path))
            if not status.committed:
                tel.counter_add("ckpt.corrupt_shards", len(status.damaged))
                raise CheckpointDamaged(
                    "checkpoint %s is %s: %s" % (
                        path, status.state, "; ".join(status.problems[:5])))
            if status.healthy is False:
                # an EXPLICIT path is a human decision — honor it, loudly
                logging.warning("restoring %s despite its UNHEALTHY stamp "
                                "(explicit path overrides the quarantine)",
                                path)
            return self._restore_at(runner, path)
        tried = 0
        for status in integrity.committed_newest_first(self.directory,
                                                       "plain"):
            if not status.committed:
                logging.warning("restore: skipping step %d (%s): %s",
                                status.step, status.state,
                                "; ".join(status.problems[:3]))
                tel.counter_add("ckpt.fallback")
                tel.counter_add("ckpt.corrupt_shards", len(status.damaged))
                continue
            if _skip_unhealthy(status):
                tel.counter_add("ckpt.fallback")
                continue
            tried += 1
            try:
                return self._restore_at(runner, status.base)
            except (CheckpointDamaged, zipfile.BadZipFile) as e:
                if jax.process_count() > 1:
                    raise  # peers must all restore the SAME step
                logging.warning("restore: step %d damaged mid-read (%s); "
                                "falling back", status.step, e)
                tel.counter_add("ckpt.fallback")
                tel.counter_add("ckpt.corrupt_shards")
        raise FileNotFoundError(
            "no valid checkpoint in %s (%d committed candidate(s) tried)"
            % (self.directory, tried))

    def _restore_at(self, runner, path: str) -> Tuple[Any, int]:
        dstep = runner.distributed_step
        params = self.restore_params(dstep.model_item.params, path)
        if dstep.model_item.optimizer is not None:
            opt_flat = _read_npz(path + ".opt.npz")
            opt_template = jax.eval_shape(dstep.model_item.optimizer.init,
                                          dstep.model_item.params)
            opt_state = _flat_to_tree(opt_template, opt_flat)
        else:
            # step_fn mode: whatever optimizer state exists lives inside
            # the user's opaque state (saved under params)
            opt_state = {}
        sync_state = None
        if os.path.exists(path + ".sync.npz"):
            sync_flat = _read_npz(path + ".sync.npz")
            try:
                sync_state = _flat_to_tree(dstep._sync_state_init(), sync_flat)
            except (KeyError, ValueError) as e:
                logging.warning("sync state in checkpoint incompatible with "
                                "current strategy (%s); reinitializing", e)
        state = dstep.init_state(params, opt_state, sync_state)
        try:
            with open(path + ".meta.json") as f:
                step = json.load(f)["step"]
        except (OSError, json.JSONDecodeError, KeyError) as e:
            raise CheckpointDamaged(
                "%s.meta.json unreadable: %s" % (path, e)) from e
        # advance the step counter to the saved step
        from autodist_tpu.train_state import TrainState
        state = TrainState(step=dstep._put(np.asarray(step, np.int32),
                                           jax.sharding.PartitionSpec()),
                           params=state.params, opt_state=state.opt_state,
                           sync_state=state.sync_state)
        runner.state = state
        notify = getattr(runner, "notify_state_restored", None)
        if callable(notify):
            notify()  # re-sync process-local sentinel LR scale
        tel.counter_add("ckpt.restores")
        logging.info("restored checkpoint %s (step %d)", path, step)
        return state, step
