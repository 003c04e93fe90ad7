"""Training health sentinel: in-graph guards, skip/rollback policy,
gradient fault injection, healthy-stamped checkpoints.

The acceptance matrix (ISSUE 9):

- zero-overhead clean path: guards add no dispatches and no extra
  readbacks, and guarded numerics match the unguarded run exactly;
- chaos proof under ``ADT_GRAD_FAULT_PLAN``: (a) a transient NaN step is
  skipped in-graph and the run converges to the fault-free loss, (b) a
  sustained corruption rolls back to the last healthy-stamped checkpoint
  and completes without ``TrainingDiverged``, (c) the same plan with the
  sentinel disabled demonstrably corrupts the run;
- fused parity: ``multi_step(k=4)`` under guards is allclose to the
  guarded per-step loop and a mid-scan NaN poisons exactly that
  microstep's stacked verdict;
- quarantine: saves vetoed while the verdict is bad, the ``healthy``
  stamp steers restore/auto-resume away from poisoned checkpoints, and
  pre-stamp checkpoints stay resumable (healthy-unknown).

Fast variants run in tier-1; the heavier strategy matrix is slow-marked
for nightly-chaos.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.runtime.sentinel import (Sentinel, SentinelPolicy,
                                           TrainingDiverged, resolve_policy)
from autodist_tpu.telemetry import spans as tel


def _problem(seed=0):
    rng = np.random.RandomState(seed)
    params = {"w": jnp.asarray(rng.randn(4, 2).astype(np.float32)),
              "b": jnp.zeros((2,), jnp.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)

    batch = {"x": rng.randn(16, 4).astype(np.float32),
             "y": rng.randn(16, 2).astype(np.float32)}
    return params, loss_fn, batch


def _build(make_builder, params, loss_fn, batch, sentinel=None, opt=None):
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=make_builder())
    runner = ad.build(loss_fn, opt or optax.adam(0.1), params, batch,
                      sentinel=sentinel)
    runner.init(params)
    return runner


def _train(runner, batch, steps):
    return [float(runner.run(batch)["loss"]) for _ in range(steps)]


def _set_plan(monkeypatch, faults):
    monkeypatch.setenv("ADT_GRAD_FAULT_PLAN",
                       json.dumps({"faults": faults}))


# ------------------------------------------------------------ clean path


def test_clean_path_zero_overhead_and_parity():
    """Guards must be free on the healthy path: identical numerics,
    identical dispatch count, identical readback count — the verdict
    rides the existing metrics transfer."""
    params, loss_fn, batch = _problem()
    plain = _build(lambda: S.AllReduce(), params, loss_fn, batch)
    losses_plain = _train(plain, batch, 6)
    d_plain = plain.distributed_step.dispatches
    rb_plain = tel.counters()["runner.readbacks"]

    guarded = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                     sentinel=True)
    losses_guarded = _train(guarded, batch, 6)
    assert guarded.distributed_step.dispatches == d_plain
    assert tel.counters()["runner.readbacks"] == rb_plain
    np.testing.assert_allclose(losses_guarded, losses_plain, rtol=1e-6)
    gp = guarded.distributed_step.gather_params(guarded.state)
    pp = plain.distributed_step.gather_params(plain.state)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), gp, pp)
    stats = guarded.step_stats()["sentinel"]
    assert stats["skips"] == 0 and stats["rollbacks"] == 0
    assert stats["last_grad_norm"] is not None
    assert stats["quarantined"] is False
    autodist_tpu.reset()


# ------------------------------------------- chaos criteria (a) and (c)


@pytest.mark.parametrize("name,make_builder", [
    ("AllReduce", lambda: S.AllReduce()),
    ("PS", lambda: S.PS()),
], ids=["AllReduce", "PS"])
def test_transient_nan_skipped_and_converges(monkeypatch, name,
                                             make_builder):
    """Criterion (a): a NaN gradient at one step is discarded in-graph
    (params carry unchanged, PS push suppressed) and the run converges
    to the fault-free loss."""
    params, loss_fn, batch = _problem()
    clean = _build(make_builder, params, loss_fn, batch)
    loss_clean = _train(clean, batch, 30)[-1]

    _set_plan(monkeypatch, [{"var": "w", "mode": "nan", "step": 3}])
    runner = _build(make_builder, params, loss_fn, batch, sentinel=True)
    losses = _train(runner, batch, 30)
    assert all(np.isfinite(losses))
    # the skipped step's update was discarded: the NEXT step sees the
    # same params, so its loss repeats the pre-fault value
    assert losses[4] == pytest.approx(losses[3])
    stats = runner.step_stats()["sentinel"]
    assert stats["skips"] == 1
    assert tel.counters()["sentinel.skips"] == 1
    assert tel.counters()["sentinel.nan_steps"] == 1
    # the discard is in-graph: a skipped step is still one dispatch
    assert runner.distributed_step.dispatches == 30
    # one discarded update costs one step of progress, not convergence
    assert losses[-1] == pytest.approx(loss_clean, rel=0.15)
    if name == "PS":
        assert tel.counters()["sentinel.ps_suppressed"] >= 1
    autodist_tpu.reset()


def test_sentinel_disabled_same_plan_corrupts(monkeypatch):
    """Criterion (c): without the sentinel the identical plan poisons the
    run — the guard is what makes the difference."""
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "nan", "step": 3}])
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch)
    losses = _train(runner, batch, 8)
    assert not np.isfinite(losses[-1])
    autodist_tpu.reset()


def test_grad_norm_limit_skips_scale_spike(monkeypatch):
    """A finite scale-spike passes the NaN guards but trips the
    grad-norm limit; ``nan_steps`` stays untouched (it counts nonfinite
    faults only)."""
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "scale", "step": 2,
                             "factor": 1e6}])
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                    sentinel=SentinelPolicy(grad_norm_limit=100.0))
    losses = _train(runner, batch, 8)
    assert all(np.isfinite(losses))
    assert losses[3] == pytest.approx(losses[2])  # spiked update discarded
    assert runner.step_stats()["sentinel"]["skips"] == 1
    assert tel.counters()["sentinel.nan_steps"] == 0
    autodist_tpu.reset()


def test_bitflip_injection_is_deterministic(monkeypatch):
    """Bit-flip mode: flipping a float32 exponent MSB blows the gradient
    up to nonfinite/huge — caught by the guards — and two identical runs
    inject identically (step-keyed, not wall-clock-keyed)."""
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "bitflip", "step": 2,
                             "bit": 30, "index": 0}])
    skips = []
    for _ in range(2):
        runner = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                        sentinel=SentinelPolicy(grad_norm_limit=100.0))
        losses = _train(runner, batch, 6)
        assert all(np.isfinite(losses))
        skips.append(runner.step_stats()["sentinel"]["skips"])
    assert skips[0] == skips[1] == 1
    autodist_tpu.reset()


def test_sharded_storage_grad_norm_is_exact():
    """Partitioned storage reports the SAME global grad norm as
    replicated storage: sharded leaves contribute ``local * S/N``
    through one psum — the scaling must be exact, not approximate."""
    rng = np.random.RandomState(0)
    params = {"big": jnp.asarray(rng.randn(64, 8).astype(np.float32)),
              "w": jnp.asarray(rng.randn(8, 2).astype(np.float32))}

    def loss_fn(p, b):
        return jnp.mean(((b["x"] @ p["big"]) @ p["w"] - b["y"]) ** 2)

    batch = {"x": rng.randn(16, 64).astype(np.float32),
             "y": rng.randn(16, 2).astype(np.float32)}
    part = _build(lambda: S.PartitionedAR(), params, loss_fn, batch,
                  sentinel=True, opt=optax.sgd(0.01))
    assert any(l.partitioned for l in part.distributed_step.layouts.values())
    norm_part = float(part.run(batch)["sentinel"]["grad_norm"])
    repl = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                  sentinel=True, opt=optax.sgd(0.01))
    norm_repl = float(repl.run(batch)["sentinel"]["grad_norm"])
    np.testing.assert_allclose(norm_part, norm_repl, rtol=1e-4)
    autodist_tpu.reset()


# -------------------------------------------------- fused parity (k=4)


@pytest.mark.parametrize("name,make_builder", [
    ("AllReduce", lambda: S.AllReduce()),
    ("PS", lambda: S.PS()),
], ids=["AllReduce", "PS"])
def test_fused_guarded_parity_and_microstep_verdict(monkeypatch, name,
                                                    make_builder):
    """Fused k=4 under guards: allclose to the guarded per-step loop
    (params + opt + skip decisions), and a mid-scan NaN microstep
    poisons exactly that microstep's stacked verdict."""
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "nan", "step": 2}])
    stack = jax.tree_util.tree_map(lambda l: np.stack([l] * 4), batch)

    per_step = _build(make_builder, params, loss_fn, batch, sentinel=True)
    step_losses = _train(per_step, batch, 4)
    per_step.distributed_step.flush_ps()
    p_ref = per_step.distributed_step.gather_params(per_step.state)
    o_ref = per_step.distributed_step.gather_opt_state(per_step.state)
    skips_ref = per_step.step_stats()["sentinel"]["skips"]

    fused = _build(make_builder, params, loss_fn, batch, sentinel=True)
    handle = fused.run_superstep(stack, sync=True)
    oks = [int(m["sentinel"]["ok"]) for m in
           [jax.tree_util.tree_map(lambda a, i=i: np.asarray(a)[i], handle)
            for i in range(4)]]
    assert oks == [1, 1, 0, 1]  # exactly the faulted microstep is bad
    fused_losses = [float(np.asarray(handle["loss"])[i]) for i in range(4)]
    np.testing.assert_allclose(fused_losses, step_losses, rtol=1e-5)
    fused.distributed_step.flush_ps()
    p_fused = fused.distributed_step.gather_params(fused.state)
    o_fused = fused.distributed_step.gather_opt_state(fused.state)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        p_fused, p_ref)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        o_fused, o_ref)
    assert fused.step_stats()["sentinel"]["skips"] == skips_ref == 1
    autodist_tpu.reset()


# --------------------------------------- rollback ladder (criterion b)


def test_sustained_corruption_rolls_back_and_completes(monkeypatch,
                                                       tmp_path):
    """Criterion (b): a bounded sustained NaN window exhausts the skip
    budget, training rolls back to the last healthy-stamped checkpoint,
    the widened replay budget skips through the window, and the run
    completes without ``TrainingDiverged``."""
    from autodist_tpu.checkpoint.saver import Saver
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "nan", "step": 4,
                             "until": 6}])
    policy = SentinelPolicy(max_skips_per_window=2, window_steps=50)
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                    sentinel=policy)
    saver = Saver(directory=str(tmp_path), max_to_keep=10)
    import itertools
    history = runner.fit(itertools.repeat(batch), steps=16, save_every=2,
                         saver=saver)
    assert len(history) == 16
    stats = runner.step_stats()["sentinel"]
    assert stats["rollbacks"] == 1
    # pass 1 skips all 3 faulty steps (rollback pends on the 3rd, past
    # budget 2); the replay skips them again under the widened budget
    assert stats["skips"] == 6
    assert tel.counters()["sentinel.rollbacks"] == 1
    assert tel.counters()["ckpt.restores"] >= 1
    final_loss = float(history[-1]["loss"])
    assert np.isfinite(final_loss)
    # training genuinely progressed past the fault window
    assert final_loss < float(history[0]["loss"])
    autodist_tpu.reset()


def test_unbounded_corruption_escalates_to_typed_failure(monkeypatch,
                                                         tmp_path):
    """The escalation ladder's hard floor: an unbounded fault defeats
    skip-widening and LR-halving, and the run fails with the typed
    ``TrainingDiverged`` after ``max_rollbacks_per_step`` rollbacks."""
    from autodist_tpu.checkpoint.saver import Saver
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "nan", "step": 4,
                             "until": 100000}])
    policy = SentinelPolicy(max_skips_per_window=1, window_steps=50,
                            max_rollbacks_per_step=2)
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                    sentinel=policy)
    saver = Saver(directory=str(tmp_path), max_to_keep=10)
    import itertools
    with pytest.raises(TrainingDiverged, match="escalation ladder"):
        runner.fit(itertools.repeat(batch), steps=64, save_every=2,
                   saver=saver)
    assert runner.step_stats()["sentinel"]["rollbacks"] == 2
    # the second rollback at the same step halved the effective LR
    assert runner.sentinel.lr_scale == pytest.approx(0.5)
    assert tel.counters()["sentinel.lr_halvings"] == 1
    autodist_tpu.reset()


def test_rollback_without_checkpoints_is_typed(monkeypatch, tmp_path):
    """A rollback with nothing to restore must fail with the typed
    error naming the fix, not a generic FileNotFoundError."""
    params, loss_fn, batch = _problem()
    monkeypatch.setenv("ADT_CKPT_DIR", str(tmp_path))
    _set_plan(monkeypatch, [{"var": "w", "mode": "nan", "step": 1,
                             "until": 100000}])
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                    sentinel=SentinelPolicy(max_skips_per_window=1,
                                            window_steps=50))
    with pytest.raises(TrainingDiverged, match="no healthy committed"):
        _train(runner, batch, 10)
    autodist_tpu.reset()


def test_lr_halving_scales_updates_exactly():
    """The escalation's LR mechanism: halving ``lr_scale`` through the
    sync_state halves the applied update exactly (linear-in-lr optax
    semantics) without recompiling."""
    params, loss_fn, batch = _problem()
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                    sentinel=True, opt=optax.sgd(0.1))
    ref = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                 sentinel=True, opt=optax.sgd(0.05))
    sen = Sentinel(SentinelPolicy(), runner)
    sen._halve_lr()  # lr_scale 1.0 -> 0.5
    d_half = runner.distributed_step.dispatches
    runner.run(batch)
    assert runner.distributed_step.dispatches == d_half + 1  # no recompile
    ref.run(batch)
    p_half = runner.distributed_step.gather_params(runner.state)
    p_ref = ref.distributed_step.gather_params(ref.state)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), p_half,
        p_ref)
    autodist_tpu.reset()


# ------------------------------------------- quarantine + healthy stamp


def test_quarantine_vetoes_saves_and_stamps(monkeypatch, tmp_path):
    """While the verdict is bad: saves are vetoed (quarantine on) or
    stamped unhealthy (quarantine off); automatic restore paths skip the
    unhealthy stamp, an explicit path overrides it."""
    from autodist_tpu.checkpoint import integrity
    from autodist_tpu.checkpoint.saver import Saver
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "nan", "step": 2,
                             "until": 100000}])
    policy = SentinelPolicy(max_skips_per_window=100, window_steps=10)
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch,
                    sentinel=policy)
    saver = Saver(directory=str(tmp_path))
    _train(runner, batch, 2)          # healthy so far
    assert saver.save(runner) is not None
    healthy_base = saver.latest()
    _train(runner, batch, 2)          # now inside the fault window
    assert runner.sentinel_save_veto()
    assert saver.save(runner) is None  # vetoed
    assert tel.counters()["sentinel.save_vetoes"] == 1

    # quarantine off: the save proceeds but carries the honest stamp
    runner.sentinel.policy.quarantine = False
    assert not runner.sentinel_save_veto()
    bad_base = saver.save(runner)
    assert bad_base is not None and bad_base != healthy_base
    status = integrity.validate_plain(*integrity.parse_base(bad_base))
    assert status.committed and status.healthy is False
    good = integrity.validate_plain(*integrity.parse_base(healthy_base))
    assert good.healthy is True

    # automatic paths skip the poisoned newest step
    assert saver.latest() == healthy_base
    _, step = saver.restore(runner)
    assert step == int(healthy_base.rsplit("ckpt-", 1)[1])
    assert tel.counters()["ckpt.unhealthy_skipped"] >= 2
    # an explicit path is a human override
    _, step = saver.restore(runner, path=bad_base)
    assert step == int(bad_base.rsplit("ckpt-", 1)[1])
    autodist_tpu.reset()


def test_prestamp_checkpoint_is_healthy_unknown(tmp_path):
    """Backfill semantics: a checkpoint whose meta predates the stamp
    classifies healthy-unknown (None) — resumable, never rejected."""
    from autodist_tpu.checkpoint import integrity
    from autodist_tpu.checkpoint.saver import Saver
    params, loss_fn, batch = _problem()
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch)
    saver = Saver(directory=str(tmp_path))
    base = saver.save(runner)
    meta_path = base + ".meta.json"
    with open(meta_path) as f:
        meta = json.load(f)
    assert meta["healthy"] is True  # new saves always stamp
    meta.pop("healthy")
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    status = integrity.validate_plain(*integrity.parse_base(base))
    assert status.committed and status.healthy is None
    assert saver.latest() == base          # unknown stays resumable
    _, step = saver.restore(runner)
    assert step == int(base.rsplit("ckpt-", 1)[1])
    autodist_tpu.reset()


def test_sharded_saver_stamps_and_skips_unhealthy(tmp_path):
    """The sharded format carries the same stamp and the same automatic
    skip (the scale path must not be the unprotected one)."""
    from autodist_tpu.checkpoint import integrity
    from autodist_tpu.checkpoint.sharded import ShardedSaver
    params, loss_fn, batch = _problem()
    runner = _build(lambda: S.PartitionedAR(), params, loss_fn, batch)
    saver = ShardedSaver(directory=str(tmp_path))
    _train(runner, batch, 2)
    good = saver.save(runner)
    assert good is not None
    status = integrity.validate_sharded(*integrity.parse_base(good))
    assert status.healthy is True
    _train(runner, batch, 2)
    bad = saver.save(runner)
    # forge an unhealthy stamp on the newest step (a quarantine-off save
    # under a bad verdict would write exactly this)
    meta_path = bad + ".shard-meta.json"
    with open(meta_path) as f:
        meta = json.load(f)
    meta["healthy"] = False
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    status = integrity.validate_sharded(*integrity.parse_base(bad))
    assert status.committed and status.healthy is False
    assert saver.latest() == good
    _, step = saver.restore(runner)
    assert step == int(good.rsplit("ckpt-", 1)[1])
    assert tel.counters()["ckpt.unhealthy_skipped"] >= 2
    autodist_tpu.reset()


def test_cli_displays_health_stamp(tmp_path, capsys):
    """``checkpoint ls`` shows the stamp column: yes / NO / ? (and fsck
    counts unhealthy steps)."""
    from autodist_tpu.checkpoint import cli
    from autodist_tpu.checkpoint.saver import Saver
    params, loss_fn, batch = _problem()
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch)
    saver = Saver(directory=str(tmp_path))
    _train(runner, batch, 1)
    base1 = saver.save(runner)
    _train(runner, batch, 1)
    base2 = saver.save(runner)
    # base1 -> pre-stamp (unknown), base2 -> unhealthy
    for base, mutate in ((base1, lambda m: m.pop("healthy")),
                         (base2, lambda m: m.update(healthy=False))):
        with open(base + ".meta.json") as f:
            meta = json.load(f)
        mutate(meta)
        with open(base + ".meta.json", "w") as f:
            json.dump(meta, f)
    assert cli.main(["--dir", str(tmp_path), "ls"]) == 0
    out = capsys.readouterr().out
    assert "HEALTHY" in out
    lines = {int(ln.split()[0]): ln for ln in out.splitlines()
             if ln.strip() and ln.split()[0].isdigit()}
    assert " ? " in lines[int(base1.rsplit("ckpt-", 1)[1])]
    assert " NO " in lines[int(base2.rsplit("ckpt-", 1)[1])]
    assert cli.main(["--dir", str(tmp_path), "fsck"]) == 0
    assert "1 stamped unhealthy" in capsys.readouterr().out
    # json surface carries it too
    assert cli.main(["--dir", str(tmp_path), "ls", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert {s["step"]: s["healthy"] for s in payload} == {
        int(base1.rsplit("ckpt-", 1)[1]): None,
        int(base2.rsplit("ckpt-", 1)[1]): False}
    autodist_tpu.reset()


# -------------------------------------------------- policy engine units


def test_policy_env_resolution(monkeypatch):
    monkeypatch.delenv("ADT_SENTINEL", raising=False)
    assert resolve_policy(None) is None
    assert resolve_policy(False) is None
    assert isinstance(resolve_policy(True), SentinelPolicy)
    monkeypatch.setenv("ADT_SENTINEL", "1")
    assert isinstance(resolve_policy(None), SentinelPolicy)
    monkeypatch.setenv("ADT_SENTINEL",
                       '{"max_skips_per_window": 7, "spike_zscore": 4.5}')
    p = resolve_policy(None)
    assert p.max_skips_per_window == 7 and p.spike_zscore == 4.5
    monkeypatch.setenv("ADT_SENTINEL", "0")
    assert resolve_policy(None) is None
    with pytest.raises(ValueError, match="window_steps"):
        SentinelPolicy(window_steps=0)
    with pytest.raises(TypeError):
        resolve_policy("yes")


def test_grad_fault_plan_rejects_unknown_fields():
    """The grad grammar is step-keyed: wire/ckpt knobs (nth/prob/...)
    must be rejected loudly, not silently dropped — a plan that tests
    something other than what it declares is worse than an error."""
    from autodist_tpu.runtime.faultinject import GradFaultPlan
    with pytest.raises(ValueError, match="unknown gradient fault field"):
        GradFaultPlan({"faults": [{"var": "w", "mode": "nan", "prob": 0.5}]})
    with pytest.raises(ValueError, match="unknown fault mode|unknown "
                                         "gradient fault"):
        GradFaultPlan({"faults": [{"var": "w", "mode": "explode"}]})
    # a top-level seed is tolerated for grammar-family symmetry only
    assert GradFaultPlan({"seed": 7, "faults": []}).rules == []


def test_lr_scale_resyncs_on_restore(monkeypatch, tmp_path):
    """The LR scale lives in three places (in-graph sync_state, the PS
    store, the Sentinel's ladder accounting); a restore replaces only
    the first — notify_state_restored must re-sync the other two, or an
    auto-resume after an escalation trains PS and device vars at
    different effective rates."""
    from autodist_tpu.checkpoint.saver import Saver
    params, loss_fn, batch = _problem()
    runner = _build(lambda: S.PS(), params, loss_fn, batch, sentinel=True)
    saver = Saver(directory=str(tmp_path))
    _train(runner, batch, 2)
    saver.save(runner)                     # checkpoint carries scale 1.0
    runner.sentinel._halve_lr()            # escalate: every copy -> 0.5
    assert runner.distributed_step.ps_store.update_scale == 0.5
    assert runner.sentinel.lr_scale == 0.5
    saver.restore(runner)                  # restored state says 1.0
    assert runner.distributed_step.ps_store.update_scale == 1.0
    assert runner.sentinel.lr_scale == 1.0
    autodist_tpu.reset()


def test_ewma_spike_detection_pends_rollback():
    """The loss-spike path the finiteness guards cannot see: a sustained
    EWMA z-score breach pends a rollback after ``spike_patience``
    consecutive spiking steps; a single outlier does not."""
    policy = SentinelPolicy(spike_zscore=4.0, spike_patience=3,
                            min_history=5, ewma_alpha=0.2)
    sen = Sentinel(policy, runner=None)
    for i in range(20):
        sen.observe({"loss": 1.0 + 0.01 * np.sin(i),
                     "sentinel": {"ok": 1, "grad_norm": 1.0,
                                  "bad_grads": 0, "bad_params": 0}})
    assert sen._pending_rollback is None
    spike = {"loss": 50.0, "sentinel": {"ok": 1, "grad_norm": 1.0,
                                        "bad_grads": 0, "bad_params": 0}}
    sen.observe(spike)
    assert sen._pending_rollback is None  # one outlier is not sustained
    sen.observe(spike)
    assert sen._pending_rollback is None
    sen.observe(spike)
    assert sen._pending_rollback is not None
    assert "loss spike" in sen._pending_rollback
    assert sen.quarantined  # saves vetoed while the spike is live


def test_unguarded_nonfinite_loss_pends_rollback():
    """step_fn-mode degradation: with no in-graph guards a nonfinite
    loss cannot be skipped, so it goes straight to the rollback path."""
    sen = Sentinel(SentinelPolicy(), runner=None)
    sen.observe({"loss": 1.0})
    assert sen._pending_rollback is None
    sen.observe({"loss": float("nan")})
    assert sen._pending_rollback is not None


def test_verify_sentinel_diagnostics():
    from autodist_tpu.analysis import rules
    policy = SentinelPolicy(window_steps=2)
    # guards compiled, small windows: clean
    assert rules.verify_sentinel(
        policy, {"sentinel_guards": True, "staleness": 0}) == []
    # no guards -> ADT420
    codes = [d.code for d in rules.verify_sentinel(
        policy, {"sentinel_guards": False})]
    assert codes == ["ADT420"]
    # stale window beyond the skip window -> ADT421
    codes = [d.code for d in rules.verify_sentinel(
        policy, {"sentinel_guards": True, "staleness": 5})]
    assert codes == ["ADT421"]
    assert rules.verify_sentinel(None, {}) == []


def test_step_fn_mode_gets_adt420_runner_diag():
    """build_step + sentinel: the opaque program carries no guards — the
    Runner logs ADT420 and the sentinel degrades to loss monitoring."""
    params, _, batch = _problem()

    def step_fn(state, b):
        loss = jnp.mean((b["x"] @ state["w"] + state["b"] - b["y"]) ** 2)
        return state, {"loss": loss}

    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build_step(step_fn, params, batch, sentinel=True)
    assert [d.code for d in runner._sentinel_diags] == ["ADT420"]
    runner.init(params)
    m = runner.run(batch)
    assert "sentinel" not in m  # no in-graph verdict on the opaque path
    assert runner.step_stats()["sentinel"]["skips"] == 0
    autodist_tpu.reset()


# ------------------------------------------ heartbeat compile grace


class _FakeCoordClient:
    def __init__(self):
        self.calls = []
        self.kv = {}

    def heartbeat(self, worker):
        self.calls.append(("heartbeat", worker))

    def put(self, key, value):
        self.calls.append(("put", key, value))
        self.kv[key] = value

    def get(self, key):
        return self.kv.get(key)


def test_pre_compile_heartbeat_and_grace_mark():
    """The heartbeat false-death fix: a beat plus a one-shot 'compiling'
    mark land BEFORE the first dispatch (which carries the compile), and
    the mark is cleared the moment the dispatch returns."""
    params, loss_fn, batch = _problem()
    runner = _build(lambda: S.AllReduce(), params, loss_fn, batch)
    fake = _FakeCoordClient()
    runner._hb_enabled = True
    runner._async_hb = fake
    runner.run(batch)
    kinds = [c[0] for c in fake.calls]
    assert kinds[:2] == ["heartbeat", "put"]  # beat + mark pre-dispatch
    assert fake.calls[1][1] == "compiling/chief"
    assert float(fake.calls[1][2]) > 0
    # one-shot: cleared after the first dispatch (epoch-zero mark — the
    # line protocol needs a non-empty value), never re-marked
    assert fake.calls[-1] == ("put", "compiling/chief", "0")
    n_calls = len(fake.calls)
    runner.run(batch)
    assert [c for c in fake.calls[n_calls:] if c[0] == "put"] == []
    runner._hb_enabled = False
    runner._async_hb = None
    autodist_tpu.reset()


def test_watchdog_compile_grace_window(monkeypatch):
    """Coordinator side: a fresh mark shields the worker from the
    heartbeat reaper; an expired or cleared mark does not."""
    import time as time_mod
    from autodist_tpu.runtime.coordinator import Coordinator
    coord = Coordinator.__new__(Coordinator)
    coord._heartbeat_timeout = 10.0
    client = _FakeCoordClient()
    assert not coord._in_compile_grace(client, "w0")      # no mark
    client.kv["compiling/w0"] = repr(time_mod.time())
    assert coord._in_compile_grace(client, "w0")          # fresh mark
    client.kv["compiling/w0"] = repr(time_mod.time() - 10000.0)
    assert not coord._in_compile_grace(client, "w0")      # expired
    client.kv["compiling/w0"] = "0"                       # cleared
    assert not coord._in_compile_grace(client, "w0")
    client.kv["compiling/w0"] = ""                        # never marked
    assert not coord._in_compile_grace(client, "w0")
    client.kv["compiling/w0"] = "garbage"
    assert not coord._in_compile_grace(client, "w0")


# ------------------------------------------------- nightly slow matrix


@pytest.mark.slow
@pytest.mark.chaos
def test_slow_partitioned_ps_fused_guarded_rollback(monkeypatch,
                                                    tmp_path):
    """Nightly matrix leg: partitioned host-PS + fused k=2 under guards
    with a sustained bit-flip window — skip accounting at readback
    boundaries, rollback to a healthy stamp, completion."""
    from autodist_tpu.checkpoint.saver import Saver
    params, loss_fn, batch = _problem()
    _set_plan(monkeypatch, [{"var": "w", "mode": "bitflip", "step": 6,
                             "until": 9, "bit": 30}])
    policy = SentinelPolicy(max_skips_per_window=2, window_steps=50,
                            grad_norm_limit=100.0)
    runner = _build(lambda: S.UnevenPartitionedPS(), params, loss_fn,
                    batch, sentinel=policy)
    saver = Saver(directory=str(tmp_path), max_to_keep=10)
    import itertools
    history = runner.fit(itertools.repeat(batch), steps=20, save_every=2,
                         saver=saver, fuse_steps=2)
    assert len(history) == 20
    stats = runner.step_stats()["sentinel"]
    assert stats["rollbacks"] >= 1
    assert np.isfinite(float(history[-1]["loss"]))
    autodist_tpu.reset()
