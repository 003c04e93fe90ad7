"""Elastic async-PS worker recovery (beyond the reference's fail-fast).

The reference's supervision is fail-fast only (``coordinator.py:98-110``;
SURVEY §5 "no elasticity"). Async host-PS makes per-worker restart SOUND:
processes couple only through the parameter service (no collective
lockstep, no jax.distributed process pinning), and a relaunched worker's
first pull fetches the owner's CURRENT published values — so with
``ADT_ELASTIC=<budget>`` the chief relaunches a dead worker instead of
aborting. Sync strategies (and PS groups owned by the dead worker) stay
fail-fast: the peers are wedged mid-collective / the authoritative state
died with the owner.

The e2e test runs the REAL chief-launched flow over the local transport:
the launched worker kills itself mid-run (first incarnation only), the
chief relaunches it, and the restarted worker trains to completion.
"""
import json
import os
import socket
import subprocess
import sys

import pytest

from _capabilities import needs_mp_collectives

# async-elastic recovery couples processes only through the coordination
# service (per-process local meshes — no cross-process collectives), so
# most tests here run anywhere; only the SYNC-elastic flows join a real
# two-process jax.distributed job and carry @needs_mp_collectives()

HERE = os.path.dirname(os.path.abspath(__file__))

USER_SCRIPT = """
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import optax
import autodist_tpu as adt
from autodist_tpu import strategy

spec, outdir = sys.argv[1], sys.argv[2]
mode = sys.argv[3] if len(sys.argv) > 3 else "crash"
ad = adt.AutoDist(resource_spec_file=spec,
                  strategy_builder=strategy.PS(sync=False))
import jax.numpy as jnp
rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)}

def loss_fn(p, batch):
    return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

batch = {"x": rng.randn(8, 8).astype(np.float32),
         "y": rng.randn(8, 4).astype(np.float32)}
step = ad.function(loss_fn, optimizer=optax.sgd(0.05), params=params)
is_worker = bool(os.environ.get("ADT_WORKER"))
marker = os.path.join(outdir, "crashed_once")

if is_worker:
    restarted = os.path.exists(marker)
    losses = []
    for i in range(12):
        losses.append(float(step(batch)["loss"]))
        if i == 2 and not restarted:
            with open(marker, "w") as f:
                f.write("x")
            if mode == "crash":
                os._exit(3)  # first incarnation dies mid-run
            time.sleep(3600)  # deadlock: alive but silent — the chief's
            # watchdog must kill us so the process watcher relaunches
    with open(os.path.join(outdir, "out_worker.json"), "w") as f:
        json.dump({"losses": losses, "restarted": restarted}, f)
    print("WORKER_DONE", restarted, flush=True)
else:
    # the chief keeps stepping (async: no barrier with the worker) and
    # exits once the (restarted) worker reports in
    worker_out = os.path.join(outdir, "out_worker.json")
    losses = []
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and not os.path.exists(worker_out):
        losses.append(float(step(batch)["loss"]))
        time.sleep(0.05)
    applied = ad.runner.distributed_step.ps_store.applied_total()
    with open(os.path.join(outdir, "out_chief.json"), "w") as f:
        json.dump({"losses": losses, "applied": applied,
                   "worker_done": os.path.exists(worker_out)}, f)
    print("CHIEF_DONE", flush=True)
"""

SPEC_YAML = """
nodes:
  - address: 127.0.0.1
    chief: true
    cpus: [0, 1]
  - address: localhost
    cpus: [0, 1]
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_elastic(tmp_path, mode, extra_env=None):
    script = tmp_path / "user_script.py"
    script.write_text(USER_SCRIPT)
    spec = tmp_path / "spec.yml"
    spec.write_text(SPEC_YAML)
    env = dict(os.environ)
    for k in ("JAX_PLATFORMS", "ADT_DEBUG_REMOTE", "ADT_WORKER"):
        env.pop(k, None)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "ADT_COORDINATOR_ADDR": "127.0.0.1:%d" % _free_port(),
        "ADT_COORDSVC_PORT": str(_free_port()),
        "ADT_ELASTIC": "1",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE)] +
            ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH")
             else [])),
    })
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, str(script), str(spec), str(tmp_path), mode],
        env=env, capture_output=True, text=True, timeout=240)


def _assert_recovered(tmp_path, proc):
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "relaunching worker" in proc.stderr, proc.stderr[-3000:]
    worker = json.loads((tmp_path / "out_worker.json").read_text())
    chief = json.loads((tmp_path / "out_chief.json").read_text())
    # the SECOND incarnation wrote the output (first died at step 2)
    assert worker["restarted"] is True
    assert (tmp_path / "crashed_once").exists()
    assert chief["worker_done"] is True
    # both trajectories converge; the chief's owner loop applied blobs
    # from its own steps plus both worker incarnations
    assert worker["losses"][-1] < worker["losses"][0]
    assert chief["losses"][-1] < chief["losses"][0]
    assert chief["applied"] > len(chief["losses"])


def test_worker_crash_relaunches_and_recovers(tmp_path):
    _assert_recovered(tmp_path, _run_elastic(tmp_path, "crash"))


def test_worker_deadlock_detected_and_recovered(tmp_path):
    """The first incarnation HANGS (alive, silent) instead of dying: the
    chief's heartbeat watchdog must notice the silence, kill the wedged
    process, and let the process watcher relaunch it — the deadlock leg
    of elastic supervision (a crash alone never exercises the watchdog)."""
    proc = _run_elastic(tmp_path, "hang",
                        extra_env={"ADT_HEARTBEAT_TIMEOUT_S": "6"})
    assert "deadlock" in proc.stderr, proc.stderr[-3000:]
    _assert_recovered(tmp_path, proc)


def _coordinator_for(tmp_path, strategy):
    """A Coordinator over a 2-node loopback cluster with ``strategy``
    serialized under a preset id (no processes launched)."""
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.runtime.cluster import Cluster
    from autodist_tpu.runtime.coordinator import Coordinator
    spec = tmp_path / "spec.yml"
    spec.write_text(SPEC_YAML)
    strategy.id = "elastic-unit-%d" % os.getpid()
    strategy.serialize()
    cluster = Cluster(ResourceSpec(str(spec)))
    return Coordinator(strategy.id, cluster, max_restarts=1)


def _ps_strategy(sync, dest="127.0.0.1:CPU:0"):
    from autodist_tpu.strategy.base import (PSSynchronizer, Strategy,
                                            VarConfig)
    return Strategy(node_config=[
        VarConfig(var_name="w", synchronizer=PSSynchronizer(
            reduction_destination=dest, sync=sync))])


def test_restart_soundness_gate(tmp_path, monkeypatch):
    """Sync strategies and dead-owner groups refuse restart; pure async
    with surviving owners allows it; no ADT_ELASTIC bring-up refuses
    everything (processes joined jax.distributed)."""
    no_elastic = _coordinator_for(tmp_path, _ps_strategy(sync=False))
    assert "ADT_ELASTIC" in no_elastic._restart_unsound_reason("localhost")

    monkeypatch.setenv("ADT_ELASTIC", "1")
    ok = _coordinator_for(tmp_path, _ps_strategy(sync=False))
    assert ok._restart_unsound_reason("localhost") is None

    sync = _coordinator_for(tmp_path, _ps_strategy(sync=True))
    assert "not async" in sync._restart_unsound_reason("localhost")

    owner = _coordinator_for(
        tmp_path, _ps_strategy(sync=False, dest="localhost:CPU:0"))
    assert "OWNS" in owner._restart_unsound_reason("localhost")
    # ...but losing a NON-owner is still recoverable in the same job
    assert owner._restart_unsound_reason("10.0.0.9") is None


def test_reap_pattern_matches_command_not_itself():
    """The remote reap pattern must match the launched command line
    (what bash exec leaves in /proc cmdline) — including commands with
    regex metacharacters — but never the pkill wrapper's own cmdline,
    which embeds the pattern text."""
    import re
    from autodist_tpu.runtime.coordinator import _reap_pattern
    for command in ("python -u /tmp/s.py a b",
                    "python -u /runs/exp+1/train.py --lr (0.1)"):
        pat = _reap_pattern(command)
        assert re.search(pat, command), (pat, command)
        wrapper = "bash -c pkill -f %s || true" % pat
        assert not re.search(pat, wrapper), (pat, wrapper)


def test_restart_budget_exhausts_to_fail_fast(tmp_path, monkeypatch):
    """_try_restart honors the budget: first death relaunches (dry-run
    remote_exec returns None), second falls through to fail-fast."""
    monkeypatch.setenv("ADT_DEBUG_REMOTE", "1")
    monkeypatch.setenv("ADT_ELASTIC", "1")
    coord = _coordinator_for(tmp_path, _ps_strategy(sync=False))
    coord._launch_cmds["localhost"] = ("python -u x.py", {})
    assert coord._try_restart("localhost", 3) is True
    assert coord._try_restart("localhost", 3) is False


# ----------------------------------------------------- sync-elastic (r4)

SYNC_USER_SCRIPT = """
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import optax
import autodist_tpu as adt
from autodist_tpu import strategy
from autodist_tpu.checkpoint.saver import Saver

spec, outdir = sys.argv[1], sys.argv[2]
ad = adt.AutoDist(resource_spec_file=spec,
                  strategy_builder=strategy.AllReduce())
import jax.numpy as jnp
rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)}

def loss_fn(p, batch):
    return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

batch = {"x": rng.randn(8, 8).astype(np.float32),
         "y": rng.randn(8, 4).astype(np.float32)}
runner = ad.build(loss_fn, optax.sgd(0.05), params, batch)
runner.init(params)  # ADT_AUTO_RESUME restores on the re-exec'd run
start = int(np.asarray(jax.device_get(runner.state.step)))
saver = Saver(directory=os.environ["ADT_CKPT_DIR"])
is_worker = bool(os.environ.get("ADT_WORKER"))
role = "worker" if is_worker else "chief"
marker = os.path.join(outdir, "crashed_once")
losses = {}
for i in range(start, 8):
    losses[i] = float(runner.run(batch)["loss"])
    saver.save(runner)  # every process: the gathers are collectives
    if is_worker and i == 2 and not os.path.exists(marker):
        with open(marker, "w") as f:
            f.write("x")
        # only the chief writes, and it may still be writing: die once
        # this step's checkpoint, the one the resumed job has to start
        # from, is committed (its meta file is written last)
        committed = os.path.join(os.environ["ADT_CKPT_DIR"],
                                 "ckpt-%d.meta.json" % (i + 1))
        deadline = time.time() + 60
        while not os.path.exists(committed) and time.time() < deadline:
            time.sleep(0.01)
        os._exit(3)  # first worker incarnation dies mid-lockstep
with open(os.path.join(outdir, "out_%s.json" % role), "w") as f:
    json.dump({"start": start, "losses": losses,
               "params": np.asarray(
                   runner.gather_params()["w"]).tolist()}, f)
print(role.upper() + "_DONE start=%d" % start, flush=True)
"""


@needs_mp_collectives()
def test_sync_elastic_whole_job_restart_resumes_from_checkpoint(tmp_path):
    """ADT_ELASTIC + ADT_ELASTIC_SYNC on a sync (AllReduce) job: a worker
    dies mid-lockstep, the chief reaps the mesh and re-execs itself, the
    resumed job restores the last checkpoint and finishes — final params
    bit-equal an uninterrupted single-process run of the same math."""
    script = tmp_path / "user_script.py"
    script.write_text(SYNC_USER_SCRIPT)
    spec = tmp_path / "spec.yml"
    spec.write_text(SPEC_YAML)
    ckpt = tmp_path / "ckpt"
    env = dict(os.environ)
    for k in ("JAX_PLATFORMS", "ADT_DEBUG_REMOTE", "ADT_WORKER"):
        env.pop(k, None)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "ADT_COORDINATOR_ADDR": "127.0.0.1:%d" % _free_port(),
        "ADT_COORDSVC_PORT": str(_free_port()),
        "ADT_ELASTIC": "1",
        "ADT_ELASTIC_SYNC": "1",
        "ADT_CKPT_DIR": str(ckpt),
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE)] +
            ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH")
             else [])),
    })
    proc = subprocess.run(
        [sys.executable, str(script), str(spec), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert "restarting the WHOLE job" in proc.stderr, proc.stderr[-4000:]
    assert "ADT_AUTO_RESUME: restored step" in proc.stderr, proc.stderr[-4000:]
    chief = json.loads((tmp_path / "out_chief.json").read_text())
    worker = json.loads((tmp_path / "out_worker.json").read_text())
    # the resumed incarnation started from the last committed checkpoint
    assert chief["start"] == 3, chief
    assert worker["start"] == 3, worker
    # steps 3..7 ran in the resumed incarnation; both processes agree
    assert sorted(map(int, chief["losses"])) == [3, 4, 5, 6, 7]
    for k in chief["losses"]:
        assert abs(chief["losses"][k] - worker["losses"][k]) < 1e-6

    # uninterrupted reference: same math, single process over 2 devices
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy as S
    adt.reset()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    batch = {"x": rng.randn(8, 8).astype(np.float32),
             "y": rng.randn(8, 4).astype(np.float32)}
    ad = adt.AutoDist(strategy_builder=S.AllReduce())
    step = ad.function(loss_fn, optimizer=optax.sgd(0.05), params=params)
    ref_losses = [float(step(batch)["loss"]) for _ in range(8)]
    ref_params = np.asarray(step.get_runner().gather_params()["w"])
    adt.reset()
    for i in range(3, 8):
        np.testing.assert_allclose(chief["losses"][str(i)], ref_losses[i],
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(chief["params"]), ref_params,
                               rtol=1e-6, atol=1e-7)


# ------------------------------------- reduced-world sync-elastic (r5)

REDUCED_WORLD_SCRIPT = """
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import optax

spec, outdir = sys.argv[1], sys.argv[2]
die_marker = os.path.join(outdir, "worker_dead_forever")
is_worker = bool(os.environ.get("ADT_WORKER"))
if is_worker and os.path.exists(die_marker):
    os._exit(3)  # the host is "gone": every relaunch dies at startup

import autodist_tpu as adt
from autodist_tpu import strategy
from autodist_tpu.checkpoint import ShardedSaver

ad = adt.AutoDist(resource_spec_file=spec,
                  strategy_builder=strategy.AllReduce())
import jax.numpy as jnp
rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)}

def loss_fn(p, batch):
    return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

batch = {"x": rng.randn(8, 8).astype(np.float32),
         "y": rng.randn(8, 4).astype(np.float32)}
runner = ad.build(loss_fn, optax.sgd(0.05), params, batch)
runner.init(params)  # ADT_AUTO_RESUME restores on re-exec'd runs
start = int(np.asarray(jax.device_get(runner.state.step)))
saver = ShardedSaver(directory=os.environ["ADT_CKPT_DIR"])
losses = {}
for i in range(start, 8):
    losses[i] = float(runner.run(batch)["loss"])
    saver.save(runner)
    if is_worker and i == 2:
        with open(die_marker, "w") as f:
            f.write("x")
        os._exit(3)  # first death, mid-lockstep
with open(os.path.join(outdir, "out_chief.json"), "w") as f:
    json.dump({"start": start, "losses": losses,
               "world": jax.device_count(),
               "params": np.asarray(
                   runner.gather_params()["w"]).tolist()}, f)
print("CHIEF_DONE start=%d world=%d" % (start, jax.device_count()),
      flush=True)
"""


# --------------------------------- in-run shrink/grow (epoch-fenced, r13)

INRUN_CHAOS_SCRIPT = """
import json, os, signal, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import optax
import autodist_tpu as adt
from autodist_tpu import strategy
from autodist_tpu.runtime import elastic
from autodist_tpu.telemetry import spans as tel

spec, outdir = sys.argv[1], sys.argv[2]
ad = adt.AutoDist(resource_spec_file=spec,
                  strategy_builder=strategy.AllReduce())
import jax.numpy as jnp
rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)}

def loss_fn(p, batch):
    return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

batch = {"x": rng.randn(8, 8).astype(np.float32),
         "y": rng.randn(8, 4).astype(np.float32)}
runner = ad.build(loss_fn, optax.sgd(0.05), params, batch)
runner.init(params)
start = int(np.asarray(jax.device_get(runner.state.step)))
is_worker = bool(os.environ.get("ADT_WORKER"))
role = "worker" if is_worker else "chief"
marker = os.path.join(outdir, "crashed_once")
TOTAL = 12
losses = {}
for i in range(start, TOTAL):
    losses[i] = float(runner.run(batch)["loss"])
    if i == 2 and is_worker and not os.path.exists(marker):
        with open(marker, "w") as f:
            f.write("x")
        time.sleep(0.1)  # let the chief clear its own step-2 boundary
        os.kill(os.getpid(), signal.SIGKILL)  # die mid-run, no cleanup
    if i == 2 and not is_worker:
        # stay OUT of the next cross-process collective while the death
        # is detected: the shrink epoch must land at a boundary (the
        # production pattern is a superstep interval >> detection time)
        time.sleep(3.0)
    time.sleep(0.25)  # superstep pacing so grow can land mid-run
out = {"start": start, "losses": losses, "world": jax.device_count(),
       "reconfigs": getattr(runner, "_reconfigs", 0),
       "epoch": elastic.current().epoch if elastic.current() else None,
       "spans": tel.get_recorder().durations_s("elastic.reconfigure"),
       "params": np.asarray(runner.gather_params()["w"]).tolist()}
with open(os.path.join(outdir, "out_%s_%d.json" % (role, start)), "w") as f:
    json.dump(out, f)
print(role.upper() + "_DONE start=%d world=%d" % (start, jax.device_count()),
      flush=True)
"""


@pytest.mark.slow
@pytest.mark.chaos
@needs_mp_collectives()
def test_inrun_shrink_to_survivors_then_grow_on_join(tmp_path):
    """The in-run elastic acceptance path: SIGKILL one of two sync workers
    mid-run → the chief publishes epoch 2 and the survivor re-forms a
    1-process mesh IN-RUN (no whole-job re-exec, no 'restarting the WHOLE
    job' in the logs); the relaunched worker announces itself, is admitted
    at epoch 3, adopts the broadcast state, and the job grows back — with
    the chief's loss trajectory bit-matching an uninterrupted reference
    (data-parallel math is world-size invariant on a fixed global batch)."""
    script = tmp_path / "user_script.py"
    script.write_text(INRUN_CHAOS_SCRIPT)
    spec = tmp_path / "spec.yml"
    spec.write_text(SPEC_YAML)
    env = dict(os.environ)
    for k in ("JAX_PLATFORMS", "ADT_DEBUG_REMOTE", "ADT_WORKER"):
        env.pop(k, None)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "ADT_COORDINATOR_ADDR": "127.0.0.1:%d" % _free_port(),
        "ADT_COORDSVC_PORT": str(_free_port()),
        "ADT_ELASTIC": "3",
        "ADT_ELASTIC_SYNC": "1",
        "ADT_ELASTIC_INRUN": "1",
        "ADT_ELASTIC_POLL_S": "0.05",
        "ADT_HEARTBEAT_TIMEOUT_S": "8",
        "ADT_CKPT_DIR": str(tmp_path / "ckpt"),
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE)] +
            ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH")
             else [])),
    })
    proc = subprocess.run(
        [sys.executable, str(script), str(spec), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-6000:]
    err = proc.stderr
    assert "published cluster epoch 2" in err, err[-6000:]
    assert "published cluster epoch 3" in err, err[-6000:]
    assert "restarting the WHOLE job" not in err, err[-6000:]
    chief = json.loads((tmp_path / "out_chief_0.json").read_text())
    # shrink + grow both happened in-run on the survivor
    assert chief["reconfigs"] == 2, chief
    assert chief["epoch"] == 3, chief
    assert chief["world"] == 4, chief  # grown back to 2 procs x 2 devices
    assert len(chief["spans"]) == 2  # downtime is span-derived
    # the revived worker adopted the broadcast state mid-run and finished
    worker_outs = [f for f in os.listdir(tmp_path)
                   if f.startswith("out_worker_")]
    assert worker_outs, os.listdir(tmp_path)
    worker = json.loads((tmp_path / worker_outs[0]).read_text())
    assert worker["start"] > 2, worker  # not a from-scratch restart

    # loss continuity: bit-match an uninterrupted single-process run
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy as S
    adt.reset()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    batch = {"x": rng.randn(8, 8).astype(np.float32),
             "y": rng.randn(8, 4).astype(np.float32)}
    ad = adt.AutoDist(strategy_builder=S.AllReduce())
    step = ad.function(loss_fn, optimizer=optax.sgd(0.05), params=params)
    ref = [float(step(batch)["loss"]) for _ in range(12)]
    adt.reset()
    for i_str, loss in chief["losses"].items():
        np.testing.assert_allclose(loss, ref[int(i_str)],
                                   rtol=1e-5, atol=1e-7)
    # every step the worker computed agrees with the chief's
    for i_str, loss in worker["losses"].items():
        np.testing.assert_allclose(loss, chief["losses"][i_str],
                                   rtol=1e-6, atol=1e-7)


@needs_mp_collectives()
def test_sync_elastic_reduced_world_after_permanent_loss(tmp_path):
    """VERDICT-r4 #1 (elastic half): a worker that dies on two consecutive
    incarnations is treated as PERMANENTLY lost — the chief excludes it,
    re-execs, and the job resumes at REDUCED world size (4 -> 2 devices)
    from its SHARDED checkpoints via the cross-topology restore, with loss
    continuity against an uninterrupted single-process run."""
    script = tmp_path / "user_script.py"
    script.write_text(REDUCED_WORLD_SCRIPT)
    spec = tmp_path / "spec.yml"
    spec.write_text(SPEC_YAML)
    ckpt = tmp_path / "ckpt"
    env = dict(os.environ)
    for k in ("JAX_PLATFORMS", "ADT_DEBUG_REMOTE", "ADT_WORKER",
              "ADT_ELASTIC_EXCLUDE"):
        env.pop(k, None)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "ADT_COORDINATOR_ADDR": "127.0.0.1:%d" % _free_port(),
        "ADT_COORDSVC_PORT": str(_free_port()),
        "ADT_ELASTIC": "3",
        "ADT_ELASTIC_SYNC": "1",
        "ADT_CKPT_DIR": str(ckpt),
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE)] +
            ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH")
             else [])),
    })
    proc = subprocess.run(
        [sys.executable, str(script), str(spec), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-6000:]
    assert "PERMANENTLY lost" in proc.stderr, proc.stderr[-6000:]
    assert "restore across topologies" in proc.stderr, proc.stderr[-6000:]
    chief = json.loads((tmp_path / "out_chief.json").read_text())
    # the surviving incarnation ran chief-only over its 2 local devices
    assert chief["world"] == 2, chief
    assert chief["start"] == 3, chief
    assert sorted(map(int, chief["losses"])) == [3, 4, 5, 6, 7]

    # uninterrupted reference: same math, single process
    import jax
    import jax.numpy as jnp
    import numpy as np_
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy as S
    adt.reset()
    rng = np_.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    batch = {"x": rng.randn(8, 8).astype(np_.float32),
             "y": rng.randn(8, 4).astype(np_.float32)}
    ad = adt.AutoDist(strategy_builder=S.AllReduce())
    step = ad.function(loss_fn, optimizer=optax.sgd(0.05), params=params)
    ref_losses = [float(step(batch)["loss"]) for _ in range(8)]
    ref_params = np_.asarray(step.get_runner().gather_params()["w"])
    adt.reset()
    for i in range(3, 8):
        np_.testing.assert_allclose(chief["losses"][str(i)], ref_losses[i],
                                    rtol=1e-5, atol=1e-7)
    np_.testing.assert_allclose(np_.asarray(chief["params"]), ref_params,
                                rtol=1e-5, atol=1e-7)
