"""Calibrate AutoStrategy's cost model from measured runs, then reuse it.

The analytic cost model ranks candidate strategies from closed-form
constants; real hardware disagrees (achieved MXU efficiency, host links).
This example measures a few strategies for real, fits the model's term
scales to those measurements (``Simulator.calibrate`` — the reference's
AutoSync measured-runs idea, ``autodist/simulator/dataset/README.md``,
realized over our analytic model), persists them, and lets
``AutoStrategy(calibration=...)`` pick with corrected constants.

Run on anything (CPU works):
    python examples/autostrategy_calibrate.py
"""
if __package__ in (None, ""):  # direct invocation: repo root on sys.path
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import autodist_tpu as adt
from autodist_tpu import strategy as S
from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.simulator.simulator import Simulator

from autodist_tpu import const

CAL_PATH = os.path.join(const.DEFAULT_WORKING_DIR, "calibration.json")


def build_case(seed=0):
    rng = np.random.RandomState(seed)
    params = {"emb": jnp.asarray(rng.randn(8192, 64), jnp.float32),
              "w": jnp.asarray(rng.randn(64, 8), jnp.float32)}

    def loss_fn(p, b):
        e = jnp.take(p["emb"], b["ids"], axis=0)
        return jnp.mean((e @ p["w"] - b["y"]) ** 2)

    batch = {"ids": rng.randint(0, 8192, (64,)).astype(np.int32),
             "y": rng.randn(64, 8).astype(np.float32)}
    return loss_fn, params, batch


def measure(builder, loss_fn, params, batch, steps=10):
    """Median steady step time through the full framework stack (the
    Runner's own step_stats supplies the steady median and goodput)."""
    adt.reset()
    ad = adt.AutoDist(strategy_builder=builder)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    for _ in range(3 + steps):
        runner.run(batch)
    stats = runner.step_stats()
    strat = runner.distributed_step.strategy
    print("  %-18s steady=%.2fms goodput=%.2f"
          % (type(builder).__name__, stats["steady_median_s"] * 1e3,
             stats["goodput"]))
    return strat, stats["steady_median_s"]


def main():
    loss_fn, params, batch = build_case()
    print("measuring candidate strategies for real:")
    measured = [measure(b, loss_fn, params, batch)
                for b in (S.AllReduce(), S.PSLoadBalancing(), S.Parallax())]
    adt.reset()

    item = ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare()
    sim = Simulator(item, ResourceSpec.from_local())
    cal = sim.calibrate(measured, save_path=CAL_PATH)
    print("fitted scales:", cal.to_dict())
    print("saved ->", CAL_PATH)

    # future sessions on the same hardware reuse the file
    builder = S.AutoStrategy(calibration=CAL_PATH)
    ad = adt.AutoDist(strategy_builder=builder)
    step = ad.function(loss_fn, optimizer=optax.adam(1e-3), params=params)
    t0 = time.perf_counter()
    losses = [float(step(batch)["loss"]) for _ in range(5)]
    print("AutoStrategy picked %s; 5 steps in %.2fs, loss %.4f -> %.4f"
          % (builder.last_ranking[0].label, time.perf_counter() - t0,
             losses[0], losses[-1]))
    adt.reset()


if __name__ == "__main__":
    main()
