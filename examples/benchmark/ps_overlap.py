"""Bench: host-PS transfer/compute overlap (PSPipeline) on a
transfer-bound config.

The serial PS step pays compute + pull(H2D) + push(D2H + host apply) per
step; with the pipeline (ADT_PS_OVERLAP=1, default) the transfers ride a
background worker. Sync PS keeps exact ordering (the win is bounded by
dispatch/host overlap); PS(staleness=1) allows the stale-by-one prefetch
and should approach step ~= max(compute, transfer).

Config: a deliberately PCIe-heavy MLP — most parameters host-resident
(no-proxy PS), small batch so compute is modest and the wire dominates.
Prints one JSON line per mode: {"mode", "step_ms", "pull_mb", "push_mb"}.

Run from the repo root:  python examples/benchmark/ps_overlap.py
"""
import json
import os
import sys
import time

import numpy as np

# repo-root import when run as a script
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def timed_run(overlap: int, staleness: int, steps: int = 8):
    os.environ["ADT_PS_OVERLAP"] = str(overlap)
    import jax
    import jax.numpy as jnp
    import optax
    import autodist_tpu as adt
    from autodist_tpu import strategy

    adt.reset()
    rng = np.random.RandomState(0)
    d = 2048
    params = {
        "w1": jnp.asarray(rng.randn(d, d) * 0.02, jnp.float32),
        "w2": jnp.asarray(rng.randn(d, d) * 0.02, jnp.float32),
        "w3": jnp.asarray(rng.randn(d, d) * 0.02, jnp.float32),
        "w4": jnp.asarray(rng.randn(d, 8) * 0.02, jnp.float32),
    }

    def loss_fn(p, batch):
        h = jnp.tanh(batch["x"] @ p["w1"])
        h = jnp.tanh(h @ p["w2"])
        h = jnp.tanh(h @ p["w3"])
        return jnp.mean((h @ p["w4"] - batch["y"]) ** 2)

    batch = {"x": rng.randn(16, d).astype(np.float32),
             "y": rng.randn(16, 8).astype(np.float32)}
    runner = adt.AutoDist(
        strategy_builder=strategy.PS(staleness=staleness)).build(
        loss_fn, optax.sgd(0.01), params, batch)
    runner.init(params)
    # warmup (compile + first transfers)
    for _ in range(3):
        runner.run(batch)
    runner.distributed_step.flush_ps()
    store = runner.distributed_step.ps_store
    b0 = dict(store.stats)
    t0 = time.perf_counter()
    last = None
    for _ in range(steps):
        last = runner.run(batch)
    # value readback sync + flush the pipeline so the window includes the
    # final push (fair vs serial)
    float(last["loss"])
    runner.distributed_step.flush_ps()
    dt = time.perf_counter() - t0
    out = {
        "mode": "overlap" if overlap else "serial",
        "staleness": staleness,
        "step_ms": round(1e3 * dt / steps, 2),
        "pull_mb": round((store.stats["bytes_pulled"] - b0["bytes_pulled"])
                         / steps / 1e6, 1),
        "push_mb": round((store.stats["bytes_pushed"] - b0["bytes_pushed"])
                         / steps / 1e6, 1),
    }
    adt.reset()
    return out


def apply_scaling(n_shards: int = 4, rows: int = 16384, cols: int = 2048,
                  iters: int = 12, threads=(1, 2, 4)):
    """Store-level microbench of the host optimizer apply: one DLRM-ish
    partitioned table, adam, timed through PSStore.apply_local with the
    thread pool at 1 (baseline) vs N workers. Shards are independent, so
    the update parallelizes across host cores (ADT_PS_APPLY_THREADS)."""
    import jax.numpy as jnp
    import optax
    from autodist_tpu.parallel.ps import PSStore, PSVarPlan

    rng = np.random.RandomState(0)
    full = rng.randn(rows, cols).astype(np.float32) * 0.02
    grad = rng.randn(rows, cols).astype(np.float32) * 0.001
    sizes = tuple([rows // n_shards] * n_shards)
    plan = PSVarPlan(var_name="emb", destinations=("127.0.0.1",) * n_shards,
                     shard_sizes=sizes)

    class _Info:
        shape = (rows, cols)
    out = {"bench": "apply_scaling", "n_shards": n_shards,
           "mb": round(full.nbytes / 1e6, 1)}
    base_ms = None
    for n in threads:
        os.environ["ADT_PS_APPLY_THREADS"] = str(n)
        store = PSStore({"emb": plan}, {"emb": _Info()}, optax.adam(1e-3))
        store.init_params({"emb": jnp.asarray(full)})
        store.push({"emb": grad})  # warmup: trace + compile the groups
        t0 = time.perf_counter()
        for _ in range(iters):
            store.push({"emb": grad})
        ms = 1e3 * (time.perf_counter() - t0) / iters
        store.close()
        if base_ms is None:
            base_ms = ms
        out["threads_%d_ms" % n] = round(ms, 2)
        out["threads_%d_speedup" % n] = round(base_ms / ms, 2)
    os.environ.pop("ADT_PS_APPLY_THREADS", None)
    return out


def main():
    results = []
    for staleness in (0, 1):
        for overlap in (0, 1):
            r = timed_run(overlap, staleness)
            results.append(r)
            print(json.dumps(r), flush=True)
    by = {(r["mode"], r["staleness"]): r["step_ms"] for r in results}
    summary = {
        "sync_speedup": round(by[("serial", 0)] / by[("overlap", 0)], 3),
        "stale1_speedup": round(by[("serial", 1)] / by[("overlap", 1)], 3),
    }
    print(json.dumps({"summary": summary}), flush=True)
    print(json.dumps(apply_scaling()), flush=True)


if __name__ == "__main__":
    main()
