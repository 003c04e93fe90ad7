#!/usr/bin/env python3
"""Compile each cell's device programs at their real shapes for a DESCRIBED
v5e:2x2 (no chip attached) and record ``memory_analysis()``.

    python3 benchmark/tools/aot_compile.py [--out benchmark/records/aot_memory.json] [cell ...]

Costs no chip time and proves nothing about speed: it finds a cell that
the TPU compiler refuses or that does not fit 16 GB before a chip run
does. The program builds its mesh from ``jax.devices()`` and places its
own state, so this script (and only this script) hands it the described
devices by patching ``parallel.mesh.ordered_devices`` and lowers on
shapes; nothing is placed and nothing runs.
"""
import argparse
import importlib
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0] = ROOT


def mem(compiled):
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["live_bytes_estimate"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        - out["alias_size_in_bytes"] + out["temp_size_in_bytes"])
    return out


def compile_cell(name, topo_devices):
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import autodist_tpu as adt
    from autodist_tpu import strategy
    from autodist_tpu.parallel import mesh as mesh_lib
    from autodist_tpu.train_state import TrainState
    from benchmark import run as bench_run

    _, cell, config, traffic = bench_run.load_cell(name)
    family = importlib.import_module("benchmark.families." + config["family"])
    chips = cell["chips"]
    devices = list(topo_devices)[:chips]
    mesh_lib.ordered_devices = lambda n=None, backend=None: devices
    adt.reset()

    train = traffic["kind"] == "train_fit"
    if train:
        loss_fn, params, example = family.train_setup(
            config, traffic, traffic["batch_per_chip"] * chips, 0)
    else:
        loss_fn, params, example = family.decode_train_stub(config, 0, chips)
    spec = adt.resource_spec.ResourceSpec.from_dict({
        "nodes": [{"address": "127.0.0.1", "chief": True,
                   "cpus": list(range(chips))}]})
    ad = adt.AutoDist(strategy_builder=getattr(strategy, cell["strategy"])(),
                      resource_spec=spec)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, example)
    dstep = runner.distributed_step
    mesh = dstep.mesh
    assert set(mesh.devices.flat) == set(devices), mesh

    def sds(tree, pspec):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), a.dtype, sharding=NamedSharding(mesh, pspec)),
            tree)

    opt = jax.eval_shape(optax.adam(1e-3).init, params)
    state = TrainState(step=sds(np.zeros((), np.int32), P()),
                       params=sds(params, P()), opt_state=sds(opt, P()),
                       sync_state=sds(dstep._sync_state_init(), P(dstep.all_axes)))
    out = {}

    def timed(label, lowered):
        t0 = time.perf_counter()
        compiled = lowered.compile()
        out[label] = dict(mem(compiled),
                          compile_s=round(time.perf_counter() - t0, 1))
        text = compiled.as_text()
        out[label]["collectives_in_hlo"] = sorted(
            {op for op in ("all-reduce", "reduce-scatter", "all-gather",
                           "collective-permute", "all-to-all")
             if op in text})
        print(name, label, json.dumps(out[label]), flush=True)

    if train:
        batch = sds(example, P(dstep.batch_axes))
        timed("train_step", dstep._step_fn.lower(state, {}, batch))
    else:
        from autodist_tpu.serving.decode import DecodeConfig
        _, setup = family.decode_setup(config)
        dcfg = DecodeConfig(slots=traffic["slots"],
                            prefill_len=traffic["prefill_len"],
                            max_new_tokens=traffic["max_new_tokens"])
        dstate = sds(setup.init_dstate(dcfg.slots), P(dstep.batch_axes))
        prog = dstep.decode_program(setup.decode_fn,
                                    setup.init_dstate(dcfg.slots))
        timed("decode_step", prog.fn.lower(state, {}, dstate))
        for bucket in sorted({1, dcfg.slots}):
            feed = {"tokens": np.zeros((bucket, dcfg.prefill_len), np.int32),
                    "length": np.zeros((bucket,), np.int32)}
            fwd = dstep.predict_program(setup.prefill_fn, example_batch=feed)
            timed("prefill_bucket_%d" % bucket, fwd.fn.lower(
                state, {}, sds(feed, P(dstep.batch_axes))))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--out")
    args = ap.parse_args()
    import jax  # noqa: F401
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = args.cells or [w["name"] for w in bench["workloads"]]
    report = {"topology": "v5e:2x2 (described, not attached)",
              "device_kind": topo.devices[0].device_kind, "cells": {}}
    for name in cells:
        report["cells"][name] = compile_cell(name, topo.devices)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
