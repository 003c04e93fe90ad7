#!/usr/bin/env python3
"""``aot_compile.py`` with the program's shape rules steered as a v5e would
steer them: the cells compile to the programs the CHIP would run.

    python3 benchmark/tools/aot_compile_as_on_the_chip.py [--out FILE] [cell ...]

``aot_compile.py`` builds each cell where JAX's default backend is the
CPU, so the rules of ``models/lm.py:make_train_setup`` that ask for the
backend (``attention="auto"``, the per-block recompute of
``auto_remat_blocks``) decide as for a CPU: a cell whose program on the
chip holds pallas kernels or recomputed blocks compiles to ANOTHER program
there (``kimi_linear_train_1chip``: XLA's 8.6 GB of attention scores, no
recompute). Here the backend probe answers "tpu", the chip's memory is the
chip table's v5e row, and every pallas kernel lowers as a Mosaic call
(``ops/pallas_mode.py:compiling_for_tpu``); nothing is placed and nothing
runs. Costs no chip time and proves nothing about speed.
"""
import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.tools import aot_compile  # noqa: E402  (sets JAX_PLATFORMS=cpu)


@contextlib.contextmanager
def as_on_the_chip():
    """The program's shape rules decide as on a v5e, and pallas kernels
    lower as Mosaic calls, for what is traced inside; yields the described
    v5e:2x2's devices. ``compile_cell`` re-points the mesh's device list
    for good: that, too, is put back."""
    import jax
    from jax.experimental import topologies
    from autodist_tpu.models import lm
    from autodist_tpu.ops import pallas_mode
    from autodist_tpu.parallel import mesh as mesh_lib
    from autodist_tpu.resource_spec import CHIP_TABLE
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    before = (jax.default_backend, lm._chip_hbm_bytes,
              mesh_lib.ordered_devices)
    jax.default_backend = lambda: "tpu"
    lm._chip_hbm_bytes = lambda: CHIP_TABLE["v5e"].hbm_bytes
    try:
        with pallas_mode.compiling_for_tpu():
            yield topo.devices
    finally:
        (jax.default_backend, lm._chip_hbm_bytes,
         mesh_lib.ordered_devices) = before


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--out")
    args = ap.parse_args()
    report = {"topology": "v5e:2x2 (described, not attached), shape rules "
              "steered as on the chip", "cells": {}}
    with as_on_the_chip() as devices:
        for name in args.cells:
            report["cells"][name] = aot_compile.compile_cell(name, devices)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
