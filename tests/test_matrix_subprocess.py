"""Subprocess-isolated combos match the in-process (reset-isolated) runs.

The reference forks a fresh process per strategy x case combo
(``tests/integration/test_all.py:53-69``); our matrix runs in-process on
``reset()``. This module proves the two are equivalent: representative
combos run in a genuinely fresh subprocess and their full trajectories
must equal the in-process runs bit-for-bit — if ``reset()`` ever leaks
state that changes results, the in-process number drifts off the
fresh-process truth and this fails. Both sides execute the SAME code
(``test_integration_matrix.run_combo``), with the matrix's own builder
configurations.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

COMBOS = [("AllReduce", "flax"), ("Parallax", "sparse"),
          ("PartitionedPS", "scan")]

CHILD = """
import json, sys
sys.path.insert(0, %(root)r)
sys.path.insert(0, %(tests)r)
import conftest  # the platform, devices and backend flags of the other side
import numpy as np
from test_integration_matrix import run_combo

out = run_combo(sys.argv[1], sys.argv[2])
out["params"] = {k: np.asarray(v).tolist() for k, v in out["params"].items()}
print("RESULT\\t" + json.dumps(out))
"""


@pytest.mark.parametrize("builder_name,case_name", COMBOS)
def test_subprocess_combo_matches_inprocess(builder_name, case_name):
    script = CHILD % {"root": os.path.dirname(HERE), "tests": HERE}
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    # ambient numerics-affecting XLA flags must apply identically to both
    # sides of the comparison: the child keeps the environment's and imports
    # ``tests/conftest.py``, which appends this process's own
    proc = subprocess.run(
        [sys.executable, "-c", script, builder_name, case_name],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT\t")][-1]
    fresh = json.loads(line[len("RESULT\t"):])

    from tests.test_integration_matrix import run_combo
    ours = run_combo(builder_name, case_name)
    np.testing.assert_array_equal(fresh["losses"], ours["losses"])
    assert set(fresh["params"]) == set(ours["params"])
    for k, v in fresh["params"].items():
        np.testing.assert_array_equal(np.asarray(v), ours["params"][k],
                                      err_msg=k)
