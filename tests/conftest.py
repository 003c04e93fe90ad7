"""Test config: run everything on a virtual 8-device CPU mesh.

The analog of the reference's CPU-only resource specs (r2/r5), which let the
full strategy/transform path run with no accelerator
(reference ``tests/integration/test_dist.py`` notes in SURVEY §4.3).
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The suite's time is XLA:CPU compiling thousands of tiny programs whose run
# time is nothing: the CPU backend builds them at optimisation level 0 (no
# LLVM passes, the fast instruction selector), which no assertion reads (a
# CPU run gives counts and values, never a time). jaxlib reads its flags
# once, when the CPU client is made just below; the variable is then put
# back, so libtpu (loaded later, for the compiles for a described chip) and
# the processes the tests start compile as they do outside the tests. The
# bit-for-bit pins of tests/data/lm_pins.json are written under this file
# too (``python tests/test_lm_pins.py --write`` imports it).
_FLAGS = os.environ["XLA_FLAGS"]
os.environ["XLA_FLAGS"] = _FLAGS + " --xla_backend_optimization_level=0"

import jax  # noqa: E402

assert len(jax.devices()) == 8, "virtual 8-device CPU mesh not active"
os.environ["XLA_FLAGS"] = _FLAGS
os.environ.setdefault("ADT_IS_TESTING", "1")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "integration: multi-process tests gated by --run-integration")
    config.addinivalue_line(
        "markers", "needs_mp_collectives: requires multi-process CPU "
        "collectives (probed lazily at first marked test's setup)")


def pytest_runtest_setup(item):
    # lazy capability gate: probe once per run, only when a marked test is
    # actually about to execute (collection stays probe-free)
    if "needs_mp_collectives" in item.keywords:
        from _capabilities import (MP_SKIP_REASON,
                                   multiprocess_collectives_supported)
        if not multiprocess_collectives_supported():
            pytest.skip(MP_SKIP_REASON)


def pytest_addoption(parser):
    parser.addoption("--run-integration", action="store_true", default=False,
                     help="run multi-process integration tests")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-integration"):
        return
    skip = pytest.mark.skip(reason="needs --run-integration")
    for item in items:
        if "integration" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _reset_autodist():
    yield
    import autodist_tpu
    autodist_tpu.reset()
