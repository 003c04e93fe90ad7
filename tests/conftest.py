"""Test config: run everything on a virtual 8-device CPU mesh.

The analog of the reference's CPU-only resource specs (r2/r5), which let the
full strategy/transform path run with no accelerator
(reference ``tests/integration/test_dist.py`` notes in SURVEY §4.3).
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert len(jax.devices()) == 8, "virtual 8-device CPU mesh not active"
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
