"""Bring-up guards (ISSUE 21): the device is identified not assumed, the
compile cache is placed from outside, the pallas kernels only interpret
on the CPU backend, and ``chip_smoke.py`` has no CPU path. All cheap —
no model is built."""
import importlib.util
import json
import os
import subprocess
import sys
import time
import types

import jax
import numpy as np
import optax
import pytest

import autodist_tpu as adt
from autodist_tpu import strategy
from autodist_tpu.resource_spec import CHIP_TABLE, ResourceSpec
from autodist_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_tpus(kind, n=4):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind, id=i)
            for i in range(n)]


def test_from_local_identifies_the_attached_chip(monkeypatch):
    monkeypatch.setattr(jax, "local_devices",
                        lambda *a, **k: _fake_tpus("TPU v5 lite"))
    spec = ResourceSpec.from_local()
    assert spec.num_tpus == 4
    assert spec.slice_info["type"] == "v5e"
    assert spec.chip_kind() == "v5e"
    assert spec.chip_hbm_bytes() == 16e9 == CHIP_TABLE["v5e"].hbm_bytes
    assert CHIP_TABLE["v5e"].peak_bf16_flops == 197e12


def test_from_local_refuses_an_unknown_chip(monkeypatch):
    monkeypatch.setattr(jax, "local_devices",
                        lambda *a, **k: _fake_tpus("TPU v99 turbo"))
    with pytest.raises(ValueError) as e:
        ResourceSpec.from_local()
    assert "TPU v99 turbo" in str(e.value)
    assert "slice.type" in str(e.value) and "slice.hbm_gib" in str(e.value)


def test_spec_disagreeing_with_live_tpu_is_refused_before_lowering(
        monkeypatch):
    """A typeless ``tpus:`` spec plans as v4 (32 GB); on a live v5e that
    default may no longer decide a budget — build refuses, naming the
    knob, before anything is traced or lowered."""
    spec = ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "tpus": 4}]})
    assert spec.chip_kind() == "v4"  # the planning default is unchanged
    ad = adt.AutoDist(resource_spec=spec,
                      strategy_builder=strategy.AllReduce())
    params = {"w": np.zeros((4, 2), np.float32)}
    batch = {"x": np.zeros((8, 4), np.float32)}

    def loss_fn(p, b):
        raise AssertionError("traced: the refusal came too late")

    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: _fake_tpus("TPU v5 lite"))
    with pytest.raises(ValueError, match="slice.type: v5e"):
        ad.build(loss_fn, optax.sgd(0.1), params, batch)
    # the declared kind is what must match — and a declared match passes
    ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "tpus": 4}],
         "slice": {"type": "v5litepod-4"}}).require_live_kind("TPU v5 lite")
    with pytest.raises(ValueError, match="v5p"):
        ResourceSpec.from_dict(
            {"nodes": [{"address": "127.0.0.1", "tpus": 4}],
             "slice": {"type": "v5p-8"}}).require_live_kind("TPU v5 lite")


def test_compile_cache_is_placed_from_outside(monkeypatch):
    updates = []  # recorded, not applied: the session's config stays put
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append(value))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert updates == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert compile_cache.enable_compile_cache() == fixed
    assert updates == [fixed, fixed]


def test_kernels_interpret_on_cpu_only(monkeypatch):
    from autodist_tpu.ops import pallas_mode
    assert jax.default_backend() == "cpu"
    assert pallas_mode.interpret() is True
    with pallas_mode.compiling_for_tpu():  # a device-less compile
        assert pallas_mode.interpret() is False
    assert pallas_mode.interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_mode.interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "rocm")
    with pytest.raises(RuntimeError, match="rocm"):
        pallas_mode.interpret()


def test_chip_smoke_has_no_cpu_path():
    """Plain ``python chip_smoke.py`` on a machine without a TPU: a
    one-line reason, a non-zero exit, no result line — and fast, because
    nothing was built. Its P0 line also shows a second process landing
    on the same fixed cache path."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert time.perf_counter() - t0 < 10
    assert "need 'tpu'" in out.stderr.strip().splitlines()[-1]
    p0 = [ln for ln in out.stdout.splitlines()
          if ln.startswith("CHIP_SMOKE P0 FAIL ")]
    assert len(p0) == 1, out.stdout
    info = json.loads(p0[0][len("CHIP_SMOKE P0 FAIL "):])
    assert info["platform"] == "cpu"
    assert info["compile_cache_dir"] == os.path.join(REPO, ".jax_cache")
    assert '"ok"' not in out.stdout and "CHIP_SMOKE PASS" not in out.stdout


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key beyond ``ok`` and
    ``device`` {platform, kind, count}; diagnostics go on earlier lines."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    p0 = {"platform": "tpu", "device_kind": "TPU v5 lite", "devices": 4,
          "jax": "0.9.0", "compile_cache_dir": "/x"}
    assert json.loads(chip_smoke.result_line(p0, [])) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    failed = json.loads(chip_smoke.result_line(p0, ["P2"]))
    assert failed["ok"] is False and set(failed) == {"ok", "device"}
