"""ADT6xx numerics-safety analyzer (analysis/numerics.py + verify_numerics).

Four layers, matching the analyzer's design:

1. the mutation matrix: >= 10 seeded numerics defects, every one caught
   through BOTH the API (``numerics.lint_text`` / ``rules.verify_numerics``)
   and the CLI (``--programs`` dump mode, ``--strategy-json``, and the
   example mode's ``--numerics``/``--compute-dtype`` flags);
2. the clean matrix: example x builder x {f32, bf16} plans lint with zero
   ADT60x errors (the managed tier is clean BY CONSTRUCTION);
3. the lowering: bf16-compute programs from real builds pass the
   dtype-flow pass through ``Runner.lint_lowered``, the master params
   stay f32, and a bf16 run tracks the f32 loss curve;
4. the search space: canon never materializes a plan with ADT60x findings
   at ANY severity (the ADT312/313-style by-construction guarantee).
"""
import json

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.analysis import cli, numerics
from autodist_tpu.analysis.diagnostics import Severity
from autodist_tpu.analysis.rules import verify, verify_numerics
from autodist_tpu.model_item import ModelItem


def codes(diags):
    return sorted(d.code for d in diags)


# --------------------------------------------------------------- fixtures

_HEADER = ('module @jit_step attributes {mhlo.num_partitions = 4 : i32, '
           'mhlo.num_replicas = 1 : i32} {')
_GROUPS = ('replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>, '
           'use_global_device_ids')


def _all_reduce(val, num, ty, handle=1):
    """A region-bearing stablehlo.all_reduce statement over ``ty``."""
    scalar = ty.split("x")[-1]
    return """    %%%d = "stablehlo.all_reduce"(%s) <{channel_handle = #stablehlo.channel_handle<handle = %d, type = 1>, %s}> ({
    ^bb0(%%lhs: tensor<%s>, %%rhs: tensor<%s>):
      %%s = stablehlo.add %%lhs, %%rhs : tensor<%s>
      stablehlo.return %%s : tensor<%s>
    }) : (tensor<%s>) -> tensor<%s>""" % (
        num, val, handle, _GROUPS, scalar, scalar, scalar, scalar, ty, ty)


def _program(body, args="%arg0: tensor<8x4xf32>", results="tensor<f32>",
             ret="%9 : tensor<f32>"):
    return "%s\n  func.func public @main(%s) -> (%s) {\n%s\n    return %s\n  }\n}\n" % (
        _HEADER, args, results, body, ret)


# The clean shape the REAL bf16 lowering emits: params arrive f32, a COPY
# is cast down for compute, the gradient is cast back to f32 BEFORE the
# accumulating collective, and the loss is f32. Zero ADT60x findings.
CLEAN_BF16 = _program(
    "\n".join([
        "    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xbf16>",
        "    %1 = stablehlo.dot_general %0, %0, contracting_dims = [1] x [1] : (tensor<8x4xbf16>, tensor<8x4xbf16>) -> tensor<8x8xbf16>",
        "    %2 = stablehlo.convert %1 : (tensor<8x8xbf16>) -> tensor<8x8xf32>",
        _all_reduce("%2", 3, "8x8xf32"),
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %9 = stablehlo.reduce(%3 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x8xf32>, tensor<f32>) -> tensor<f32>",
    ]))

# Every text-level mutation: (name, program text, code, severity). Each is
# CLEAN_BF16 with exactly one numerics defect injected.
TEXT_MUTATIONS = [
    # 1. gradient psum in bf16 — the accumulator rounds every hop
    ("bf16_psum", _program("\n".join([
        "    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xbf16>",
        _all_reduce("%0", 1, "8x4xbf16"),
        "    %2 = stablehlo.convert %1 : (tensor<8x4xbf16>) -> tensor<8x4xf32>",
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %9 = stablehlo.reduce(%2 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>",
    ])), "ADT601", Severity.ERROR),
    # 2. f16 variant of the same defect (the table covers both halves)
    ("f16_psum", _program("\n".join([
        "    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xf16>",
        _all_reduce("%0", 1, "8x4xf16"),
        "    %2 = stablehlo.convert %1 : (tensor<8x4xf16>) -> tensor<8x4xf32>",
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %9 = stablehlo.reduce(%2 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>",
    ])), "ADT601", Severity.ERROR),
    # 3. reduce_scatter in bf16 — the ZeRO wire without the f32 cast-up
    ("bf16_reduce_scatter", _program("\n".join([
        "    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xbf16>",
        ('    %1 = "stablehlo.reduce_scatter"(%0) <{channel_handle = '
         '#stablehlo.channel_handle<handle = 1, type = 1>, '
         'scatter_dimension = 0 : i64, ' + _GROUPS + '}> ({'),
        "    ^bb0(%lhs: tensor<bf16>, %rhs: tensor<bf16>):",
        "      %s = stablehlo.add %lhs, %rhs : tensor<bf16>",
        "      stablehlo.return %s : tensor<bf16>",
        "    }) : (tensor<8x4xbf16>) -> tensor<2x4xbf16>",
        "    %2 = stablehlo.convert %1 : (tensor<2x4xbf16>) -> tensor<2x4xf32>",
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %9 = stablehlo.reduce(%2 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<2x4xf32>, tensor<f32>) -> tensor<f32>",
    ])), "ADT601", Severity.ERROR),
    # 4. scalar bf16 cross-replica sum: the loss pmean on rounded values
    ("bf16_scalar_loss_pmean", _program("\n".join([
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %0 = stablehlo.reduce(%arg0 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>",
        "    %1 = stablehlo.convert %0 : (tensor<f32>) -> tensor<bf16>",
        _all_reduce("%1", 2, "bf16"),
        "    %9 = stablehlo.convert %2 : (tensor<bf16>) -> tensor<f32>",
    ])), "ADT603", Severity.WARNING),
    # 5. master round-trip: the "updated" f32 param IS the rounded value
    ("master_roundtrip", _program("\n".join([
        "    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xbf16>",
        "    %1 = stablehlo.convert %0 : (tensor<8x4xbf16>) -> tensor<8x4xf32>",
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %9 = stablehlo.reduce(%1 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>",
    ])), "ADT602", Severity.ERROR),
    # 6. the round-trip hidden behind other value-preserving ops
    ("master_roundtrip_via_transpose", _program("\n".join([
        "    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xbf16>",
        "    %1 = stablehlo.transpose %0, dims = [1, 0] : (tensor<8x4xbf16>) -> tensor<4x8xbf16>",
        "    %2 = stablehlo.convert %1 : (tensor<4x8xbf16>) -> tensor<4x8xf32>",
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %9 = stablehlo.reduce(%2 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<4x8xf32>, tensor<f32>) -> tensor<f32>",
    ])), "ADT602", Severity.ERROR),
    # 7. entry returns the loss as a bf16 scalar — rounded before any
    # consumer (sentinel EWMA, early stopping) sees it
    ("half_loss_returned", _program("\n".join([
        "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
        "    %0 = stablehlo.reduce(%arg0 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>",
        "    %9 = stablehlo.convert %0 : (tensor<f32>) -> tensor<bf16>",
    ]), results="tensor<bf16>", ret="%9 : tensor<bf16>"),
     "ADT603", Severity.WARNING),
]

# train/eval pair whose collectives are order-compatible (same kind,
# groups, element count) but disagree on the element dtype: the ADT605
# rendezvous defect no shape-level check can see.
TRAIN_F32 = _program("\n".join([
    _all_reduce("%arg0", 1, "8x4xf32"),
    "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
    "    %9 = stablehlo.reduce(%1 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>",
]))
EVAL_BF16 = _program("\n".join([
    "    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xbf16>",
    _all_reduce("%0", 1, "8x4xbf16"),
    "    %2 = stablehlo.convert %1 : (tensor<8x4xbf16>) -> tensor<8x4xf32>",
    "    %cst = stablehlo.constant dense<0.0> : tensor<f32>",
    "    %9 = stablehlo.reduce(%2 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>",
]))


def _mlp_item(dtype=np.float32):
    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(16, 32) * 0.1, dtype),
              "w2": jnp.asarray(rng.randn(32, 4) * 0.1, dtype)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"].astype(jnp.float32))
        return jnp.mean((h @ p["w2"].astype(jnp.float32) - b["y"]) ** 2)

    batch = {"x": np.zeros((8, 16), np.float32),
             "y": np.zeros((8, 4), np.float32)}
    return ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare(), batch


def _spec(n=4):
    from autodist_tpu.resource_spec import ResourceSpec
    return ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True, "tpus": n}]})


# ------------------------------------------------- 1. the mutation matrix


def test_clean_bf16_shape_has_no_findings():
    """The managed tier's exact lowering shape — bf16 compute, f32
    accumulation, f32 loss — produces ZERO findings (the analyzer must
    not cry wolf on the thing it exists to enable)."""
    assert numerics.lint_text(CLEAN_BF16) == []


@pytest.mark.parametrize("name,text,code,severity",
                         TEXT_MUTATIONS,
                         ids=[m[0] for m in TEXT_MUTATIONS])
def test_text_mutations_caught_via_api(name, text, code, severity):
    diags = numerics.lint_text(text)
    hits = [d for d in diags if d.code == code]
    assert hits, (name, codes(diags))
    assert all(d.severity == severity for d in hits), hits


@pytest.mark.parametrize("name,text,code,severity",
                         TEXT_MUTATIONS,
                         ids=[m[0] for m in TEXT_MUTATIONS])
def test_text_mutations_caught_via_cli(tmp_path, capsys, name, text, code,
                                       severity):
    """The same defects through ``--programs`` dump mode: errors exit 1,
    warnings exit 0, and the finding appears in the JSON document."""
    f = tmp_path / ("%s.hlo" % name)
    f.write_text(text)
    rc = cli.main(["--programs", str(f), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    found = {d["code"] for p in doc["programs"] for d in p["diagnostics"]}
    assert code in found, (name, found)
    assert rc == (1 if severity >= Severity.ERROR else 0)


def test_cross_program_dtype_mismatch_api():
    diags = numerics.lint_programs({"train": TRAIN_F32, "eval": EVAL_BF16})
    assert "ADT605" in codes(diags)
    # ADT605 only fires on a genuine disagreement: the pair against
    # itself is clean, and the bf16 side alone carries its own ADT601
    assert "ADT605" not in codes(
        numerics.lint_programs({"a": TRAIN_F32, "b": TRAIN_F32}))


def test_cross_program_dtype_mismatch_cli(tmp_path, capsys):
    ftrain = tmp_path / "train.hlo"
    feval = tmp_path / "eval.hlo"
    ftrain.write_text(TRAIN_F32)
    feval.write_text(EVAL_BF16)
    rc = cli.main(["--programs", str(ftrain), str(feval),
                   "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    cross = {d["code"]
             for d in doc["schedule_check"]["diagnostics"]}
    assert "ADT605" in cross
    assert rc == 1


def test_half_stored_params_plan_level_api():
    """Mutation: params STORED in bf16 under AllReduce — no f32 master
    anywhere. Both plan-level errors fire through verify_numerics AND
    through the registered rule that verify()/the searcher runs."""
    item, _ = _mlp_item(jnp.bfloat16)
    spec = _spec()
    strategy = S.AllReduce().build(item, spec)
    diags = verify_numerics(strategy, item, spec)
    assert "ADT601" in codes(diags) and "ADT602" in codes(diags)
    errors = [d for d in diags if d.severity >= Severity.ERROR]
    assert {"ADT601", "ADT602"} <= {d.code for d in errors}
    # the registered rule path (what AutoDist(validate=) and the search
    # scorer consume) sees the same errors
    assert {"ADT601", "ADT602"} <= set(codes(verify(strategy, item, spec)))


def test_half_stored_params_lowered_cli(tmp_path, capsys):
    """The SAME defect caught one layer down: lower a real bf16-stored
    training step and run the CLI dtype-flow pass over the dump — the
    half psum is right there in the text (ADT601 at exit 1)."""
    autodist_tpu.reset()
    item, batch = _mlp_item(jnp.bfloat16)
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
    runner = ad.build(item.loss_fn, optax.adam(1e-3),
                      dict(item.params), batch)
    runner.init(dict(item.params))
    text = runner.lowered_text(batch)
    autodist_tpu.reset()
    f = tmp_path / "half_stored.hlo"
    f.write_text(text)
    rc = cli.main(["--programs", str(f), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    found = {d["code"] for p in doc["programs"] for d in p["diagnostics"]}
    assert "ADT601" in found
    assert rc == 1


def test_unknown_compute_dtype_api_and_cli(tmp_path, capsys):
    """Mutation: compute_dtype="fp8" (not a supported tier). The plan
    rule errors through the API, and a serialized strategy carrying it
    is rejected by the CLI's --strategy-json mode at exit 1."""
    item, _ = _mlp_item()
    spec = _spec()
    strategy = S.AllReduce().build(item, spec)
    strategy.graph_config.compute_dtype = "fp8"
    diags = verify(strategy, item, spec)
    bad = [d for d in diags if d.code == "ADT602"]
    assert bad and all(d.severity >= Severity.ERROR for d in bad)

    f = tmp_path / "strategy.json"
    f.write_text(json.dumps(strategy.to_dict()))
    rc = cli.main(["sentiment_classifier", "--strategy-json", str(f),
                   "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert "ADT602" in {d["code"] for d in doc["diagnostics"]}


def test_sentinel_less_bf16_api_and_cli(capsys):
    """Mutation: a bf16 plan armed with NO sentinel — legal but
    unguarded (ADT604 warning, exit stays 0). An enabled policy
    silences it."""
    from autodist_tpu.runtime.sentinel import SentinelPolicy
    item, _ = _mlp_item()
    spec = _spec()
    strategy = S.AllReduce(compute_dtype="bf16").build(item, spec)
    diags = verify_numerics(strategy, item, spec)
    assert "ADT604" in codes(diags)
    assert all(d.severity == Severity.WARNING
               for d in diags if d.code == "ADT604")
    armed = verify_numerics(strategy, item, spec,
                            sentinel_policy=SentinelPolicy(enabled=True))
    assert "ADT604" not in codes(armed)

    rc = cli.main(["sentiment_classifier", "--strategy", "AllReduce",
                   "--numerics", "--compute-dtype", "bf16",
                   "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["errors"] == 0
    assert "ADT604" in {d["code"] for d in doc["diagnostics"]}


def test_zero_sharded_exemption_flip():
    """bf16-stored params are EXEMPT under an all-ZeroSharded plan (f32
    shard math + f32 opt state IS the master); the same vars under
    AllReduce are the ADT601/602 mutation. The flip is the boundary."""
    item, _ = _mlp_item(jnp.bfloat16)
    spec = _spec()
    zero = S.ZeroSharded().build(item, spec)
    meta_ok = all("Zero" in type(n.synchronizer).__name__
                  for n in zero.node_config)
    assert meta_ok, [type(n.synchronizer).__name__
                     for n in zero.node_config]
    clean = [d for d in verify_numerics(zero, item, spec)
             if d.code in ("ADT601", "ADT602")]
    assert clean == [], codes(clean)
    flipped = S.AllReduce().build(item, spec)
    assert {"ADT601", "ADT602"} <= set(
        codes(verify_numerics(flipped, item, spec)))


def test_loss_tier_warning_on_unmanaged_half_params():
    """ADT603 at plan level: half-stored params WITHOUT the managed
    compute tier leak the compute dtype into the loss; the managed tier
    (f32 params + compute_dtype=bf16) does not trip it."""
    item, _ = _mlp_item(jnp.bfloat16)
    spec = _spec()
    unmanaged = S.AllReduce().build(item, spec)
    assert "ADT603" in codes(verify_numerics(unmanaged, item, spec))
    f32_item, _ = _mlp_item()
    managed = S.AllReduce(compute_dtype="bf16").build(f32_item, spec)
    assert "ADT603" not in codes(verify_numerics(managed, f32_item, spec))


# ------------------------------------------------------ 2. the clean matrix

_MATRIX_EXAMPLES = ["sentiment_classifier", "lm1b"]
_MATRIX_BUILDERS = ["PS", "PSLoadBalancing", "PartitionedPS", "AllReduce",
                    "AllReduceInt8Wire", "PSInt8Wire", "PartitionedAR",
                    "ZeroSharded", "ZeroShardedInt8Wire", "Parallax",
                    "WithRemat"]


@pytest.mark.parametrize("example", _MATRIX_EXAMPLES)
@pytest.mark.parametrize("builder", _MATRIX_BUILDERS)
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_example_builder_dtype_matrix_lints_clean(capsys, example, builder,
                                                  dtype):
    """Acceptance: every example x builder x {f32, bf16} builder plan
    lints with zero ADT60x ERRORS through the CLI's --numerics leg (the
    sentinel-less ADT604 warning is expected on bf16 and does not fail
    the lint)."""
    rc = cli.main([example, "--strategy", builder, "--numerics",
                   "--compute-dtype", dtype, "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0, doc
    adt6_errors = [d for d in doc["diagnostics"]
                   if d["code"].startswith("ADT60")
                   and d["severity"] == "error"]
    assert adt6_errors == []


# ------------------------------------------------------- 3. the lowering


BF16_BUILDERS = [
    ("AllReduce-bf16", lambda: S.AllReduce(compute_dtype="bf16")),
    ("ZeroSharded-bf16", lambda: S.ZeroSharded(compute_dtype="bf16")),
    ("PS-bf16", lambda: S.PS(compute_dtype="bf16")),
]


@pytest.mark.parametrize("name,builder", BF16_BUILDERS,
                         ids=[b[0] for b in BF16_BUILDERS])
def test_bf16_lowered_program_lints_clean(name, builder):
    """The managed tier's real lowering passes its own analyzer: bf16
    compute is visible in the program, but accumulation and loss are
    f32, so Runner.lint_lowered reports zero ADT60x."""
    autodist_tpu.reset()
    item, batch = _mlp_item()
    ad = autodist_tpu.AutoDist(strategy_builder=builder())
    runner = ad.build(item.loss_fn, optax.adam(1e-2),
                      dict(item.params), batch)
    runner.init(dict(item.params))
    text = runner.lowered_text(batch)
    assert "bf16" in text, "the bf16 tier lowered no bf16 compute"
    diags = runner.lint_lowered(batch)
    adt6 = [d for d in diags if d.code.startswith("ADT60")]
    assert adt6 == [], codes(adt6)
    autodist_tpu.reset()


def test_bf16_e2e_loss_parity_and_f32_master():
    """Acceptance: a bf16 plan TRAINS — the loss tracks the f32 curve
    within the sentinel-scale band, step_stats reports the tier, and
    gathered params stay float32 (the master never leaves f32). With
    the sentinel armed (ADT604: half precision ships with the skip /
    rollback net) bf16 rounding alone trips no guard, and the casts add
    no dispatch."""
    import jax

    def leg(compute_dtype, sentinel=None):
        autodist_tpu.reset()
        item, batch = _mlp_item()
        rng = np.random.RandomState(1)
        batches = [{"x": rng.randn(8, 16).astype(np.float32),
                    "y": rng.randn(8, 4).astype(np.float32)}
                   for _ in range(10)]
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(
            compute_dtype=compute_dtype))
        runner = ad.build(item.loss_fn, optax.adam(1e-2),
                          dict(item.params), batches[0], sentinel=sentinel)
        runner.init(dict(item.params))
        hist = runner.fit(batches)
        stats = dict(runner.step_stats(),
                     dispatches=runner.distributed_step.dispatches)
        leaves = {str(x.dtype) for x in jax.tree_util.tree_leaves(
            runner.gather_params())}
        return [float(m["loss"]) for m in hist], stats, leaves

    f_losses, f_stats, f_leaves = leg("f32")
    b_losses, b_stats, b_leaves = leg("bf16", sentinel=True)
    autodist_tpu.reset()
    assert f_stats["compute_dtype"] == "f32"
    assert b_stats["compute_dtype"] == "bf16"
    assert b_stats["sentinel"]["skips"] == 0
    assert b_stats["sentinel"]["rollbacks"] == 0
    assert b_stats["dispatches"] == f_stats["dispatches"]
    assert f_leaves == b_leaves == {"float32"}
    np.testing.assert_allclose(b_losses, f_losses, rtol=0.3, atol=5e-3)
    assert abs(b_losses[-1] - f_losses[-1]) <= (
        0.1 * max(abs(f_losses[-1]), 1e-3) + 1e-3)


# ----------------------------------------------------- 4. the search space


def test_search_canon_never_emits_adt60x():
    """Acceptance: seeds + a deep mutation sweep, every materialized
    plan verified — zero ADT60x at ANY severity (with a sentinel armed,
    as the searcher's deployments are). The compute axis is in the
    space (both tiers must appear) yet canon keeps it numerics-clean by
    construction."""
    import random
    from autodist_tpu.runtime.sentinel import SentinelPolicy
    from autodist_tpu.search.space import PlanSpace
    item, _ = _mlp_item()
    spec = _spec()
    space = PlanSpace(item, spec)
    rng = random.Random(0)
    frontier = [plan for _, plan in space.seeds()]
    assert {p.compute_dtype for p in frontier} == {"f32", "bf16"}
    seen_dtypes = set()
    policy = SentinelPolicy(enabled=True)
    for step in range(150):
        plan = frontier[rng.randrange(len(frontier))]
        mut = space.mutate(plan, rng)
        if mut is None:
            continue
        plan, _op = mut
        frontier.append(plan)
        seen_dtypes.add(plan.compute_dtype)
        strategy = space.build(plan)
        adt6 = [d for d in verify(strategy, item, spec)
                if d.code.startswith("ADT60")]
        adt6 += [d for d in verify_numerics(strategy, item, spec,
                                            sentinel_policy=policy)
                 if d.code.startswith("ADT60")]
        assert adt6 == [], (plan.describe(), codes(adt6))
    assert seen_dtypes == {"f32", "bf16"}, seen_dtypes


def test_plan_roundtrip_keeps_compute_dtype():
    """Strategy IR round-trip: compute_dtype survives to_dict/from_dict
    and from_strategy rejects an out-of-space tier instead of laundering
    it into the search frontier."""
    from autodist_tpu.search.space import PlanSpace
    from autodist_tpu.strategy.base import Strategy
    item, _ = _mlp_item()
    spec = _spec()
    space = PlanSpace(item, spec)
    strategy = S.AllReduce(compute_dtype="bf16").build(item, spec)
    rt = Strategy.from_dict(strategy.to_dict())
    assert rt.graph_config.compute_dtype == "bf16"
    plan = space.from_strategy(rt)
    assert plan is not None and plan.compute_dtype == "bf16"
    rt.graph_config.compute_dtype = "fp8"
    assert space.from_strategy(rt) is None
