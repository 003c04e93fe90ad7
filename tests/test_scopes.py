"""Names inside the compiled programs and the host's spans around them.

The tracing contracts (``telemetry/scopes.py``, docs/observability.md
"Scopes inside the compiled programs"):

- the compiled step's instruction -> ``op_name`` map holds every scope of
  the table, tells forward from backward ops of the loss, and finds the
  lean head's ``custom_vjp`` backward rule under its own scope, with and
  without remat; a Kimi-Linear model's mixers (``kda`` > ``kda_scan``,
  ``mla`` > ``mla_core``, ``moe_shared``) are there too, with their
  recomputed ops; a DeepSeek-V2 model's step has ``mla_core`` around its
  scores alone and hands ``moe.aux_loss`` out beside the loss; a Keye-VL-2.0
  model's step has ``dsa_index``, ``dsa_topk`` and ``dsa_core`` inside
  ``attention``, recomputes neither of the first two, and hands out the
  pairs its indexers chose; an LFM2 model's step has ``conv_mix`` >
  ``conv_core`` inside ``attention``, the projections outside the core;
- every matmul of a family's step lies under a sub-layer's name
  (``attention``, ``moe``, ``dense_ffn``; a head's or the exit gate's in
  the loss), ``attn_core`` is a second name on every softmax core, a
  shared expert stays under ``moe_shared``;
- the map is computed ON DEMAND: a fit, traced or not, lowers and
  compiles nothing extra; the step account (``telemetry.step_account``)
  is read off the map's one compile;
- ``runner.readback`` is tiled by its two children (the wait for the
  device, the copy), every span of a step carries that step's index, and
  a fit of N steps yields N of each;
- with tracing on, a ``jax.profiler`` session holds the spans as
  ``TraceAnnotation``\\ s on the profiler's own clock;
- the goodput report charges the wait to ``compute`` and still sums to
  the wall;
- ``DecodeEngine`` results carry the four timestamps, and its spans the
  request ids;
- set-up is an account of its own (docs/observability.md "Set-up"): every
  phase of build, init and the first step once, children inside parents,
  what JAX traced and compiled beneath them, the trace-time gauges; it
  outlives ``clear()``, ``reset()`` and a new build drop it, a recompile
  after set-up is the window's, and with tracing off there is none.
"""
import contextlib
import glob
import re

import jax
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu import telemetry
from autodist_tpu.data.prefetch import DevicePrefetcher
from autodist_tpu.models import lm
from autodist_tpu.runtime.runner import Runner
from autodist_tpu.telemetry import scopes
from autodist_tpu.telemetry import spans as tel

STEP = "jit_local_step"
PER_STEP = ("runner.next_batch", "runner.prologue", "runner.dispatch",
            "runner.feed", "dstep.dispatch", "runner.release",
            "runner.control",
            "runner.readback", "runner.wait_device", "runner.fetch",
            "runner.step_time", "runner.callbacks")


@pytest.fixture(autouse=True)
def _tracing_off_after():
    yield
    tel.configure(None)


def build_lm(builder=None, **build_kw):
    cfg = lm.LMConfig.tiny()
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=8, lean_head=True)
    ad = autodist_tpu.AutoDist(strategy_builder=builder or S.AllReduce())
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch, **build_kw)
    runner.init(params)
    return runner, batch, cfg


def components(op_name):
    """Path components of an op_name with their transform wrappers."""
    return op_name.split("/")


def pass_of(op_name):
    """"fwd" / "bwd" by the FIRST loss component of the path (under remat
    a backward op reads transpose(jvp(loss))/jvp(loss)/checkpoint/...),
    None outside the differentiated loss."""
    for part in components(op_name):
        if part.endswith("(loss))") or part.endswith("(loss)"):
            return "bwd" if "transpose(" in part else "fwd"
    return None


def under(scope_map, *wanted, in_pass=None):
    """Instructions with an op_name whose path holds every wanted
    component, in the given pass of the loss if one is named."""
    return [name for name, ops in scope_map.items()
            if any(all(w in components(o) for w in wanted)
                   and (in_pass is None or pass_of(o) == in_pass)
                   for o in ops)]


# ------------------------------------------------------------ (a) the map


@contextlib.contextmanager
def small_chip():
    """``lm._chip_hbm_bytes`` made so small that ``auto_remat_blocks``
    recomputes every block in the backward pass."""
    chip = lm._chip_hbm_bytes
    lm._chip_hbm_bytes = lambda: 1e5
    try:
        yield
    finally:
        lm._chip_hbm_bytes = chip


def tiny_lm1b(remat):
    builder = S.WithRemat(S.AllReduce()) if remat else S.AllReduce()
    runner, batch, _ = build_lm(builder, sentinel=True)
    assert bool(runner.distributed_step.strategy.graph_config.remat) == remat
    return runner, batch


def tiny_kimi_linear():
    """Two KDA layers, a latent one, a dense and two routed feed-forwards
    with a shared expert, every block recomputed."""
    import dataclasses
    cfg = dataclasses.replace(
        lm.LMConfig.kimi_linear_48b_a3b(
            num_layers=3, max_seq_len=32, layer_types=("kda", "mla", "kda")),
        vocab_size=128, d_model=32, num_heads=2, mlp_dim=16, kda_num_heads=2,
        kda_head_dim=16, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, dense_dim=64, num_experts=8,
        experts_per_token=2, experts_held=(0, 1))
    with small_chip():
        return lm.make_train_setup(cfg, seq_len=16, batch_size=8)


def tiny_deepseek_v2():
    import dataclasses
    cfg = dataclasses.replace(
        lm.LMConfig.deepseek_v2_lite(num_layers=3, max_seq_len=32),
        vocab_size=128, d_model=32, num_heads=2, mlp_dim=16, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, dense_dim=64,
        num_experts=8, experts_per_token=2, experts_held=(0, 1))
    return lm.make_train_setup(cfg, seq_len=16, batch_size=8)


def tiny_keye_vl2():
    from tests.test_keye_vl2 import tiny_config
    with small_chip():
        return lm.make_train_setup(tiny_config(indexer_topk=4), seq_len=16,
                                   batch_size=8)


def tiny_lfm2():
    from tests.test_lfm2_moe import tiny_config
    with small_chip():
        return lm.make_train_setup(tiny_config(), seq_len=16, batch_size=8)


def tiny_olmoe():
    from tests.test_olmoe import tiny_config
    return lm.make_train_setup(tiny_config(), seq_len=16, batch_size=8)


def tiny_ouro():
    from tests.test_ouro import tiny_config
    with small_chip():
        return lm.make_train_setup(tiny_config(), seq_len=16, batch_size=8)


def tiny_nemotron_h():
    from tests.test_nemotron_h import tiny_config
    with small_chip():
        return lm.make_train_setup(tiny_config(), seq_len=16, batch_size=8)


FAMILIES = {"kimi_linear": tiny_kimi_linear, "deepseek_v2": tiny_deepseek_v2,
            "keye_vl2": tiny_keye_vl2, "lfm2": tiny_lfm2, "olmoe": tiny_olmoe,
            "ouro": tiny_ouro, "nemotron_h": tiny_nemotron_h}
STEPS = ["lm1b", "lm1b_remat"] + sorted(FAMILIES)


@pytest.fixture(scope="module")
def step_of():
    """``step_of(family)``: one step of a tiny model of the family and what
    the program says of it afterwards: ``{"loss_fn", "counters", "map",
    "account"}``. Built on first use and kept for the module: every case
    that reads a family's step map shares ONE build and ONE compile of it."""
    built = {}

    def get(family):
        if family not in built:
            autodist_tpu.reset()
            if family.startswith("lm1b"):
                loss_fn = None
                runner, batch = tiny_lm1b(remat=family == "lm1b_remat")
            else:
                loss_fn, params, batch, _ = FAMILIES[family]()
                ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce())
                runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
                runner.init(params)
            counters = runner.run(batch).get("counters", {})
            assert STEP in telemetry.registered_programs()
            m = telemetry.scope_map(STEP)
            assert m is telemetry.scope_map(STEP)  # computed once, kept
            built[family] = {"loss_fn": loss_fn, "counters": counters,
                             "map": m,
                             "account": telemetry.step_account(STEP)}
            autodist_tpu.reset()
        return built[family]
    try:
        yield get
    finally:
        autodist_tpu.reset()


@pytest.fixture(scope="module")
def kimi_linear_step_map(step_of):
    return step_of("kimi_linear")["map"]


def paths(scope_map):
    return [o for ops in scope_map.values() for o in ops]


@pytest.mark.parametrize("remat", [False, True])
def test_step_map_holds_every_scope_and_tells_the_passes_apart(step_of,
                                                               remat):
    m = step_of("lm1b_remat" if remat else "lm1b")["map"]
    # (replicated storage gathers nothing: scopes.PARAMS has its own test)
    for name in (scopes.GRAD_SYNC, scopes.OPTIMIZER, scopes.SENTINEL):
        assert under(m, name), name
    # the loss: JAX itself wraps the scope per pass
    assert under(m, "jvp(loss)", in_pass="fwd")
    assert under(m, "transpose(jvp(loss))", in_pass="bwd")
    # the model's scopes, in both passes
    for name in (scopes.EMBED, scopes.BLOCKS, scopes.ATTENTION,
                 scopes.ATTN_CORE, scopes.DENSE_FFN):
        # (XLA merges a recomputed op with its forward twin and keeps one
        # of the two names: under remat the lookup shows in one pass only)
        assert under(m, name, in_pass="fwd") or (remat
                                                 and name == scopes.EMBED)
        assert under(m, name, in_pass="bwd"), name
    # attention sits inside a block, its core inside attention, the dense
    # feed-forward inside a block and outside attention
    assert all(o.index("/blocks/") < o.index("/attention/")
               for o in paths(m) if "/attention/" in o)
    assert all(o.index("/attention/") < o.index("/attn_core/")
               for o in paths(m) if "/attn_core/" in o)
    assert all(o.index("/blocks/") < o.index("/dense_ffn/")
               and "/attention/" not in o
               for o in paths(m) if "/dense_ffn/" in o)
    # the lean head: forward under the forward pass, and the custom_vjp
    # rule, traced at transpose time, under its own scope in the backward
    assert under(m, scopes.LEAN_HEAD, in_pass="fwd")
    head_bwd = under(m, scopes.LEAN_HEAD_BWD, in_pass="bwd")
    assert head_bwd and not under(m, scopes.LEAN_HEAD_BWD, in_pass="fwd")
    assert not under(m, scopes.PLAIN_HEAD)  # a step has one or the other
    dots = [o for n in head_bwd for o in m[n] if "dot_general" in o
            and scopes.LEAN_HEAD_BWD in components(o)]
    assert dots, "the rule's three chunk matmuls are under its scope"
    if remat:
        assert under(m, "rematted_computation", in_pass="bwd")
        assert not under(m, "rematted_computation", in_pass="fwd")
    # every scope the step uses is a name of the table
    step_scopes = {scopes.PARAMS, scopes.LOSS, scopes.GRAD_SYNC,
                   scopes.OPTIMIZER, scopes.SENTINEL, scopes.LEAN_HEAD,
                   scopes.LEAN_HEAD_BWD, scopes.EMBED, scopes.BLOCKS,
                   scopes.ATTENTION, scopes.ATTN_CORE, scopes.DENSE_FFN,
                   scopes.PLAIN_HEAD}
    assert step_scopes <= set(scopes.SCOPES)
    with pytest.raises(KeyError):
        scopes.scope("no_such_scope")


MATMULS = ("dot_general", "conv_general_dilated")
BLOCK_PARTS = (scopes.ATTENTION, scopes.MOE, scopes.DENSE_FFN)
HEADS = (scopes.LEAN_HEAD, scopes.LEAN_HEAD_BWD, scopes.PLAIN_HEAD,
         scopes.EXIT_GATE)


@pytest.mark.parametrize("family", STEPS)
def test_no_matmul_of_a_step_is_without_a_sub_layers_name(step_of, family):
    """Every matmul and convolution under ``blocks`` lies under
    ``attention``, ``moe`` or ``dense_ffn`` (what is left of a block, the
    ``block_rest_ms_per_step`` of the benchmark, holds none), and every
    one of the loss outside the blocks and the embedding under a head's
    name or the exit gate's. None of the three names PR 49 added is a
    flax module's name: a reader matches a whole path component."""
    m = step_of(family)["map"]
    matmuls = [o for o in paths(m) if o.endswith(MATMULS)]
    in_blocks = [o for o in matmuls if scopes.BLOCKS in components(o)]
    assert in_blocks
    bare = [o for o in in_blocks
            if not set(BLOCK_PARTS) & set(components(o))]
    assert not bare, bare[:3]
    of_the_loss = [o for o in matmuls if pass_of(o) is not None
                   and not {scopes.BLOCKS, scopes.EMBED} & set(components(o))]
    assert of_the_loss
    bare = [o for o in of_the_loss if not set(HEADS) & set(components(o))]
    assert not bare, bare[:3]
    # each new name is on a path only where this tree's scope put it:
    # outside its outer scope it would be a module's name
    outer = {scopes.ATTN_CORE: scopes.ATTENTION, scopes.DENSE_FFN:
             scopes.BLOCKS, scopes.PLAIN_HEAD: "loss"}
    for name, outside in outer.items():
        for o in paths(m):
            if name in components(o):
                upto = o[:o.index("/" + name + "/")]
                assert outside in re.split(r"[/()]", upto), o
    assert under(m, scopes.ATTN_CORE)
    assert bool(under(m, scopes.LEAN_HEAD)) != bool(
        under(m, scopes.PLAIN_HEAD))


@pytest.mark.parametrize("family, core", [
    ("deepseek_v2", scopes.MLA_CORE), ("kimi_linear", scopes.MLA_CORE),
    ("keye_vl2", scopes.DSA_CORE), ("lfm2", scopes.DSA_CORE),
    ("nemotron_h", scopes.DSA_CORE)])
def test_the_neutral_core_scope_holds_what_the_named_core_holds(
        step_of, family, core):
    """``attn_core`` is a second name on the same ops: every path that
    holds it holds the family's own core scope and the reverse, so
    ``attn_core_ms_per_step`` reads what ``mla_core_ms_per_step`` /
    ``dsa_core_ms_per_step`` read."""
    m = step_of(family)["map"]
    both = [o for o in paths(m) if scopes.ATTN_CORE in components(o)]
    assert both and any(o.endswith("dot_general") for o in both)
    assert all(core in components(o) for o in both)
    assert all(scopes.ATTN_CORE in components(o) for o in paths(m)
               if core in components(o))
    assert all(components(o).index(scopes.ATTENTION)
               < components(o).index(scopes.ATTN_CORE)
               < components(o).index(core) for o in both)


@pytest.mark.parametrize("family", ["deepseek_v2", "kimi_linear",
                                    "nemotron_h"])
def test_a_shared_expert_stays_the_routed_feed_forwards(step_of, family):
    """A shared expert is a dense feed-forward module too, and its ops
    lie under ``moe`` > ``moe_shared``, never under ``dense_ffn``."""
    m = step_of(family)["map"]
    shared = [o for o in paths(m) if "shared" in components(o)]
    assert any(o.endswith("dot_general") for o in shared)
    assert all(scopes.MOE_SHARED in components(o)
               and scopes.DENSE_FFN not in components(o) for o in shared)
    assert not any(scopes.MOE in components(o) for o in paths(m)
                   if scopes.DENSE_FFN in components(o))


@pytest.mark.parametrize("scope, outer", [
    (scopes.KDA, scopes.ATTENTION), (scopes.KDA_SCAN, scopes.KDA),
    (scopes.MLA, scopes.ATTENTION), (scopes.MLA_CORE, scopes.MLA),
    (scopes.MOE_SHARED, scopes.MOE)])
def test_a_mixers_scope_holds_its_forward_backward_and_recomputed_ops(
        kimi_linear_step_map, scope, outer):
    m = kimi_linear_step_map
    assert scope in scopes.SCOPES
    # (XLA merges a recomputed op with its forward twin and keeps one of
    # the two names, so the forward pass may show under the backward's)
    assert under(m, scope, in_pass="fwd") or under(
        m, scope, "rematted_computation")
    assert under(m, scope, in_pass="bwd")
    assert under(m, scope, "rematted_computation", in_pass="bwd")
    assert not under(m, scope, "rematted_computation", in_pass="fwd")
    # it sits inside its outer scope, inside a block
    inside = [o for o in paths(m) if scope in components(o)]
    assert inside and all(
        components(o).index(scopes.BLOCKS) < components(o).index(outer)
        < components(o).index(scope) for o in inside)


def test_the_delta_rules_matmuls_are_under_its_scope(kimi_linear_step_map):
    m = kimi_linear_step_map
    dots = [o for n in under(m, scopes.KDA_SCAN) for o in m[n]
            if "dot_general" in o and scopes.KDA_SCAN in components(o)]
    assert dots
    # the mixer's projections are KDA's and not the core's
    assert [o for n in under(m, scopes.KDA) for o in m[n]
            if "dot_general" in o and scopes.KDA in components(o)
            and scopes.KDA_SCAN not in components(o)]


def test_a_deepseek_v2_step_names_its_attention_cores_and_counts_its_balance_loss(
        step_of):
    """``mla_core`` holds the scores' matmuls and the softmax and NOT the
    mixer's projections, rotation or temperature; the step's metrics carry
    the device counter ``moe.aux_loss`` (the routed layers' balance losses
    summed: between 1 a layer, an even router, and E / k)."""
    step = step_of("deepseek_v2")
    assert "moe.aux_loss" in step["loss_fn"].device_counters
    counters, m = step["counters"], step["map"]
    assert 2 * 1.0 <= float(counters["moe.aux_loss"]) <= 2 * 8 / 2
    core = [o for o in paths(m) if scopes.MLA_CORE in components(o)]
    assert core and all(
        components(o).index(scopes.ATTENTION) < components(o).index(scopes.MLA)
        < components(o).index(scopes.MLA_CORE) for o in core)
    assert any("dot_general" in o for o in core)
    assert under(m, scopes.MLA_CORE, in_pass="fwd")
    assert under(m, scopes.MLA_CORE, in_pass="bwd")
    # the projections and the rotation are the mixer's, not the core's
    outside = [o for o in paths(m) if scopes.MLA in components(o)
               and scopes.MLA_CORE not in components(o)]
    assert any("dot_general" in o for o in outside)
    assert any(o.endswith(("/cos", "/sin")) for o in outside)
    assert not any(o.endswith(("/cos", "/sin")) for o in core)


def test_a_keye_vl2_step_names_its_indexer_choice_and_core_and_counts_the_pairs(
        step_of):
    """``dsa_index`` holds the indexer's projections and index scores,
    ``dsa_topk`` the choice alone (no matmul) and ``dsa_core`` the
    attention function's call (scores and softmax, NOT the q/k/v
    projections or the rotation), each inside ``attention``; with every
    block recomputed in the backward pass no recomputed op is the
    indexer's or the choice's (the selection is kept by name); the step's
    metrics carry ``dsa.selected_pairs`` and ``dsa.causal_pairs``."""
    step = step_of("keye_vl2")
    assert {"dsa.selected_pairs", "dsa.causal_pairs"} <= set(
        step["loss_fn"].device_counters)
    counters, m = step["counters"], step["map"]
    # a replica's row of 16 positions (8 rows over 8 devices), two layers:
    # 4 keys a query from position 3 on
    assert int(counters["dsa.causal_pairs"]) == 2 * 16 * 17 // 2
    assert int(counters["dsa.selected_pairs"]) == 2 * (4 * 5 // 2 + 12 * 4)
    ops = paths(m)
    for scope in (scopes.DSA_INDEX, scopes.DSA_TOPK, scopes.DSA_CORE):
        assert scope in scopes.SCOPES
        # (a reduction's own scalar computation inside a loop's body is
        # named from the body's scope on: no device op of its own)
        inside = [o for o in ops if scope in components(o)
                  and scopes.BLOCKS in components(o)]
        assert inside and all(
            components(o).index(scopes.BLOCKS)
            < components(o).index(scopes.ATTENTION)
            < components(o).index(scope) for o in inside)
    index = [o for o in ops if scopes.DSA_INDEX in components(o)]
    topk = [o for o in ops if scopes.DSA_TOPK in components(o)]
    core = [o for o in ops if scopes.DSA_CORE in components(o)]
    assert any("dot_general" in o for o in index)
    assert any(o.endswith(("/cos", "/sin")) for o in index)
    assert not any("dot_general" in o for o in topk)
    assert any("dot_general" in o for o in core)
    assert not any(o.endswith(("/cos", "/sin")) for o in core)
    # forward only and once: nothing of the indexer or the choice is
    # differentiated or recomputed; the core is both
    assert any("rematted_computation" in o for o in ops)
    assert not any("rematted_computation" in o or "transpose(" in o
                   for o in index + topk)
    assert any("transpose(" in o for o in core)
    # the indexer is no part of the core under its neutral name either
    assert not any(scopes.ATTN_CORE in components(o) for o in index + topk)


def test_an_lfm2_step_names_its_conv_mixers_and_their_cores(step_of):
    """``conv_mix`` holds a gated short convolution whole (both
    projections' matmuls), ``conv_core`` what lies between them (the gates
    and the 3-tap convolution: no matmul), each inside ``attention``, so
    ``attn_ms_per_step`` stays the mixers' total; the grouped core of the
    one attention layer is under ``dsa_core``; with every block recomputed
    the core is forward, backward and recomputed."""
    step = step_of("lfm2")
    assert step["loss_fn"].device_counters == (
        "moe.max_expert_pairs", "moe.routed_pairs", "moe.chosen_pairs")
    counters, m = step["counters"], step["map"]
    # a replica's row of 16 positions, four routed layers, top-3
    assert int(counters["moe.chosen_pairs"]) == 4 * 16 * 3
    ops = paths(m)
    for scope in (scopes.CONV_MIX, scopes.CONV_CORE):
        assert scope in scopes.SCOPES
        inside = [o for o in ops if scope in components(o)
                  and scopes.BLOCKS in components(o)]
        assert inside and all(
            components(o).index(scopes.BLOCKS)
            < components(o).index(scopes.ATTENTION)
            < components(o).index(scopes.CONV_MIX)
            <= components(o).index(scope) for o in inside)
    mix = [o for o in ops if scopes.CONV_MIX in components(o)]
    core = [o for o in ops if scopes.CONV_CORE in components(o)]
    assert any("dot_general" in o for o in mix)
    assert not any("dot_general" in o for o in core)
    assert any(o.endswith("/mul") for o in core)
    assert any("transpose(" in o for o in core)
    assert any("rematted_computation" in o for o in core)
    grouped = [o for o in ops if scopes.DSA_CORE in components(o)]
    assert any("dot_general" in o for o in grouped)
    assert not any(scopes.CONV_MIX in components(o) for o in grouped)
    # a convolution has no softmax core
    assert not any(scopes.ATTN_CORE in components(o) for o in mix)


def test_partitioned_storage_gathers_under_the_params_scope():
    runner, batch, _ = build_lm(S.PartitionedAR())
    runner.run(batch)
    m = telemetry.scope_map(STEP)
    gathers = [o for n in under(m, scopes.PARAMS) for o in m[n]]
    assert any("all_gather" in o for o in gathers), gathers[:5]
    assert not under(m, scopes.PARAMS, in_pass="fwd")


def test_map_names_are_the_running_executables_instructions():
    """A fresh compile with the cache bypassed names the instructions the
    jit's own executable has: metadata is no part of a name."""
    runner, batch, _ = build_lm()
    runner.run(batch)
    dstep = runner.distributed_step
    ran = dstep._step_fn.lower(
        runner.state, {}, runner.remapper.remap_feed(batch)).compile()
    assert set(scopes.parse_scope_map(ran.as_text())) == \
        set(telemetry.scope_map(STEP))
    # eval and the fused program are registered beside the step
    assert {"jit_local_eval", "jit_local_multi"} <= set(
        telemetry.registered_programs())
    ev = telemetry.scope_map("jit_local_eval")
    assert under(ev, scopes.LOSS) and not under(ev, "jvp(loss)")
    assert not under(ev, scopes.LOSS, in_pass="bwd")
    # the registry does not keep a runner alive
    assert telemetry.scope_map("jit_no_such_program") is None


def test_the_step_account_is_read_off_the_maps_one_compile():
    """``step_account`` after ``scope_map`` and the reverse compile
    nothing more; its memory is the compiler's analysis of the program
    that runs, field for field; a new registration drops both; a name
    nobody registered gives None."""
    tel.configure("1")
    runner, batch, _ = build_lm()
    runner.run(batch)
    compiles = lambda: tel.counters().get("compile.backend_compiles", 0)  # noqa: E731
    before = compiles()
    m = telemetry.scope_map(STEP)
    assert compiles() == before + 1
    account = telemetry.step_account()       # the step's module by default
    assert compiles() == before + 1
    assert account["module"] == STEP and account["instructions"] is m
    stats = runner.distributed_step._step_fn.lower(
        runner.state, {}, runner.remapper.remap_feed(batch)
    ).compile().memory_analysis()
    assert account["memory"] == {
        "temp_bytes": stats.temp_size_in_bytes,
        "argument_bytes": stats.argument_size_in_bytes,
        "output_bytes": stats.output_size_in_bytes,
        "alias_bytes": stats.alias_size_in_bytes,
        "code_bytes": stats.generated_code_size_in_bytes,
        "peak_bytes": stats.peak_memory_in_bytes}
    assert account["memory"]["temp_bytes"] > 0
    assert account["memory"]["argument_bytes"] > 0
    # the reverse order, on a program nobody asked about yet
    before = compiles()
    ev = telemetry.step_account("jit_local_eval")
    assert compiles() == before + 1
    assert telemetry.scope_map("jit_local_eval") is ev["instructions"]
    assert compiles() == before + 1
    # a new registration of the name drops the account with the map
    scopes.register_program(STEP, runner._lower_step)
    assert STEP not in scopes._accounts
    again = telemetry.step_account(STEP)
    # (JAX answers the same lowering under the same options from memory)
    assert again["instructions"] is not m and again["instructions"] == m
    assert again["memory"] == account["memory"]
    assert telemetry.step_account("jit_no_such_program") is None
    # a backend without an analysis gives None for the memory, no guess
    import types
    assert scopes._memory(types.SimpleNamespace(
        memory_analysis=lambda: None)) is None


def test_map_survives_an_executable_from_another_trees_cache(tmp_path,
                                                             monkeypatch):
    """The hazard: metadata is no part of the persistent cache's key, so a
    process loads the executable an EARLIER tree compiled, without this
    tree's scopes; and ``lowered.compile()`` of what the jit ran is
    answered from memory with that same executable. The map must come
    from a compile of its own."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        # "the parent": the same program traced without any scope
        with monkeypatch.context() as mp:
            mp.setattr(scopes, "scope",
                       lambda name: contextlib.nullcontext())
            runner, batch, _ = build_lm()
            loss = runner.run(batch)["loss"]
        entries = sorted(p.name for p in tmp_path.glob(STEP + "-*-cache"))
        assert len(entries) == 1
        autodist_tpu.reset()
        del runner
        jax.clear_caches()
        # this tree: its jit loads the parent's executable...
        runner, batch, _ = build_lm()
        assert runner.run(batch)["loss"] == loss
        stale = runner.distributed_step._step_fn.lower(
            runner.state, {}, runner.remapper.remap_feed(batch)).compile()
        assert "optimizer/" not in stale.as_text()
        # ...and the map still reads this tree's scopes, writing nothing
        m = telemetry.scope_map(STEP)
        assert under(m, scopes.OPTIMIZER) and under(m, scopes.LEAN_HEAD_BWD)
        assert set(m) == set(scopes.parse_scope_map(stale.as_text()))
        assert sorted(p.name for p in tmp_path.glob(
            STEP + "-*-cache")) == entries
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_parse_scope_map_text():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.3 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/jvp(loss)/mul" source_file="a.py" source_line=3}
  ROOT %add.1 = f32[8]{0} add(%mul.3, %p0), metadata={op_name="jit(f)/optimizer/add"}
}

%body.7 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  ROOT %dot.9 = f32[8]{0} dot(%arg), metadata={op_name="jit(f)/transpose(jvp(loss))/lean_head_bwd/while/body/dot_general"}
}

ENTRY %main.4 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %copy.2 = f32[8]{0} copy(%a)
  %while.5 = (s32[], f32[8]{0}) while(%a), condition=%cond.6, body=%body.7, metadata={op_name="jit(f)/transpose(jvp(loss))/lean_head_bwd/while"}
  ROOT %fusion.1 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/optimizer/add"}
}
"""
    m = scopes.parse_scope_map(text)
    assert m["fusion.1"] == ["jit(f)/optimizer/add", "jit(f)/jvp(loss)/mul",
                             "jit(f)/optimizer/add"]
    assert m["copy.2"] == [] and m["a"] == ["a"]
    assert m["dot.9"] == ["jit(f)/transpose(jvp(loss))/lean_head_bwd/"
                          "while/body/dot_general"]
    assert m["while.5"] == ["jit(f)/transpose(jvp(loss))/lean_head_bwd/while"]
    # names printed without the percent sign parse the same
    assert scopes.parse_scope_map(text.replace("%", "")) == m


# ------------------------------------------- (b) nothing extra untraced


@pytest.mark.parametrize("mode", ["0", "1"])
def test_a_fit_lowers_and_compiles_nothing_extra(mode, monkeypatch):
    lowered, compiled = [], []
    for name in ("_lower_step", "_lower_eval", "_lower_fused"):
        orig = getattr(Runner, name)
        monkeypatch.setattr(
            Runner, name,
            lambda self, _o=orig, _n=name: lowered.append(_n) or _o(self))
    orig_compiled = scopes.compiled
    monkeypatch.setattr(scopes, "compiled",
                        lambda n: compiled.append(n) or orig_compiled(n))
    tel.configure(mode)
    runner, batch, _ = build_lm()
    runner.fit([batch] * 4)
    dstep = runner.distributed_step
    assert lowered == [] and compiled == []
    assert dstep._step_fn._cache_size() == 1
    assert STEP not in scopes._accounts  # nobody asked: no account
    # asking is what costs: one lowering, one compile, then kept, for the
    # map and the account together
    assert telemetry.scope_map(STEP) and telemetry.scope_map(STEP)
    assert telemetry.step_account(STEP)["memory"]
    assert lowered == ["_lower_step"] and compiled == [STEP]
    assert dstep._step_fn._cache_size() == 1  # the jit's cache is untouched
    assert jax.config.jax_enable_compilation_cache  # and the flag restored


# ----------------------------------------------------- (c) the host spans


def by_name(events):
    out = {}
    for e in events:
        out.setdefault(e.name, []).append(e)
    return out


@pytest.mark.parametrize("fit_kw", [{}, {"metrics_every": 3}])
def test_every_step_has_its_spans_with_its_index(fit_kw):
    tel.configure("1")
    runner, batch, _ = build_lm()
    runner.run(batch)  # compile outside the recorded fit
    tel.get_recorder().clear()
    n = 6
    seen = []
    runner.fit(DevicePrefetcher(iter([batch] * n), runner, depth=2),
               callbacks=[lambda i, m: seen.append(i)], **fit_kw)
    assert seen == list(range(n))
    spans = by_name(tel.get_recorder().events())
    per_group = fit_kw.get("metrics_every", 1)
    for name in PER_STEP:
        want = n
        if name == "runner.next_batch":
            want = n + 1            # the one that finds the source empty
        if name == "runner.callbacks" and per_group > 1:
            want = n                # one per materialized handle
        assert len(spans[name]) == want, (name, len(spans[name]))
        steps = sorted(e.args["step"] for e in spans[name])
        # steps 1..n of this runner (step 0 ran before the fit)
        assert steps[:n] == list(range(1, n + 1)), (name, steps)
    assert len(spans["prefetch.place"]) == n
    assert sorted(e.args["item"] for e in spans["prefetch.place"]) == \
        list(range(n))
    # placements happen inside the loop's next(), the polls after a
    # dispatch inside runner.control
    nb = {e.span_id for e in spans["runner.next_batch"]}
    assert all(e.parent_id in nb for e in spans["prefetch.place"])
    disp = {e.span_id for e in spans["runner.dispatch"]}
    assert all(e.parent_id in disp for e in spans["runner.control"])
    # the readback is tiled by its two children: by ORDER and NESTING in
    # every readback, and by time in the fit's tightest one. A gap the code
    # leaves (work in the readback outside both children) is in every
    # readback; a pause the host puts into one (1.2 ms of 8.2 under six
    # xdist workers) is not, and would have to fall into all six to show
    # here, so the bound on CPU wall time is one a loaded host does not break
    own = []
    for rb in spans["runner.readback"]:
        kids = sorted((e for name in ("runner.wait_device", "runner.fetch")
                       for e in spans[name] if e.parent_id == rb.span_id),
                      key=lambda e: e.ts_ns)
        assert [k.name for k in kids] == ["runner.wait_device",
                                          "runner.fetch"]
        assert all(k.args["step"] == rb.args["step"] for k in kids)
        assert kids[0].ts_ns >= rb.ts_ns
        assert kids[0].ts_ns + kids[0].dur_ns <= kids[1].ts_ns
        assert kids[1].ts_ns + kids[1].dur_ns <= rb.ts_ns + rb.dur_ns
        own.append((rb.dur_ns - sum(k.dur_ns for k in kids), rb.dur_ns))
    assert len(own) == n
    assert any(gap < max(0.1 * dur, 200_000) for gap, dur in own), own


def test_fused_supersteps_carry_their_first_microstep():
    tel.configure("1")
    runner, batch, _ = build_lm()
    tel.get_recorder().clear()
    runner.fit([batch] * 8, fuse_steps=4, metrics_every=2)
    spans = by_name(tel.get_recorder().events())
    for name in ("runner.dispatch", "dstep.dispatch", "runner.feed",
                 "runner.control", "runner.readback", "runner.wait_device",
                 "runner.fetch", "runner.callbacks"):
        assert sorted(e.args["step"] for e in spans[name]) == [0, 4], name
    # the fused program is inspectable at the k that ran
    fused = telemetry.scope_map("jit_local_multi")
    assert under(fused, "while", in_pass="bwd")
    assert under(fused, scopes.OPTIMIZER)


# ------------------------------------------ (d) spans in a profiler trace


def test_spans_are_annotations_in_a_profiler_session(tmp_path):
    from jax.profiler import ProfileData
    tel.configure("1")
    runner, batch, _ = build_lm()
    runner.run(batch)
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner.fit([batch] * 2)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if re.match(r"^(runner|dstep|prefetch)\.", ev.name):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    for name in ("runner.fit",) + PER_STEP:
        assert name in found, (name, sorted(found))
    # scalar args ride along: a step's annotations share its index
    assert sorted(s["step"] for s in found["runner.dispatch"]) == [1, 2]
    assert sorted(s["step"] for s in found["runner.wait_device"]) == [1, 2]


def test_the_first_step_profile_holds_the_first_step_span(tmp_path,
                                                          monkeypatch):
    """``AutoDist(tracing=True)`` profiles the first step: the session
    starts before ``setup.first_step`` is entered, so the span lies in the
    profiler's own file with the step's spans and the device's ops."""
    from jax.profiler import ProfileData
    from autodist_tpu import const
    monkeypatch.setattr(const, "DEFAULT_TRACE_DIR", str(tmp_path))
    tel.configure("1")
    cfg = lm.LMConfig.tiny()
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=8)
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(), tracing=True)
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    runner.run(batch)
    runner.run(batch)  # only the first step is profiled
    path = glob.glob(str(tmp_path / "*/plugins/profile/*/*.xplane.pb"))[0]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"setup.first_step", "runner.dispatch", "dstep.dispatch"} <= names


def test_untraced_spans_open_no_annotation(monkeypatch):
    made = []
    monkeypatch.setattr(tel, "_trace_annotation",
                        lambda name, args: made.append(name) or 1 / 0)
    tel.configure("0")
    runner, batch, _ = build_lm()
    runner.fit([batch] * 2)
    assert made == []


# ------------------------------------------------------------ (e) goodput


def test_goodput_charges_the_wait_to_compute():
    from autodist_tpu.telemetry import goodput
    assert goodput.classify("runner.wait_device", "runner") == "compute"
    assert goodput.classify("runner.fetch", "runner") == "readback"
    assert goodput.classify("runner.readback", "runner") == "readback"
    assert goodput.classify("runner.next_batch", "runner") == "host_input"
    assert goodput.classify("runner.callbacks", "runner") == "host_loop"
    assert goodput.classify("runner.control", "runner") == "host_loop"
    assert "host_loop" in goodput.BUCKETS
    tel.configure("1")
    runner, batch, _ = build_lm()
    runner.run(batch)
    tel.get_recorder().clear()
    runner.fit(DevicePrefetcher(iter([batch] * 8), runner, depth=2),
               callbacks=[lambda i, m: None])
    report = runner.goodput_report()
    assert abs(report.coverage - 1.0) < 0.02
    spans = by_name(tel.get_recorder().events())
    waited = sum(e.dur_ns for e in spans["runner.wait_device"]) / 1e9
    fetched = sum(e.dur_ns for e in spans["runner.fetch"]) / 1e9
    # the wait is in compute, the copy (and little else) in readback
    assert report.buckets["compute"] >= waited
    assert fetched <= report.buckets["readback"] < fetched + 0.2 * waited \
        + 1e-3
    assert report.buckets["host_loop"] > 0
    assert report.buckets["host_input"] > 0
    # runner.fit's own time is all that is left unnamed
    fit = spans["runner.fit"][0]
    kids = sum(e.dur_ns for e in tel.get_recorder().events()
               if e.parent_id == fit.span_id)
    assert report.buckets["other"] == pytest.approx(
        (fit.dur_ns - kids) / 1e9, abs=1e-4)
    assert "host_loop" in report.format_table()


# ---------------------------------------------------- the decode engine


def test_decode_results_carry_timestamps_and_spans_carry_request_ids():
    from autodist_tpu.serving.decode import DecodeConfig, DecodeEngine
    tel.configure("1")
    runner, batch, cfg = build_lm()
    runner.run(batch)
    engine = DecodeEngine(runner, lm.make_decode_setup(cfg),
                          DecodeConfig(slots=8, max_new_tokens=4,
                                       prefill_len=8))
    try:
        engine.warmup()
        tel.get_recorder().clear()
        rng = np.random.RandomState(0)
        futures = [engine.submit(rng.randint(0, cfg.vocab_size, (3 + i,)),
                                 max_new_tokens=1 + i) for i in range(3)]
        results = [f.result(timeout=120) for f in futures]
    finally:
        engine.close()
    for i, r in enumerate(results):
        assert len(r["tokens"]) == 1 + i
        assert r["t_submit"] <= r["t_admitted"] <= r["t_first_token"] \
            <= r["t_done"]
    # one prefill emits its group's first tokens at one instant
    spans = by_name(tel.get_recorder().events())
    prefill_rids = [e.args["rids"] for e in spans["serve.prefill"]]
    rids = sorted(r for group in prefill_rids for r in group)
    assert len(rids) == 3 and len(set(rids)) == 3
    # every admission group is copied; it is inserted unless its prefill
    # alone satisfied all of it (the first request's cap is one token)
    assert [e.args["rids"] for e in spans["serve.admit_copy"]] == \
        prefill_rids
    inserted = [e.args["rids"] for e in spans["serve.insert"]]
    assert inserted and all(g in prefill_rids for g in inserted)
    # the prefill span holds the bucket's dispatch and readback
    pre = {e.span_id for e in spans["serve.prefill"]}
    assert any(e.parent_id in pre for e in spans["serve.readback"])
    # every decode step names the requests it advanced
    steps = spans["serve.decode_step"]
    assert steps and all(set(e.args["rids"]) <= set(rids) for e in steps)
    assert all(len(e.args["rids"]) == e.args["live"] for e in steps)
    # the three serving programs are inspectable under their scopes
    assert under(telemetry.scope_map("jit_local_decode"), scopes.DECODE)
    assert under(telemetry.scope_map("jit_local_decode"), scopes.DECODE,
                 scopes.BLOCKS, scopes.ATTENTION)
    assert under(telemetry.scope_map("jit__insert"), scopes.INSERT)
    assert under(telemetry.scope_map("jit_local_predict"), scopes.PREFILL)


# ------------------------------------------------- (f) the set-up account

PHASES = {"setup.build": None, "setup.capture": "setup.build",
          "setup.strategy": "setup.build",
          "setup.compile_strategy": "setup.build",
          "setup.mesh": "setup.build", "setup.transform": "setup.build",
          "setup.init": None, "setup.init_state": "setup.init",
          "setup.first_step": None}
TRACE_TIME_GAUGES = ("lean_head.chunks", "lean_head.chunk_width",
                     "lean_head.dead_cols", "attention.flash_layers",
                     "attention.kda_kernel_layers", "model.remat_blocks",
                     "model.kept_expert_layers", "model.kept_expert_bytes",
                     "model.kept_dense_layers", "model.kept_dense_bytes",
                     "model.kept_sublayer_out_layers",
                     "model.kept_sublayer_out_bytes",
                     "model.kept_shared_layers", "model.kept_shared_bytes",
                     "model.kept_mixer_in_layers",
                     "model.kept_mixer_in_bytes", "model.loop_steps",
                     "model.block_applications",
                     "model.kept_core_bytes")


@pytest.fixture(scope="module")
def set_up():
    """A traced build -> init -> two steps of the tiny LM, the recorder
    cleared as a benchmark clears it before its window: the account and
    the window's state after it, as plain data."""
    tel.configure("1")
    try:
        runner, batch, _ = build_lm()
        runner.run(batch)
        runner.run(batch)
        before = telemetry.setup_account()
        did = [e for e in tel.get_recorder().events() if e.cat == "jax"]
        tel.get_recorder().clear()
        yield {"before": before, "account": telemetry.setup_account(),
               "jax": did,
               "events": tel.get_recorder().events(),
               "gauges": tel.gauges(),
               "first_step_s": runner.step_stats()["first_step_s"]}
    finally:
        tel.configure(None)
        autodist_tpu.reset()



def inside(child, parent):
    return (parent["start_ns"] <= child["start_ns"]
            and child["end_ns"] <= parent["end_ns"])


def test_set_up_yields_every_phase_once_children_inside_parents(set_up):
    phases = set_up["account"]["phases"]
    assert sorted(p["name"] for p in phases) == sorted(PHASES)
    by = {p["name"]: p for p in phases}
    for name, parent in PHASES.items():
        if parent is None:
            assert by[name]["parent"] == 0, name
        else:
            assert by[name]["parent"] == by[parent]["id"], name
            assert inside(by[name], by[parent]), name
        assert set(by[name]["args"]) >= {"hbm_in_use", "hbm_peak"}, name
    # the three roots follow each other and do not overlap
    build, init, first = (by[n] for n in ("setup.build", "setup.init",
                                          "setup.first_step"))
    assert build["end_ns"] <= init["start_ns"]
    assert init["end_ns"] <= first["start_ns"]
    # one pair of clock readings serves the span and first_step_s
    assert set_up["first_step_s"] == round(
        (first["end_ns"] - first["start_ns"]) / 1e9, 6)


def test_what_jax_did_lies_under_the_phase_that_was_live(set_up):
    by = {p["name"]: p for p in set_up["account"]["phases"]}
    assert {e.name for e in set_up["jax"]} <= {
        "jax.trace", "jax.lower", "jax.backend_compile", "jax.cache_load"}
    # what the model's own init compiled before the build names no phase
    did = [e for e in set_up["jax"] if "phase" in (e.args or {})]
    assert all(e.ts_ns + e.dur_ns <= by["setup.build"]["start_ns"]
               for e in set_up["jax"] if e not in did)
    for e in did:
        phase = by[e.args["phase"]]
        assert phase["start_ns"] <= e.ts_ns, e
        assert e.ts_ns + e.dur_ns <= phase["end_ns"], e
    # the step program was traced, lowered and compiled (or loaded) in
    # the first step, by name
    first = [e for e in did if e.args["phase"] == "setup.first_step"]
    for name in ("jax.trace", "jax.lower"):
        assert any(e.name == name and "local_step" in e.args["fun_name"]
                   for e in first), name
    args = by["setup.first_step"]["args"]
    assert "jit(local_step)" in args["programs"]
    # the jits traced inside the step's trace are counted once: the
    # phase's seconds are the outermost spans', not all of them
    traced = [e for e in first if e.name in ("jax.trace", "jax.lower")]
    step = [e for e in traced if e.args["fun_name"] in ("local_step",
                                                       "jit(local_step)")]
    assert len(step) == 2 < len(traced)
    assert sum(e.dur_ns for e in step) / 1e9 <= args["trace_lower_s"] \
        < sum(e.dur_ns for e in traced) / 1e9
    assert args["backend_compile_s"] + args["cache_load_s"] > 0
    # a phase holds what JAX did beneath it, not beneath one inside it
    assert by["setup.init"]["args"]["programs"] == {}
    assert by["setup.build"]["args"]["trace_lower_s"] == 0.0
    c = set_up["account"]["counters"]
    assert c["compile.traces"] > 0
    assert c["compile.backend_compiles"] + c.get("compile.cache_hits", 0) > 0
    assert c["dstep.dispatches"] == 1  # as they stood when set-up ended


def test_clear_keeps_the_account_and_drops_the_windows_state(set_up):
    assert set_up["account"] == set_up["before"]
    assert set_up["events"] == [] and set_up["gauges"] == {}


@pytest.mark.parametrize("gauge", TRACE_TIME_GAUGES)
def test_a_gauge_set_at_trace_time_is_in_the_account(set_up, gauge):
    assert gauge in set_up["account"]["gauges"]
    if gauge in ("lean_head.chunks", "lean_head.chunk_width"):
        assert set_up["account"]["gauges"][gauge] > 0


@pytest.mark.parametrize("layers_that_fit", [0, 1, 2])
def test_the_kept_expert_layers_are_gauges_of_the_traced_loss(
        monkeypatch, layers_that_fit):
    """``model.kept_expert_layers`` / ``model.kept_expert_bytes`` beside
    ``model.remat_blocks``, set as the loss is traced: a tiny model that
    holds 2 of its 8 experts in two routed layers, on a chip made so small
    that its blocks are recomputed and so many layers' products fit."""
    from tests.test_held_experts_kept import tiny_share
    cfg, params, _ = tiny_share()
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    a_layer = lm.held_expert_kept_bytes(4 * 16, (2, 32, 16), itemsize=4)
    assert a_layer == 2 * 4 * 64 * 2 * 16
    hbm = (12 * count + (layers_that_fit + 0.5) * a_layer) / (
        1 - lm.KEPT_EXPERTS_HBM_LEFT)
    monkeypatch.setattr(lm, "_chip_hbm_bytes", lambda: hbm)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=4)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.remat_blocks"] == 3
    assert gauges["model.kept_expert_layers"] == layers_that_fit
    assert gauges["model.kept_expert_bytes"] == layers_that_fit * a_layer


def build_linear(builder, ad=None):
    rng = np.random.RandomState(0)
    params = {"w": np.zeros((4, 2), np.float32)}
    batch = {"x": rng.randn(16, 4).astype(np.float32),
             "y": rng.randn(16, 2).astype(np.float32)}

    def loss_fn(p, b):
        return ((b["x"] @ p["w"] - b["y"]) ** 2).mean()

    ad = ad or autodist_tpu.AutoDist(strategy_builder=builder)
    runner = ad.build(loss_fn, optax.adam(0.1), params, batch)
    return ad, runner, params, batch


def test_reset_drops_the_account_and_a_second_build_starts_a_fresh_one():
    tel.configure("1")
    ad, runner, params, batch = build_linear(S.AllReduce())
    runner.init(params)
    runner.run(batch)
    first = telemetry.setup_account()
    assert {"setup.build", "setup.init", "setup.first_step"} <= {
        p["name"] for p in first["phases"]}
    build_linear(None, ad=ad)
    second = telemetry.setup_account()["phases"]
    assert [p["name"] for p in second if p["parent"] == 0] == ["setup.build"]
    assert second[-1]["id"] > max(p["id"] for p in first["phases"])
    telemetry.reset()
    assert telemetry.setup_account() == {"phases": [], "counters": {},
                                         "gauges": {}}


def test_what_the_lowering_decides_at_build_is_in_the_account():
    """``zero.hbm_saved_bytes`` is set when the step is built: gone from
    the window's gauges after ``clear()``, kept by the account."""
    tel.configure("1")
    build_linear(S.ZeroSharded())
    tel.get_recorder().clear()
    assert "zero.hbm_saved_bytes" not in tel.gauges()
    assert telemetry.setup_account()["gauges"]["zero.hbm_saved_bytes"] > 0


def test_a_recompile_after_set_up_is_the_windows_under_its_dispatch():
    tel.configure("1")
    _, runner, params, batch = build_linear(S.AllReduce())
    runner.init(params)
    runner.run(batch)
    account = telemetry.setup_account()
    tel.get_recorder().clear()
    runner.run(batch)                                   # step 1: nothing
    c = tel.counters()
    assert c["compile.backend_compiles"] + c["compile.cache_hits"] == 0
    runner.run({k: np.concatenate([v, v]) for k, v in batch.items()})
    c = tel.counters()                                  # step 2: new shape
    assert c["compile.backend_compiles"] + c["compile.cache_hits"] > 0
    events = tel.get_recorder().events()
    by_id = {e.span_id: e for e in events}
    did = [e for e in events if e.cat == "jax"]
    assert did and "phase" not in (did[-1].args or {})
    compiled = [e for e in did if e.name in ("jax.backend_compile",
                                             "jax.cache_load")]
    assert compiled and all(
        by_id[e.parent_id].name == "dstep.dispatch"
        and by_id[e.parent_id].args["step"] == 2 for e in compiled)
    assert telemetry.setup_account() == account          # not set-up's


def test_with_tracing_off_no_account_and_no_listener(monkeypatch):
    from jax import monitoring
    registered = []
    monkeypatch.setattr(tel, "_LISTENING", False)
    for name in ("register_event_duration_secs_listener",
                 "register_event_listener"):
        monkeypatch.setattr(monitoring, name,
                            lambda fn, _n=name: registered.append(_n))
    tel.configure("0")
    assert tel.span("setup.build", tel.SETUP_CAT) is tel._NOOP
    _, runner, params, batch = build_linear(S.AllReduce())
    runner.init(params)
    runner.run(batch)
    assert registered == []
    assert telemetry.setup_account() == {"phases": [], "counters": {},
                                         "gauges": {}}
    assert runner.step_stats()["first_step_s"] > 0      # the host's clock
    c = tel.counters()
    assert c["compile.traces"] == c["compile.backend_compiles"] == 0
    tel.configure("1")
    tel.configure("1")
    assert sorted(registered) == ["register_event_duration_secs_listener",
                                  "register_event_listener"]
