"""OLMoE on the normal training path (``LMConfig.olmoe_1b_7b``): the
config-driven block and the dropless top-k routing against the plain
float32 reference ``benchmark/reference/olmoe.py`` at a tiny size, and
lm1b's model held to what it was before the block became config-driven.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums only (sorted rows through a grouped matmul
against every expert on every token, masked; a fused rsqrt against a
divide by sqrt): 1e-5 relative to the largest entry is some twenty float32
roundings, and a dropped routed pair, a missing gate, a renormalised
top-k or a bf16 matmul misses it by orders of magnitude.
"""
import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu import telemetry
from autodist_tpu.models import layers, lm
from autodist_tpu.parallel import expert
from benchmark.reference import olmoe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-5
TOP_K = 2
SEQ = 16


def tiny_config(**kw):
    """d 64, 4 heads of 16, 8 experts top-2 of width 32, vocab 256, 2
    layers; eps, theta and both loss coefficients as published."""
    sizes = dict(vocab_size=256, d_model=64, num_heads=4, num_experts=8,
                 experts_per_token=TOP_K, mlp_dim=32)
    sizes.update(kw)
    return dataclasses.replace(
        lm.LMConfig.olmoe_1b_7b(num_layers=2, max_seq_len=SEQ), **sizes)


def close(got, want, rtol=RTOL):
    """Within rtol of the reference's largest entry, elementwise."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * max(np.abs(want).max(), 1e-30))


def flat(tree):
    """{"params/layer_0/moe/router": leaf, ...} of a parameter-shaped tree."""
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def batches(n, rows=8, vocab=256, seed=1):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, SEQ + 1)).astype(np.int32)}
            for _ in range(n)]


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=8, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


def reference_loss(params, batch, lb_coef, z_coef):
    tokens = batch["tokens"]
    logits, l_lb, l_z = ref.forward(params, tokens[:, :-1], TOP_K)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll) + lb_coef * l_lb + z_coef * l_z


# (load-balance, z) coefficients: the published ones, and both at 1 so that
# the router losses' own gradients are held to the reference, not hidden
# under the NLL's
COEFS = {"published": (0.01, 0.001), "router_losses_at_1": (1.0, 1.0)}


@pytest.fixture(scope="module", params=sorted(COEFS))
def loss_and_grads(request, tiny):
    cfg, _, params, _, batch = tiny
    lb_coef, z_coef = COEFS[request.param]
    loss_fn = lm.make_train_setup(
        dataclasses.replace(cfg, router_aux_loss_coef=lb_coef,
                            router_z_loss_coef=z_coef),
        seq_len=SEQ, batch_size=8, seed=0)[0]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, lb_coef=lb_coef, z_coef=z_coef)))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


LEAVES = sorted(
    ["embed/embedding", "final_ln/scale", "lm_head/kernel"]
    + ["layer_%d/%s" % (i, leaf) for i in range(2) for leaf in (
        "RMSNorm_0/scale", "RMSNorm_1/scale",
        "MultiHeadAttention_0/query/kernel", "MultiHeadAttention_0/key/kernel",
        "MultiHeadAttention_0/value/kernel", "MultiHeadAttention_0/out/kernel",
        "MultiHeadAttention_0/q_norm/scale", "MultiHeadAttention_0/k_norm/scale",
        "moe/router", "moe/gate_proj", "moe/up_proj", "moe/down_proj")])


def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(apply_fn(params, ids), ref.logits_fn(params, ids, TOP_K))


def test_loss_matches_the_reference_with_both_router_losses(loss_and_grads):
    got, want, grads, _ = loss_and_grads
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    assert sorted(grads) == ["params/" + n for n in LEAVES]


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert np.abs(np.asarray(want["params/" + leaf])).max() > 0
    close(got["params/" + leaf], want["params/" + leaf])


@pytest.fixture(scope="module")
def flash_and_default(tiny):
    """(loss, gradient leaves) of the tiny model with the attention core
    through the flash kernel (interpreted here) and through XLA."""
    cfg, _, params, _, batch = tiny
    got = {}
    for mode in ("flash", "default"):
        loss_fn, same = lm.make_train_setup(
            cfg, seq_len=SEQ, batch_size=8, seed=0, attention=mode)[:2]
        jax.tree_util.tree_map(np.testing.assert_array_equal, same, params)
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        got[mode] = (float(loss), flat(grads))
    return got


def test_flash_attention_gives_the_default_paths_loss(flash_and_default):
    flash, default = flash_and_default["flash"][0], \
        flash_and_default["default"][0]
    assert abs(flash - default) <= RTOL * abs(default)


@pytest.mark.parametrize("leaf", LEAVES)
def test_flash_attention_gives_the_default_paths_gradient(flash_and_default,
                                                          leaf):
    """RoPE, QK-norm and the routed feed-forward around the kernel: the
    same mathematics as the default path, every leaf to the file's 1e-5."""
    close(flash_and_default["flash"][1]["params/" + leaf],
          flash_and_default["default"][1]["params/" + leaf])


def test_without_recomputed_blocks_the_kept_name_lowers_to_nothing(
        tiny, monkeypatch):
    """``olmoe_train_1chip`` runs the flash kernel and recomputes no block:
    the names on the kernel's q, output and log-sum-exp
    (``ops/flash_attention.py:KEPT``) are three ``name`` equations a layer
    in the jaxpr of loss and gradient and nothing in the program lowered
    from it: the StableHLO text is the one traced with no name at all."""
    from autodist_tpu.ops import flash_attention as fa
    cfg, _, params, _, batch = tiny

    def traced():
        jax.clear_caches()      # (the kernel's forward rule is traced once)
        step = jax.jit(jax.value_and_grad(lm.make_train_setup(
            cfg, seq_len=SEQ, batch_size=8, seed=0, attention="flash")[0])
        ).trace(params, batch)
        # (a private function's symbol ends in a running number that the
        # jaxpr's equations shift: @argsort_98 / @argsort_97)
        return str(step.jaxpr), re.sub(r"(@\w+?)_\d+\b", r"\1",
                                       step.lower().as_text())

    jaxpr, lowered = traced()
    assert jaxpr.count("name[name=%s]" % fa.KEPT) == 3 * cfg.num_layers
    assert "remat" not in jaxpr and "checkpoint" not in jaxpr
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    unnamed, lowered_unnamed = traced()
    assert fa.KEPT not in unnamed
    assert lowered_unnamed == lowered


# the three cells' attention shapes (seq, head width) and the side of the
# rule each lands on where the backend is a TPU
CELL_SHAPES = {"olmoe_train_1chip": (2048, 128, True),
               "lm1b_train_1chip": (256, 64, False),
               "lm1b_train_4chip_ar": (256, 64, False)}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_auto_attention_decides_from_shapes_and_backend(cell):
    seq, head_dim, flash = CELL_SHAPES[cell]
    assert lm.auto_flash_attention(seq, head_dim, "tpu") is flash
    assert lm.auto_flash_attention(seq, head_dim, "cpu") is False
    assert lm.auto_flash_attention(1 << 20, head_dim, "cpu") is False


@pytest.mark.parametrize("seq, head_dim, flash", [
    (512, 128, True),     # the shortest sequence the sweep read
    (256, 128, False),
    (1000, 128, False),   # tiles of 8 rows: the parent kept these on XLA
    (1200, 128, False),   # tiles of 16
    (2000, 128, False),
    (1536, 128, True),    # three whole 512-row tiles
    (4096, 64, False),    # narrow heads at long sequences: no cell reads them
    (8192, 64, True),     # XLA's scores stop fitting: memory, as before
    (8200, 128, True),
])
def test_auto_attention_takes_the_kernel_only_at_tiles_the_chip_read(
        seq, head_dim, flash):
    assert lm.auto_flash_attention(seq, head_dim, "tpu") is flash
    assert lm.auto_flash_attention(seq, head_dim, "cpu") is False


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_gauge_says_how_many_layers_took_the_kernel(cell, monkeypatch):
    """``attention.flash_layers`` is set when the loss is traced, from what
    the rule decided. The rule's TPU branch is steered HERE (the backend
    probe answers "tpu", the kernels stay interpreted), at a cell's seq
    and head width on a narrow one-layer model."""
    from autodist_tpu.ops import pallas_mode
    seq, head_dim, flash = CELL_SHAPES[cell]
    cfg = tiny_config(d_model=2 * head_dim, num_heads=2, max_seq_len=seq,
                      num_layers=1)
    monkeypatch.setattr(pallas_mode, "interpret", lambda: True)
    for backend, layers in (("tpu", 1 if flash else 0), ("cpu", 0)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        loss_fn, params, batch, _ = lm.make_train_setup(
            cfg, seq_len=seq, batch_size=1, seed=0)
        telemetry.reset()
        jax.eval_shape(loss_fn, params, batch)
        assert telemetry.get_recorder().gauges()[
            "attention.flash_layers"] == layers


def routed_layer(rng, tokens, d, f, n_experts):
    w = lambda *shape: (rng.standard_normal(shape)  # noqa: E731
                        / np.sqrt(shape[-2])).astype(np.float32)
    return (rng.standard_normal((tokens, d)).astype(np.float32),
            {"router": w(d, n_experts), "gate_proj": w(n_experts, d, f),
             "up_proj": w(n_experts, d, f), "down_proj": w(n_experts, f, d)})


def run_both(x, m, top_k):
    with jax.default_matmul_precision("highest"):
        got = expert.dropless_moe_ffn(x, m["router"], m["gate_proj"],
                                      m["up_proj"], m["down_proj"], top_k)
        want = ref.routed_ffn(jnp.asarray(x).reshape(-1, x.shape[-1]), m,
                              top_k)
    return got, want


def test_no_pair_is_dropped_under_sixfold_imbalance():
    """Three quarters of the tokens are forced onto expert 0 (16 experts,
    top-2: 6 times the mean load). A capacity of 2 x the mean would drop
    two thirds of them; the output still equals every expert applied to
    every token and masked by the routing."""
    rng = np.random.RandomState(0)
    x, m = routed_layer(rng, 128, 32, 16, 16)
    x[:, 0] = np.where(np.arange(128) % 4 < 3, 1.0, -1.0)
    m["router"][0, 0] = 40.0
    chosen = jax.lax.top_k(jax.nn.softmax(x @ m["router"]), 2)[1]
    counts = np.bincount(np.asarray(chosen).ravel(), minlength=16)
    assert counts.max() / counts.mean() >= 6.0
    (out, lb, z, load), (want, want_lb, want_z) = run_both(x, m, 2)
    close(out, want)
    close(lb, want_lb)
    close(z, want_z)
    # the layer's load, as it reaches the device counters
    np.testing.assert_array_equal(load, counts)
    assert counts.max() == 96 and counts.sum() == 256


def test_top_8_of_64_at_a_tiny_width():
    rng = np.random.RandomState(1)
    x, m = routed_layer(rng, 96, 16, 8, 64)
    (out, lb, z, load), (want, want_lb, want_z) = run_both(
        x.reshape(2, 48, 16), m, 8)
    assert out.shape == (2, 48, 16) and lb.shape == z.shape == ()
    assert load.shape == (64,) and int(load.sum()) == 96 * 8
    close(out.reshape(96, 16), want)
    close(lb, want_lb)
    close(z, want_z)


def test_rope_is_the_rotate_half_form():
    rng = np.random.RandomState(2)
    x = rng.standard_normal((2, 6, 3, 8)).astype(np.float32)
    ang = np.arange(6)[:, None] * 10000.0 ** (-np.arange(0, 8, 2) / 8)
    ang = np.concatenate([ang, ang], -1)[None, :, None, :]
    want = x * np.cos(ang) + np.concatenate(
        [-x[..., 4:], x[..., :4]], -1) * np.sin(ang)
    np.testing.assert_allclose(layers.rope(jnp.asarray(x), jnp.arange(6),
                                           10000.0), want, atol=1e-5)
    # decode: each row at its own position is that position's row
    one = layers.rope(jnp.asarray(x[:, 3:4]), jnp.array([[3], [3]]), 10000.0)
    np.testing.assert_allclose(one, want[:, 3:4], atol=1e-5)


def test_qk_norm_spans_all_heads():
    rng = np.random.RandomState(3)
    x = rng.standard_normal((2, 5, 32)).astype(np.float32)
    mha = layers.MultiHeadAttention(num_heads=4, head_dim=8, use_bias=False,
                                    qk_norm_eps=1e-5)
    variables = mha.init(jax.random.PRNGKey(0), x)
    p = jax.tree_util.tree_map(np.asarray, variables["params"])
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (32,)
    assert "bias" not in p["query"] and "bias" not in p["out"]
    p["q_norm"]["scale"] = rng.standard_normal(32).astype(np.float32)
    _, (k, _) = mha.apply({"params": p}, x, return_kv=True)
    flat = (x @ p["key"]["kernel"].reshape(32, 32))
    want = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-5) \
        * p["k_norm"]["scale"]
    np.testing.assert_allclose(k.reshape(2, 5, 32), want, atol=1e-5)


def test_the_lean_head_takes_a_head_without_a_bias():
    cfg = tiny_config(vocab_size=32768, d_model=16, num_heads=2,
                      num_experts=4, mlp_dim=8)
    lean, params, batch, _ = lm.make_train_setup(cfg, seq_len=8, batch_size=2)
    plain = lm.make_train_setup(cfg, seq_len=8, batch_size=2,
                                lean_head=False)[0]
    assert "bias" not in params["params"]["lm_head"]
    from autodist_tpu.ops import xent
    calls = []
    real = xent.chunked_softmax_xent
    xent.chunked_softmax_xent = lambda *a: calls.append(1) or real(*a)
    try:
        got = jax.value_and_grad(lean)(params, batch)
    finally:
        xent.chunked_softmax_xent = real
    assert calls, "vocab 32768 must engage the lean head by its own rule"
    want = jax.value_and_grad(plain)(params, batch)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        close(a, b, rtol=1e-4)


def cpu_spec(n):
    from autodist_tpu.resource_spec import ResourceSpec
    return ResourceSpec.from_dict({"nodes": [{
        "address": "127.0.0.1", "chief": True, "cpus": list(range(n))}]})


def fit_two_steps(tiny, devices, pool):
    from autodist_tpu.data.prefetch import DevicePrefetcher
    _, loss_fn, params, _, batch = tiny
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                               resource_spec=cpu_spec(devices))
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    history = runner.fit(DevicePrefetcher(iter(pool), runner, depth=2),
                         metrics_every=2)
    return runner, history


@pytest.mark.parametrize("devices", [1, 4])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """Through AutoDist(AllReduce).build and Runner.fit; on 4 replicas each
    takes its router losses over its own 2 rows, and so does the
    reference's train_check (one block per replica)."""
    pool = batches(2, seed=5)
    _, params = tiny[0], tiny[2]
    with jax.default_matmul_precision("highest"):
        want = ref.train_check(
            functools.partial(ref.nll_sum, top_k=TOP_K), ref.batch_weight,
            params, pool[0], pool[1], jax.devices()[:devices])
        runner, history = fit_two_steps(tiny, devices, pool)
    got = [float(m["loss"]) for m in history]
    np.testing.assert_allclose(got, want, rtol=RTOL)
    assert len(runner.distributed_step.mesh.devices.flat) == devices


@pytest.mark.parametrize("tracing", [True, False])
def test_router_load_reaches_the_counters_only_under_telemetry(tiny, tracing):
    telemetry.configure("1" if tracing else "0")
    try:
        runner, history = fit_two_steps(tiny, 1, batches(2, seed=6))
        moved = {k: v for k, v in telemetry.counters().items()
                 if k.startswith("moe.")}
    finally:
        telemetry.configure(None)
    # the step reports them either way, as device scalars beside the loss
    pairs = 8 * SEQ * TOP_K * 2  # rows x seq x k x layers
    assert all(int(m["counters"]["moe.routed_pairs"]) == pairs
               for m in history)
    if not tracing:
        assert moved == {}
        return
    assert moved["moe.routed_pairs"] == 2 * pairs
    fullest = sum(int(m["counters"]["moe.max_expert_pairs"]) for m in history)
    assert moved["moe.max_expert_pairs"] == fullest >= 2 * pairs / 8


def test_the_step_holds_no_host_callback(tiny):
    """The router load leaves the step as outputs, not through the host."""
    runner, _ = fit_two_steps(tiny, 1, batches(1, seed=7))
    text = runner.lowered_text(batches(1, seed=7)[0])
    for marker in ("callback", "host", "infeed", "outfeed"):
        assert marker not in text.lower()


def test_prefill_and_cached_decode_equal_full_recompute(tiny):
    """RoPE by cursor, QK-norm and the routed feed-forward through the
    serving methods of the same model."""
    cfg, _, params, apply_fn, _ = tiny
    setup = lm.make_decode_setup(cfg)
    prompt = np.array([[5, 9, 17, 3]], np.int32)
    pre = setup.prefill_fn(params, {"tokens": jnp.asarray(prompt),
                                    "length": jnp.array([4])})
    dstate = dict(setup.init_dstate(1), k=pre["k"], v=pre["v"],
                  token=pre["next_token"], cursor=np.array([4], np.int32),
                  alive=np.ones(1, np.bool_))
    ids = list(prompt[0])
    for _ in range(4):
        want = int(np.argmax(np.asarray(
            apply_fn(params, np.asarray([ids], np.int32)))[0, -1]))
        assert int(dstate["token"][0]) == want
        ids.append(want)
        out = setup.decode_fn(params, dstate)
        dstate = dict(dstate, k=out["k"], v=out["v"], token=out["next_token"],
                      cursor=dstate["cursor"] + 1)


# ------------------------------------------------- lm1b is what it was


@pytest.fixture(scope="module")
def before():
    with open(os.path.join(HERE, "data", "lm_pins.json")) as f:
        return json.load(f)


def test_lm1b_parameter_tree_is_unchanged(before):
    shapes = jax.eval_shape(
        lambda: lm.TransformerLM(lm.LMConfig.lm1b()).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    tree = [["/".join(str(k.key) for k in path), list(a.shape), str(a.dtype)]
            for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert tree == before["lm1b_tree"]


def test_tiny_lm_loss_and_gradient_are_bit_equal_to_the_parent(before):
    loss_fn, params, batch, _ = lm.make_train_setup(
        lm.LMConfig.tiny(), seq_len=16, batch_size=4, seed=0)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    assert float(loss).hex() == before["tiny_loss"]
    assert float(norm).hex() == before["tiny_gradnorm"]


@pytest.mark.parametrize("field, value, says", [
    ("norm", "batchnorm", "norm"),
    ("experts_per_token", 0, "experts_per_token"),
    ("experts_per_token", 9, "experts_per_token")])
def test_an_architecture_the_block_cannot_build_is_refused(field, value, says):
    with pytest.raises(ValueError, match=says):
        cfg = dataclasses.replace(tiny_config(), **{field: value})
        lm.make_train_setup(cfg, seq_len=8, batch_size=2)


@pytest.mark.parametrize("preset, routed, rotary", [
    ("lm1b", False, False), ("tiny", False, False),
    ("olmoe_1b_7b", True, True)])
def test_one_field_decides_each_branch_of_the_block(preset, routed, rotary):
    """No second switch: experts make the feed-forward routed, a rope
    theta makes the positions rotary, and ``mlp_dim`` is the one
    feed-forward width either way."""
    cfg = getattr(lm.LMConfig, preset)()
    assert bool(cfg.num_experts) == routed
    assert (cfg.rope_theta is not None) == rotary
    assert cfg.mlp_dim > 0
    assert {f.name for f in dataclasses.fields(cfg)}.isdisjoint(
        {"ffn", "positions", "expert_dim"})


def test_the_published_preset_is_the_catalog_row():
    cfg = lm.LMConfig.olmoe_1b_7b()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_experts,
            cfg.experts_per_token, cfg.mlp_dim, cfg.vocab_size,
            cfg.max_seq_len, cfg.norm_eps, cfg.rope_theta) == (
        16, 2048, 16, 64, 8, 1024, 50304, 4096, 1e-5, 10000.0)
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale)
    with pytest.raises(ValueError, match="experts_per_token"):
        dataclasses.replace(cfg, experts_per_token=65)


# ------------------------- device counters, through any loss's lowering


def counting_problem(has_aux):
    rng = np.random.RandomState(0)
    params = {"w": rng.standard_normal((4, 2)).astype(np.float32)}
    batch = {"x": rng.standard_normal((16, 4)).astype(np.float32),
             "y": rng.standard_normal((16, 2)).astype(np.float32)}

    def loss_fn(p, b):
        err = b["x"] @ p["w"] - b["y"]
        telemetry.device_counters.add("test.rows", jnp.int32(b["x"].shape[0]))
        telemetry.device_counters.add("test.calls", jnp.int32(1))
        telemetry.device_counters.add("test.calls", jnp.int32(1))  # sums
        loss = jnp.mean(err ** 2)
        return (loss, jnp.mean(jnp.abs(err))) if has_aux else loss
    loss_fn.device_counters = ("test.rows", "test.calls")  # it says so
    return loss_fn, params, batch


@pytest.mark.parametrize("has_aux", [False, True])
@pytest.mark.parametrize("devices", [1, 4])
def test_a_loss_counts_on_the_device_beside_its_own_aux(has_aux, devices):
    loss_fn, params, batch = counting_problem(has_aux)
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                               resource_spec=cpu_spec(devices))
    runner = ad.build(loss_fn, optax.sgd(0.1), params, batch, has_aux=has_aux)
    runner.init(params)
    metrics = runner.run(batch)
    # integers take the largest over replicas: each sees its own rows
    assert int(metrics["counters"]["test.rows"]) == 16 // devices
    assert int(metrics["counters"]["test.calls"]) == 2
    assert ("aux" in metrics) == has_aux
    if has_aux:
        assert float(metrics["aux"]) > 0
    evaluated = runner.evaluate([batch])  # an evaluation counts nothing
    assert "counters" not in evaluated and ("aux" in evaluated) == has_aux


def test_fused_steps_count_every_microstep():
    from autodist_tpu.data.prefetch import DevicePrefetcher
    loss_fn, params, batch = counting_problem(False)
    telemetry.configure("1")
    try:
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                                   resource_spec=cpu_spec(2))
        runner = ad.build(loss_fn, optax.sgd(0.1), params, batch)
        runner.init(params)
        history = runner.fit(DevicePrefetcher(iter([batch] * 4), runner,
                                              depth=2, stack=2), fuse_steps=2)
        counted = telemetry.counters()
    finally:
        telemetry.configure(None)
    assert len(history) == 4
    assert counted["test.rows"] == 4 * 8 and counted["test.calls"] == 8


def test_outside_a_collection_adding_does_nothing():
    loss_fn, params, batch = counting_problem(False)
    assert float(jax.jit(jax.grad(loss_fn))(params, batch)["w"].sum()) != 0
    with telemetry.device_counters.collect(loss_fn.device_counters) as seen:
        loss_fn(params, batch)
    assert {k: int(v) for k, v in seen.items()} == {"test.rows": 16,
                                                    "test.calls": 2}


def test_a_loss_that_declares_nothing_counts_nothing_and_pays_nothing():
    """The lowering reads the declaration and traces nothing to find the
    counters: building traces a loss that counts as often as one that
    does not, and a loss without a declaration reports none, whatever it
    adds."""
    def build(declares):
        loss_fn, params, batch = counting_problem(False)
        traces = []

        def counting_traces(p, b):
            traces.append(1)
            return loss_fn(p, b)
        if declares:
            counting_traces.device_counters = loss_fn.device_counters
        ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                                   resource_spec=cpu_spec(1))
        runner = ad.build(counting_traces, optax.sgd(0.1), params, batch)
        at_build = len(traces)
        runner.init(params)
        metrics = runner.run(batch)
        autodist_tpu.reset()
        return at_build, metrics
    plain, metrics = build(False)
    assert "counters" not in metrics
    declared, metrics = build(True)
    assert int(metrics["counters"]["test.calls"]) == 2
    assert declared == plain


def test_a_declaration_the_loss_does_not_keep_is_refused():
    loss_fn, params, batch = counting_problem(False)
    loss_fn.device_counters = ("test.rows", "test.other")
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                               resource_spec=cpu_spec(1))
    runner = ad.build(loss_fn, optax.sgd(0.1), params, batch)
    runner.init(params)
    with pytest.raises(ValueError, match="test.other"):
        runner.run(batch)


# -------------- the TPU's compiler, without a chip (costs no chip time)


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to test
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_v5e_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


def test_the_routed_layer_compiles_for_a_v5e_at_the_published_widths(
        one_v5e_chip):
    """Forward and backward of one OLMoE feed-forward at the cell's shapes
    (8,192 tokens, d 2048, 64 experts of 1024, top-8) through XLA:TPU and
    Mosaic for a described chip: a tile the kernels' VMEM cannot hold, or a
    shape they refuse, fails here and not on the chip."""
    from autodist_tpu.ops import pallas_mode
    T, d, f, E, k = 8192, 2048, 1024, 64, 8

    def loss(x, router, w_gate, w_up, w_down):
        y, lb, z, _ = expert.dropless_moe_ffn(x, router, w_gate, w_up, w_down,
                                              k, jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32)) + lb + z
    shapes = [((T, d), jnp.bfloat16), ((d, E), jnp.float32),
              ((E, d, f), jnp.float32), ((E, d, f), jnp.float32),
              ((E, f, d), jnp.float32)]
    avals = [jax.ShapeDtypeStruct(s, t, sharding=one_v5e_chip)
             for s, t in shapes]
    with pallas_mode.compiling_for_tpu():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *avals).compile()
    text = compiled.as_text()
    # three grouped matmuls forward, six backward (gmm + tgmm each)
    assert text.count('custom_call_target="tpu_custom_call"') >= 9
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_flash_attention_compiles_for_a_v5e_at_the_cells_shape(one_v5e_chip):
    """The forward and the backward kernel at ``olmoe_train_1chip``'s
    attention shape ([4, 2048, 16, 128] bfloat16, causal) through Mosaic
    for a described chip: a tile ``_tiles`` chooses that the kernels' VMEM
    cannot hold fails here and not on the chip."""
    from autodist_tpu.ops import pallas_mode
    from autodist_tpu.ops.flash_attention import flash_attention
    x = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_v5e_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))
    with pallas_mode.compiling_for_tpu():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile()
    text = compiled.as_text()
    # flash_fwd and, since PR 34, ONE backward kernel, flash_bwd
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # no [B, H, S, S] tensor: out, lse and delta are all it keeps
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("seq, heads, kv_heads, head_dim, normed, window", [
    (16384, 32, 4, 128, True, 2048),     # trinity_mini_train_1chip
    (8192, 32, 4, 128, True, None),      # keye_vl2_train_1chip
    (16384, 28, 4, 128, False, 4096),    # smallthinker_train_1chip
    (4096, 16, 16, 128, False, None),    # ouro_2_6b_train_1chip
    (4096, 32, 32, 256, True, None)],    # heads of two lane tiles
    ids=["trinity_mini", "keye_vl2", "smallthinker", "ouro", "heads_of_256"])
def test_attn_pre_compiles_for_a_v5e_at_the_cells_shapes(
        one_v5e_chip, seq, heads, kv_heads, head_dim, normed, window):
    """``ops/attn_pre.py``'s two kernels in front of the flash kernels, the
    operands handed over heads-first, at every shape a cell runs them at,
    bfloat16, through Mosaic for a described chip: the lane roll, the 16-row
    loop over every head of a 512-token tile (all heads of a step fit VMEM
    twice over) and the ``[1, D]`` partial sums lower (``tests/
    test_attn_pre.py`` has their values, interpreted)."""
    from autodist_tpu.models import layers
    from autodist_tpu.ops import attn_pre, pallas_mode
    from autodist_tpu.ops.flash_attention import flash_attention
    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)
             for shape, dtype in (
                 ((1, seq, heads, head_dim), jnp.bfloat16),
                 ((1, seq, kv_heads, head_dim), jnp.bfloat16),
                 ((1, seq, kv_heads, head_dim), jnp.bfloat16),
                 ((head_dim,), jnp.float32), ((head_dim,), jnp.float32))]

    def loss(q, k, v, wq, wk):
        q, k = attn_pre.attn_pre(
            q, k, (wq, wk) if normed else None, 1e-5, jnp.arange(seq),
            layers.rope_inv_freq(head_dim, 1e4))
        return jnp.sum(flash_attention(
            q, k, v.transpose(0, 2, 1, 3), causal=True, window=window,
            heads_first=True).astype(jnp.float32))
    with pallas_mode.compiling_for_tpu():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *avals).compile().as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel, count in (("attn_pre_fwd", 2), ("attn_pre_bwd", 2),
                          ("flash_fwd", 1), ("flash_bwd", 1)):
        assert sum(kernel in call for call in calls) == count, (kernel, calls)


@pytest.mark.parametrize("shape, dtype, segments", [
    ((1, 8192, 16, 128), jnp.bfloat16, False),
    ((16, 1024, 16, 64), jnp.bfloat16, False),   # lm1b's heads
    ((4, 2048, 16, 128), jnp.bfloat16, True),
    ((1, 32768, 8, 128), jnp.bfloat16, True),
    ((2, 8192, 8, 256), jnp.float32, True),
    ((8, 512, 12, 64), jnp.bfloat16, True),      # BERT's padding mask
    # float32 (``LMConfig.dtype``'s default) at batch x heads of 64 and
    # more: 1,024-row tiles were refused here and only here (PR 28's review)
    ((4, 2048, 16, 64), jnp.float32, False),
    ((4, 8192, 16, 64), jnp.float32, False),
    ((4, 8192, 16, 128), jnp.float32, True),
    ((4, 2048, 16, 256), jnp.float32, True),
])
def test_the_kernels_tiles_fit_the_v5es_vmem(one_v5e_chip, shape, dtype,
                                             segments):
    """The forward kernel and the ONE backward kernel (PR 34: dq's float32
    accumulator of the whole head in VMEM beside the tiles, its
    ``vmem_limit_bytes`` from the shapes), causal, compile for the described
    chip at the one tile size ``_tiles`` gives, over head widths, dtypes,
    segment ids and batch x heads (the fit of a larger tile moved with all
    four: a device-less reading, PERF.md section 6, PR 28)."""
    from autodist_tpu.ops import flash_attention as fa, pallas_mode
    assert fa._tiles(shape[1], shape[1]) == (512, 512)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)
    seg = jax.ShapeDtypeStruct(shape[:2], jnp.int32,
                               sharding=one_v5e_chip) if segments else None

    def loss(q, k, v, seg):
        return jnp.sum(fa.flash_attention(q, k, v, True, seg)
                       .astype(jnp.float32))
    with pallas_mode.compiling_for_tpu():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x, seg).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd" in text


@pytest.mark.parametrize("window", [None, 4096],
                         ids=["every_earlier_key", "window_4096"])
def test_the_kernels_fit_the_v5es_vmem_at_16384_over_groups_of_7(
        one_v5e_chip, window):
    """``smallthinker_train_1chip``'s two launches, [1, 16384, 28, 128]
    bfloat16 over 4 K/V heads (groups of SEVEN query heads in the K/V head
    index), causal, with the window's tile table and without: the forward
    kernel and the ONE backward kernel (the head's float32 dq accumulator,
    8 MB at 16,384 x 128, stays under half of VMEM) compile for the
    described chip."""
    from autodist_tpu.ops import flash_attention as fa, pallas_mode
    assert fa._tiles(16384, 16384) == (512, 512)
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16,
                             sharding=one_v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                              sharding=one_v5e_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, window=window)
                       .astype(jnp.float32))
    with pallas_mode.compiling_for_tpu():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd" in text


def test_the_cells_whole_step_compiles_with_its_kernels(v5e_2x2, monkeypatch):
    """``olmoe_train_1chip``'s step as the benchmark builds it, compiled
    for the described chip with every pallas kernel as a Mosaic call
    (``benchmark/tools/aot_compile.py`` under ``compiling_for_tpu``): nine
    grouped expert matmuls and, since ``attention="auto"`` puts seq 2048 x
    heads of 128 on the kernel, attention's two (three until PR 34 made
    the backward one kernel). PR 25 lost a chip call
    to a tile that exhausted VMEM only inside the whole step. The rule's
    backend probe is steered here: JAX's default backend is the CPU."""
    import sys
    from autodist_tpu.ops import pallas_mode
    from autodist_tpu.parallel import mesh as mesh_lib
    path = list(sys.path)
    from benchmark.tools import aot_compile  # (it re-points sys.path[0])
    sys.path[:] = path
    # compile_cell replaces these two for good: put them back afterwards
    monkeypatch.setattr(mesh_lib, "ordered_devices", mesh_lib.ordered_devices)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = []
    mem = aot_compile.mem
    monkeypatch.setattr(aot_compile, "mem",
                        lambda c: (compiled.append(c), mem(c))[1])
    try:
        with pallas_mode.compiling_for_tpu():
            out = aot_compile.compile_cell("olmoe_train_1chip",
                                           v5e_2x2.devices)
    finally:
        autodist_tpu.reset()
    text = compiled[0].as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 11
    assert all(name in text for name in ("flash_fwd", "flash_bwd"))
    assert not any(name in text for name in ("flash_dq", "flash_dkdv"))
    # state + step scratch fit one chip's 16 GB with room to spare
    assert out["train_step"]["live_bytes_estimate"] < 14 << 30
    assert out["train_step"]["temp_size_in_bytes"] < 4426620928  # PR 27's


def test_four_chip_step_exchanges_gradients_beside_its_compute(
        v5e_2x2, monkeypatch):
    """The default ``AllReduce()`` step of a small LM (kernels of 1 and
    4 MiB, as lm1b's attention and MLP kernels are against PACK_BYTES)
    compiled for the four described chips (PR 26): the lowering gave the
    program its asynchronous-collective options; XLA:TPU wrapped
    gradient all-reduces with compute of the backward pass into
    ``async_collective_fusion``s (an all-reduce overlaps nowhere else)
    with backward compute still scheduled after the first of them; and
    the program holds one gradient all-reduce per group the metadata
    lists: the combiner merged the launch-bound ones and nothing else."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from autodist_tpu.parallel import collectives
    from autodist_tpu.parallel import mesh as mesh_lib
    from autodist_tpu.train_state import TrainState
    devices = list(v5e_2x2.devices)
    monkeypatch.setattr(mesh_lib, "ordered_devices",
                        lambda n=None, backend=None: devices)
    cfg = lm.LMConfig(vocab_size=1024, d_model=512, num_layers=2,
                      num_heads=8, mlp_dim=2048, max_seq_len=64,
                      dtype=jnp.bfloat16)
    loss_fn, params, batch, _ = lm.make_train_setup(cfg, seq_len=64,
                                                    batch_size=16)
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                               resource_spec=cpu_spec(4))
    opt = optax.adam(1e-3)
    dstep = ad.build(loss_fn, opt, params, batch).distributed_step
    meta = dstep.metadata
    autodist_tpu.reset()
    assert meta["async_collectives"] == sorted(
        collectives.ASYNC_COLLECTIVE_OPTIONS)
    groups = meta["grad_sync_groups"]
    assert {g["kind"] for g in groups} == {"var", "pack"}

    def sds(tree, pspec):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), a.dtype,
                sharding=NamedSharding(dstep.mesh, pspec)), tree)
    state = TrainState(
        step=sds(np.zeros((), np.int32), P()), params=sds(params, P()),
        opt_state=sds(jax.eval_shape(opt.init, params), P()),
        sync_state=sds(dstep._sync_state_init(), P(dstep.all_axes)))
    text = dstep._step_fn.lower(
        state, {}, sds(batch, P(dstep.batch_axes))).compile().as_text()

    # computation name -> its lines; the entry computation's in order
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if line[:1] not in (" ", "}") and "{" in line:
            name = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", line).group(1)
            cur = comps[name] = []
            entry = name if line.startswith("ENTRY") else entry
        elif cur is not None:
            cur.append(line)
    is_grad_sum = lambda l: " all-reduce(" in l and "grad_sync" in l  # noqa: E731
    fused = {n: ls for n, ls in comps.items()
             if n.startswith("async_collective_fusion")
             and any(is_grad_sum(l) for l in ls)}
    assert fused, "no gradient all-reduce rides a compute op"
    assert any("transpose(jvp(loss))" in l for ls in fused.values()
               for l in ls), "none of them rides the backward pass"
    calls = [i for i, l in enumerate(comps[entry])
             for m in [re.search(r"calls=%([\w.\-]+)", l)]
             if m and m.group(1) in fused]
    backward = [i for i, l in enumerate(comps[entry])
                if " fusion(" in l and "transpose(jvp(loss))" in l]
    assert calls and backward and min(calls) < max(backward)
    # one all-reduce of gradients per group: those left alone stand in
    # the entry computation, a fused one counts once per chain (a long
    # one is cut into steps over several fusions, all of one chain)
    alone = sum(is_grad_sum(l) for l in comps[entry])
    chains = {m.group(1) for ls in fused.values() for l in ls
              if is_grad_sum(l)
              for m in [re.search(r'chain_id="(\d+)"', l)] if m}
    assert alone + len(chains) == len(groups)


# ---- what the cell's loss_rtol refuses (benchmark/tools/loss_limit.py)


def bench_json(*parts):
    with open(os.path.join(HERE, "..", "benchmark", *parts)) as f:
        return [json.loads(line) for line in f] if parts[-1].endswith(
            ".jsonl") else json.load(f)


CELL = bench_json("workloads", "olmoe_train_1chip.json")
PLANTED = sorted(CELL["loss_rtol_refuses"] + CELL["loss_rtol_lets_through"])


@pytest.fixture(scope="module")
def tiny_readings():
    """Every fault planted into the float32 reference at the rehearsal's
    tiny size, read as the benchmark's driver reads a run."""
    from benchmark.tools import loss_limit
    config = bench_json("tests", "configs", "olmoe_tiny.json")
    traffic = dict(bench_json("traffic", "train_b4_s2048_every16.json"),
                   batch_per_chip=8, seq=16)
    rows = loss_limit.readings(config, traffic, 7, CELL["loss_rtol"])
    return {r["fault"]: r["reading"] for r in rows}


def test_the_cell_file_names_every_fault_the_tool_plants():
    from benchmark.tools import loss_limit
    assert PLANTED == sorted(loss_limit.faults())
    assert not set(CELL["loss_rtol_refuses"]) & set(
        CELL["loss_rtol_lets_through"])


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_moves_what_the_driver_reads(tiny_readings, fault):
    """The faults are really planted: each moves the reading by far more
    than the 1e-5 the float32 program and reference differ by (RTOL). At
    this size no expert can go over a capacity of 2 (16 experts, top-8:
    the mean load is half of the tokens), and rounding to bfloat16 is the
    smallest of the three precision controls."""
    reading = tiny_readings[fault]
    assert tiny_readings["sound"] == 0.0
    if fault == "over_capacity_2_dropped":
        assert reading == 0.0
    elif fault == "computed_in_bfloat16":
        assert RTOL < reading < min(
            tiny_readings["computed_in_float8_e4m3fn"],
            tiny_readings["computed_in_float8_e5m2"])
    else:
        assert reading > 10 * RTOL


def limit_record(fault):
    return [r["reading"] for r in bench_json("records", "pr25_loss_limit.jsonl")
            if r.get("fault") == fault]


def test_the_limit_is_three_times_the_worst_sound_run_on_the_chip():
    sound = limit_record("sound_on_the_chip")
    assert len(sound) >= 20
    assert 2.9 * max(sound) <= CELL["loss_rtol"] <= 3.1 * max(sound)


@pytest.mark.parametrize("fault", PLANTED)
def test_the_cell_file_says_what_the_record_shows(fault):
    """At the published widths (records/pr25_loss_limit.jsonl): a fault
    is REFUSED if every reading of it stays over the limit even with the
    program's own worst noise against it; everything else is let through
    and the cell file has to say so."""
    readings = limit_record(fault)
    noise = max(limit_record("sound_on_the_chip"))
    assert readings
    refused = min(readings) - noise > CELL["loss_rtol"]
    assert refused == (fault in CELL["loss_rtol_refuses"])
