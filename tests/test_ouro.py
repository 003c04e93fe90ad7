"""Ouro-2.6B on the normal training path (``LMConfig.ouro_2_6b``): one stack
of layers run four times over shared weights as one scanned body, a norm
on each sub-layer's output and between the passes, the head read after
every pass and an exit gate that weighs the four losses, against the plain
float32 reference ``benchmark/reference/ouro.py`` at a tiny size.
``tests/test_ouro_cell.py`` has the same model through ``Runner.fit``, the
configuration file and the cell's loss limit.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (a fused rsqrt against a divide by sqrt, the
exit distribution in logs against products, one head on the passes' stack
against a head a pass). ``RTOL`` 1e-5 of the largest entry holds logits,
loss and EVERY gradient leaf of the three-layer, four-pass model: a pass
left out, a norm left out, a gate not read or a bfloat16 matmul misses by
orders of magnitude.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import lm
from autodist_tpu.telemetry import device_counters, scopes
from benchmark.reference import ouro as ref
from benchmark.tools.loss_limit import patched
from tests.test_kimi_linear import close, flat
from tests.test_lfm2_moe import batches      # (2 x 33 ids under 256, seeded)

SEQ = 32          # tests.test_lfm2_moe.batches' own
PASSES = 4
LAYERS = 3


def tiny_config(**kw):
    """Three Ouro layers at d 48 (3 heads of 16, SwiGLU of 80, four norms a
    layer), all four passes, an untied table of 256 rows, beta 0.05."""
    sizes = dict(vocab_size=256, d_model=48, num_heads=3, head_dim=16,
                 mlp_dim=80, dense_dim=80)
    sizes.update(kw)
    return dataclasses.replace(
        lm.LMConfig.ouro_2_6b(num_layers=LAYERS, max_seq_len=64), **sizes)


def reference_loss(params, batch, stacks=None):
    return jnp.mean(ref.token_losses(params, batch, stacks))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    # (the seeded gate's bias is zero: move it, so that a gate read without
    # its bias is another model)
    params["params"]["exit_gate"]["bias"] = jnp.asarray([0.3], jnp.float32)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    _, loss_fn, params, _, batch = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


LAYER_LEAVES = (
    ["MultiHeadAttention_0/%s/kernel" % n
     for n in ("query", "key", "value", "out")]
    + ["mlp/%s_proj/kernel" % n for n in ("gate", "up", "down")]
    + [n + "/scale" for n in ("RMSNorm_0", "attn_out_norm", "RMSNorm_1",
                              "mlp_out_norm")])
STACK_LEAVES = ["final_ln/scale"] + [
    "layer_%d/%s" % (i, leaf) for i in range(LAYERS) for leaf in LAYER_LEAVES]
LEAVES = sorted(["embed/embedding", "lm_head/kernel", "exit_gate/kernel",
                 "exit_gate/bias"] + STACK_LEAVES)


# ------------------------------------------------- the config, the preset


def test_the_published_preset_is_the_catalogs_row():
    cfg = lm.LMConfig.ouro_2_6b()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.head_dim,
            cfg.num_kv_heads, cfg.vocab_size, cfg.max_seq_len) == (
        48, 2048, 16, 128, None, 49152, 65536)
    assert (cfg.dense_dim, cfg.first_k_dense_replace, cfg.num_experts) == (
        5632, 48, 0)                         # a SwiGLU in EVERY layer
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-6
    assert cfg.rope_theta == 1e6 and cfg.layer_types is None
    assert (cfg.loop_steps, cfg.exit_entropy_coef, cfg.sandwich_norm) == (
        4, 0.05, True)
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale
                or cfg.tie_embedding or cfg.qk_norm or cfg.qk_head_norm)
    assert (ref.T, ref.BETA, ref.RMS_EPS, ref.ROPE_THETA) == (
        4, 0.05, 1e-6, 1e6)


def test_the_tree_holds_each_block_once_and_one_gate(tiny):
    """Four passes, ONE set of weights: three layers of four norms, q, k,
    v, o and a SwiGLU, one final norm, two tables and the gate's 48 + 1."""
    _, _, params, _, _ = tiny
    assert set(flat(params)) == {"params/" + leaf for leaf in LEAVES}
    p = params["params"]
    assert p["layer_0"]["MultiHeadAttention_0"]["query"]["kernel"].shape \
        == (48, 3, 16)
    assert p["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (48, 80)
    assert p["exit_gate"]["kernel"].shape == (48, 1)
    assert p["exit_gate"]["bias"].shape == (1,)
    assert p["lm_head"]["kernel"].shape == (48, 256)


@pytest.mark.parametrize("bad", [
    dict(loop_steps=0),                      # no pass at all
    dict(exit_entropy_coef=0.05),            # a gate's term without a loop
    dict(loop_steps=2, num_experts=4, experts_per_token=2),   # sown in a scan
    dict(loop_steps=2, indexer_num_heads=2, indexer_head_dim=8,
         indexer_topk=4)])
def test_a_config_that_names_half_a_loop_is_refused(bad):
    with pytest.raises(ValueError, match="loop_steps"):
        lm.LMConfig(**bad)


# --------------------------------------------- against the plain reference


def test_the_last_passs_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(jax.jit(apply_fn)(params, ids),
              jax.jit(ref.logits_fn)(params, ids))


def test_every_passs_normed_state_matches_the_reference(tiny):
    """``hidden`` hands the loss all T normed states, [T, B, S, d]."""
    cfg, _, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        got = lm.TransformerLM(cfg).apply(params, ids,
                                          method=lm.TransformerLM.hidden)
        want = jnp.stack(ref.states(params, ids))
    assert got.shape == (PASSES, 2, SEQ, 48)
    close(got, want)
    # the passes differ: the loop is not one pass read four times
    assert float(jnp.max(jnp.abs(got[1] - got[0]))) > 1e-2


def test_loss_matches_the_reference(loss_and_grads):
    got, want, _, _ = loss_and_grads
    close(got, want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert np.abs(want["params/" + leaf]).max() > 0
    close(got["params/" + leaf], want["params/" + leaf])


def test_the_lean_head_reads_the_four_passes_in_one_call(tiny,
                                                         loss_and_grads):
    """The cell's head: ONE ``chunked_softmax_xent`` on the [T N, d] stack
    against the one kernel, the plain head's loss and gradients."""
    cfg, _, params, _, batch = tiny
    lean, _, _, _ = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2,
                                        seed=0, lean_head=True)
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(lean)(params, batch))
        value, grads = jax.jit(jax.value_and_grad(lean))(params, batch)
    assert text.count("custom_vjp_call") == 1
    assert "f32[%d,48]" % (PASSES * 2 * SEQ) in text
    got_loss, _, got, _ = loss_and_grads
    close(value, got_loss)
    for name, g in flat(grads).items():
        close(g, got[name])


# ------------------------------------------ the loop tied to an untied twin


def test_the_loops_gradient_is_the_sum_over_an_untied_twins_copies(
        tiny, loss_and_grads):
    """The reference with T SEPARATE copies of the stack (layers and final
    norm), set equal: the same loss, and per-copy gradients whose SUM is
    the looped model's gradient, leaf for leaf. Every copy's gradient is
    its own (no two passes contribute alike), so a loop that dropped a
    pass's contribution, or counted one twice, is seen."""
    _, _, params, _, batch = tiny
    p = params["params"]
    stack = {k: v for k, v in p.items()
             if k.startswith("layer_") or k == "final_ln"}
    with jax.default_matmul_precision("highest"):
        value, copies = jax.jit(jax.value_and_grad(
            lambda stacks: reference_loss(params, batch, stacks)))(
            [stack] * PASSES)
    got_loss, _, got, _ = loss_and_grads
    close(value, got_loss)
    copies = [flat(c) for c in copies]
    assert len(copies) == PASSES
    for leaf in STACK_LEAVES:
        per_copy = [np.asarray(c[leaf]) for c in copies]
        close(got["params/" + leaf], sum(per_copy))
        top = max(np.abs(g).max() for g in per_copy)
        assert all(np.abs(g).max() > 1e-3 * top for g in per_copy), leaf
        assert all(np.abs(a - b).max() > 1e-3 * top
                   for a, b in zip(per_copy, per_copy[1:])), leaf


# -------------------------------------------------- one pass, scan, unroll


def test_one_pass_without_a_gate_is_the_plain_model(tiny):
    """``loop_steps`` 1 with the gate's term off: no gate in the tree, the
    same blocks, and the loss the plain path's mean NLL bit for bit (the
    model's own logits through a log-softmax), which is the reference's
    first pass alone."""
    cfg, _, params, _, batch = tiny
    once = dataclasses.replace(cfg, loop_steps=1, exit_entropy_coef=0.0)
    loss_fn, tree, _, apply_fn = lm.make_train_setup(
        once, seq_len=SEQ, batch_size=2, seed=0)
    mine = {k: v for k, v in params["params"].items() if k != "exit_gate"}
    assert jax.tree_util.tree_map(jnp.shape, tree["params"]) \
        == jax.tree_util.tree_map(jnp.shape, mine)
    assert loss_fn.__dict__.get("device_counters") is None
    plain = {"params": mine}

    def mean_nll(p, b):
        logp = jax.nn.log_softmax(apply_fn(p, b["tokens"][:, :-1]))
        return jnp.mean(-jnp.take_along_axis(
            logp, b["tokens"][:, 1:, None], axis=-1)[..., 0])
    text = str(jax.make_jaxpr(loss_fn)(plain, batch))
    assert " scan[" not in text and "log_sigmoid" not in text
    with jax.default_matmul_precision("highest"):
        got = jax.jit(loss_fn)(plain, batch)
        assert float(got) == float(jax.jit(mean_nll)(plain, batch))
        with patched(ref, "T", 1):        # (one pass reads no gate)
            want = jax.jit(reference_loss)(params, batch)
    close(got, want)


def test_the_scanned_passes_are_the_references_plain_loop(tiny):
    """The passes as ONE scanned body against the reference's Python loop
    over passes and layers: a function of two passes' states gives the
    same gradient through either, stack leaf for stack leaf, and the
    trace holds each block once whatever the passes."""
    cfg, _, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    model = lm.TransformerLM(cfg)

    def of_two_passes(states_fn):
        def f(p):
            h = states_fn(p)
            return jnp.sum(jnp.square(h[-1])) + jnp.sum(h[1])
        with jax.default_matmul_precision("highest"):
            return flat(jax.jit(jax.grad(f))(params))
    got = of_two_passes(lambda p: model.apply(
        p, ids, method=lm.TransformerLM.hidden))
    want = of_two_passes(lambda p: ref.states(p, ids))
    for leaf in STACK_LEAVES + ["embed/embedding"]:
        assert np.abs(want["params/" + leaf]).max() > 0
        close(got["params/" + leaf], want["params/" + leaf])
    text = str(jax.make_jaxpr(lambda p: model.apply(
        p, ids, method=lm.TransformerLM.hidden))(params))
    assert text.count(" scan[") == 1 and "length=%d" % PASSES in text
    assert "unroll=%d" % PASSES in text


# ------------------------------------------------------ the exit distribution


def test_the_exit_distribution_sums_to_one_and_is_the_references():
    g = jnp.asarray(np.random.RandomState(0).randn(PASSES - 1, 50) * 3,
                    jnp.float32)
    log_p = lm.exit_log_distribution(g)
    assert log_p.shape == (PASSES, 50)
    np.testing.assert_allclose(jnp.sum(jnp.exp(log_p), axis=0), 1.0,
                               atol=1e-6)
    want = jnp.stack(ref.exit_distribution(list(jax.nn.sigmoid(g))))
    np.testing.assert_allclose(jnp.exp(log_p), want, atol=1e-6)


def test_a_uniform_gate_gives_entropy_ln_t():
    """Uniform over the exits is ``lambda^t = 1 / (T - t + 1)``: a quarter,
    a third, a half."""
    lam = jnp.asarray([[1 / 4], [1 / 3], [1 / 2]], jnp.float32)
    log_p = lm.exit_log_distribution(jnp.log(lam) - jnp.log1p(-lam))
    np.testing.assert_allclose(jnp.exp(log_p), 0.25, atol=1e-6)
    entropy = -jnp.sum(jnp.exp(log_p) * log_p, axis=0)
    np.testing.assert_allclose(entropy, math.log(PASSES), atol=1e-6)


def test_a_saturated_gate_has_a_finite_entropy_and_gradient():
    def entropy(g):
        log_p = lm.exit_log_distribution(g)
        return -jnp.sum(jnp.exp(log_p) * log_p)
    g = jnp.asarray([[80.0, -80.0], [-80.0, 80.0], [0.0, 0.0]], jnp.float32)
    value, grad = jax.value_and_grad(entropy)(g)
    assert np.isfinite(value) and 0 <= float(value) < 1e-3 + math.log(2)
    assert np.all(np.isfinite(grad))


def test_the_step_counts_the_exit_masses_and_the_entropy(tiny):
    """``loop.exit_mass_<t>`` (batch means of p^t) sum to 1 and
    ``loop.exit_entropy`` lies in (0, ln T]; the gauges say what ran."""
    _, loss_fn, params, _, batch = tiny
    names = tuple("loop.exit_mass_%d" % t for t in range(1, PASSES + 1)) \
        + ("loop.exit_entropy",)
    assert loss_fn.device_counters == names
    telemetry.reset()

    def counted(p, b):
        with device_counters.collect(names) as got:
            loss_fn(p, b)
        return dict(got)
    got = jax.jit(counted)(params, batch)
    masses = [float(got[n]) for n in names[:-1]]
    assert abs(sum(masses) - 1.0) < 1e-5 and min(masses) > 0.01
    assert 0 < float(got["loop.exit_entropy"]) <= math.log(PASSES)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.loop_steps"] == PASSES
    assert gauges["model.block_applications"] == PASSES * LAYERS
    assert gauges["model.kept_core_bytes"] == 0      # nothing is recomputed
    assert gauges["model.remat_blocks"] == 0


# ------------------------------------------------------------------ serving


@pytest.mark.parametrize("method", ["prefill", "decode_step"])
def test_serving_refuses_a_looped_model_by_name(tiny, method):
    cfg, _, params, _, batch = tiny
    args = ((batch["tokens"][:, :8], jnp.full((2,), 8))
            if method == "prefill" else
            (batch["tokens"][:, 0], None, None, jnp.zeros((2,), jnp.int32)))
    with pytest.raises(NotImplementedError, match="loop_steps"):
        lm.TransformerLM(cfg).apply(
            params, *args, method=getattr(lm.TransformerLM, method))


# -------------------------------------------------- the rules count passes


def parents_remat_rule(param_count, num_layers, hbm_bytes):
    """``auto_remat_blocks`` as the tree at 18208f9 had it."""
    return (hbm_bytes is not None and num_layers > 1
            and 16.0 * param_count > hbm_bytes / 2)


# (parameters as the configurations' files count them, layers, recomputed
# on a v5e: what each of the six cells' steps has been since its PR)
SIX = {"lm1b": (303_861_103, 8, False),
       "olmoe_1b_7b": (625_518_592, 1, False),
       "kimi_linear_48b_a3b": (602_188_288, 5, True),
       "deepseek_v2_lite": (634_691_072, 6, True),
       "keye_vl2_30b_a3b": (561_969_664, 5, True),
       "lfm2_24b_a2b": (558_424_448, 6, True)}


@pytest.mark.parametrize("name", sorted(SIX))
def test_with_one_pass_the_remat_rule_decides_as_the_parents(name):
    params, layers, recomputed = SIX[name]
    for hbm in (None, 16e9, 32e9, 95e9):
        assert lm.auto_remat_blocks(params, layers, hbm) \
            == parents_remat_rule(params, layers, hbm)
    assert lm.auto_remat_blocks(params, layers, 16e9) == recomputed
    # on the line itself and a parameter either side of it
    for count in (10 ** 9 - 1, 10 ** 9, 10 ** 9 + 1):
        assert lm.auto_remat_blocks(count, layers, 32e9) \
            == parents_remat_rule(count, layers, 32e9)


def test_the_remat_rule_counts_a_looped_models_applications():
    """Six Ouro layers pass the parent's line by 2 % and five would not,
    though their 20 applications keep four times the activations of five
    layers run once: the rule holds a looped model to the state plus as
    much again for EACH pass."""
    layer, rest = 51_388_416, 2 * 100_663_296 + 2048 + 2049
    six, five, four = (n * layer + rest for n in (6, 5, 4))
    assert six == 509_661_185
    assert parents_remat_rule(six, 6, 16e9)
    assert not parents_remat_rule(five, 5, 16e9)
    for count, layers in ((six, 6), (five, 5), (four, 4)):
        assert lm.auto_remat_blocks(count, layers, 16e9, 4)
    # one layer run four times is four applications
    assert lm.auto_remat_blocks(six, 1, 16e9, 4)
    assert not lm.auto_remat_blocks(six, 1, 16e9, 1)
    assert not lm.auto_remat_blocks(six, 6, None, 4)
    assert not lm.auto_remat_blocks(10_000_000, 6, 16e9, 4)


@pytest.mark.parametrize("name, tokens, heads, qk, v, an_application", [
    ("ouro_2_6b", 4096, 16, 128, 128, 33_816_576),
    ("olmoe_1b_7b", 2048 * 4, 16, 128, 128, 67_633_152),
    ("kimi_linear_48b_a3b", 8192, 32, 192, 128, 168_820_736),
    ("deepseek_v2_lite", 8192, 16, 192, 128, 84_410_368),
    ("keye_vl2_30b_a3b", 8192, 32, 128, 128, 135_266_304),
    ("lfm2_24b_a2b", 8192, 32, 64, 64, 68_157_440)])
def test_what_an_application_keeps_of_its_flash_core(name, tokens, heads, qk,
                                                     v, an_application):
    """q and the output in bfloat16, the log-sum-exp in float32, at each
    cell's tokens and heads (a latent layer's q is wider than its
    output)."""
    assert lm.flash_kept_bytes(tokens, heads, qk, v, 2) == an_application \
        == 2 * tokens * heads * (qk + v) + 4 * tokens * heads


def test_the_kept_cores_of_the_cell_count_applications():
    """The cell: 24 applications of 33.8 MB, 0.81 GB beside 6.12 GB of
    state, leave the share of the chip free that the held experts'
    products are held to, with room for 178."""
    one = lm.flash_kept_bytes(4096, 16, 128, 128, 2)
    room = (1 - lm.KEPT_EXPERTS_HBM_LEFT) * 16e9 - 12 * 509_661_185
    assert 24 * one < room and room // one == 178


def test_the_gauge_counts_what_every_application_keeps(monkeypatch):
    """Through ``make_train_setup`` on a chip made small enough to
    recompute the blocks: every application's recomputed block saves the
    flash core's names, and ``model.kept_core_bytes`` is layers x passes
    x an application's bytes."""
    cfg = tiny_config()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(lm, "_chip_hbm_bytes", lambda: 1e6)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0, attention="flash")
    telemetry.reset()
    text = str(jax.make_jaxpr(jax.grad(loss_fn))(params, batch))
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.remat_blocks"] == LAYERS
    assert gauges["attention.flash_layers"] == LAYERS
    assert gauges["model.kept_core_bytes"] == LAYERS * PASSES \
        * lm.flash_kept_bytes(2 * SEQ, 3, 16, 16, 4)
    assert text.count("name=flash_core_kept") > 0


# ------------------------------------------------------------- the scopes


def test_a_looped_step_names_its_passes_and_its_gate(tiny):
    """``loop`` sits inside ``blocks`` and holds the scanned body (its
    blocks' matmuls and the final norm); ``exit_gate`` holds the gate's
    product and the distribution, outside the blocks."""
    _, loss_fn, params, _, batch = tiny
    assert scopes.LOOP in scopes.SCOPES and scopes.EXIT_GATE in scopes.SCOPES
    # (as the lowering differentiates it: under the ``loss`` scope, which
    # JAX then wraps as jvp(loss) / transpose(jvp(loss)))
    text = jax.jit(jax.grad(scopes.scoped(scopes.LOSS)(loss_fn))).lower(
        params, batch).compile().as_text()
    ops = [o for names in scopes.parse_scope_map(text).values()
           for o in names]
    loop = [o.split("/") for o in ops if scopes.LOOP in o.split("/")]
    gate = [o.split("/") for o in ops if scopes.EXIT_GATE in o.split("/")]
    assert loop and all(
        scopes.BLOCKS in o and o.index(scopes.BLOCKS) < o.index(scopes.LOOP)
        for o in loop)
    assert any("dot_general" in o for o in loop)
    assert any("final_ln" in o for o in loop)
    assert any(part.startswith("transpose(") for o in loop for part in o)
    assert gate and not any(scopes.BLOCKS in o for o in gate)
    assert any("dot_general" in o for o in gate)
