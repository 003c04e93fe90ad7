"""Kimi-Linear on the normal training path (``LMConfig.kimi_linear_48b_a3b``):
KDA layers beside NoPE latent attention, a leading dense layer, a share of
sigmoid-routed experts with a shared expert, against the plain float32
reference ``benchmark/reference/kimi_linear.py`` at a tiny size, and
lm1b's and OLMoE's models held to what they built before the model got a
per-layer pattern.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (the chunked delta rule with its triangular
solve against the token-by-token recurrence; sorted rows through a grouped
matmul against every expert on every token; a fused rsqrt against a divide
by sqrt). ``RTOL`` 1e-5 of the largest entry holds logits, loss, the
chunked op alone and every gradient leaf of ONE layer of each kind. Through
the five-layer model the gradients' float32 rounding adds up layer by
layer (measured: 6e-5 at worst, on the first KDA layers' leaves; against a
float64 run of the reference the program and the float32 reference are
equally far, 2e-6 to 5e-6 a layer), so the whole model's leaves are held
to ``DEEP_RTOL`` 1e-4: a decay left out, beta fixed at 1, a lost expert or
a bfloat16 matmul misses either by orders of magnitude.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu import telemetry
from autodist_tpu.models import layers, lm
from autodist_tpu.ops import kda as kda_op
from autodist_tpu.ops.attention import reference_attention
from autodist_tpu.ops.flash_attention import flash_attention
from autodist_tpu.parallel import expert
from benchmark.reference import kimi_linear as ref

RTOL = 1e-5
DEEP_RTOL = 1e-4
TOP_K = 4
HELD = (0, 1, 2, 3)
SEQ = 32


def tiny_config(**kw):
    """The cell's five layers (KDA + dense, KDA + MoE, KDA + MoE, MLA +
    MoE, KDA + MoE) at d 48: 4 KDA heads of 16, 4 latent heads (latent 24,
    16 + 8 score features, values of 16), dense width 96, 16 experts of
    width 32 of which 4 are held, top-4, one shared expert, vocab 256."""
    sizes = dict(vocab_size=256, d_model=48, num_heads=4, mlp_dim=32,
                 kda_num_heads=4, kda_head_dim=16, kv_lora_rank=24,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 dense_dim=96, num_experts=16, experts_per_token=TOP_K,
                 experts_held=HELD)
    sizes.update(kw)
    layers_ = sizes.pop("num_layers", 5)
    return dataclasses.replace(
        lm.LMConfig.kimi_linear_48b_a3b(num_layers=layers_, max_seq_len=64),
        **sizes)


def tiny_olmoe():
    return dataclasses.replace(
        lm.LMConfig.olmoe_1b_7b(num_layers=1, max_seq_len=16), vocab_size=64,
        d_model=32, num_heads=2, num_experts=4, experts_per_token=2,
        mlp_dim=16)


def close(got, want, rtol=RTOL):
    """Within rtol of the reference's largest entry, elementwise."""
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * max(np.abs(want).max(), 1e-30))


def flat(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def batches(n, rows=2, vocab=256, seed=1):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, SEQ + 1)).astype(np.int32)}
            for _ in range(n)]


def reference_loss(params, batch):
    return ref.nll_sum(params, batch, TOP_K, HELD) / ref.batch_weight(
        {"tokens": np.zeros(batch["tokens"].shape)})


def both_losses_and_grads(cfg):
    loss_fn, params, _, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    batch = batches(1)[0]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


@pytest.fixture(scope="module")
def loss_and_grads():
    return both_losses_and_grads(tiny_config())


KDA_LEAVES = ["kda/" + n for n in (
    "A_log", "dt_bias", "o_norm", "q_conv", "k_conv", "v_conv",
    "q_proj/kernel", "k_proj/kernel", "v_proj/kernel", "o_proj/kernel",
    "f_a_proj/kernel", "f_b_proj/kernel", "g_a_proj/kernel",
    "g_b_proj/kernel", "b_proj/kernel")]
MLA_LEAVES = ["mla/" + n for n in (
    "q_proj/kernel", "kv_a_proj/kernel", "kv_a_norm/scale",
    "kv_b_proj/kernel", "o_proj/kernel")]
MOE_LEAVES = ["moe/" + n for n in (
    "router", "gate_proj", "up_proj", "down_proj", "shared/gate_proj/kernel",
    "shared/up_proj/kernel", "shared/down_proj/kernel")]
DENSE_LEAVES = ["mlp/%s_proj/kernel" % n for n in ("gate", "up", "down")]
NORMS = ["RMSNorm_0/scale", "RMSNorm_1/scale"]
LAYER_LEAVES = [KDA_LEAVES + DENSE_LEAVES, KDA_LEAVES + MOE_LEAVES,
                KDA_LEAVES + MOE_LEAVES, MLA_LEAVES + MOE_LEAVES,
                KDA_LEAVES + MOE_LEAVES]
LEAVES = sorted(
    ["embed/embedding", "final_ln/scale", "lm_head/kernel"]
    + ["layer_%d/%s" % (i, leaf) for i, names in enumerate(LAYER_LEAVES)
       for leaf in names + NORMS])


def test_the_tiny_model_has_the_cells_layer_pattern(tiny):
    cfg, _, params, _, _ = tiny
    assert cfg.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert cfg.first_k_dense_replace == 1
    # every leaf but the routers' choice-only bias, which has no gradient
    bias = {"params/layer_%d/moe/e_score_correction_bias" % i
            for i in range(1, 5)}
    assert set(flat(params)) - bias == {"params/" + leaf for leaf in LEAVES}
    assert all(not np.any(flat(params)[b]) for b in bias)


def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(apply_fn(params, ids),
              ref.logits_fn(params, ids, TOP_K, HELD))


def test_loss_matches_the_reference_and_is_the_nll_alone(loss_and_grads):
    got, want, grads, _ = loss_and_grads
    close(got, want)
    # the bias chooses and does nothing else: no gradient reaches it
    assert all(not np.any(g) for name, g in grads.items()
               if name.endswith("e_score_correction_bias"))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert np.abs(want["params/" + leaf]).max() > 0
    close(got["params/" + leaf], want["params/" + leaf], DEEP_RTOL)


@pytest.mark.parametrize("kind, dense", [("kda", 1), ("kda", 0), ("mla", 0)])
def test_one_layer_of_each_kind_matches_to_1e_5(kind, dense):
    """Without four more layers' rounding behind it, every leaf of a layer
    is within 1e-5."""
    got_loss, want_loss, got, want = both_losses_and_grads(tiny_config(
        num_layers=1, layer_types=(kind,), first_k_dense_replace=dense))
    close(got_loss, want_loss)
    assert (kind + "/o_proj/kernel" in "".join(got)) and len(got) >= 10
    for name in got:
        close(got[name], want[name])


# --------------------------- the chunked delta rule against the recurrence


def kda_inputs(seq, decay, seed=0, width=16, B=2, H=3, values=None):
    """q, k normalised as the mixer does; ``decay``: "seeded" draws the log
    decay as seeded parameters give it (A in [1, 16], softplus(dt_bias) in
    [1e-3, 1e-1]); "strongest" is the parameterisation's end: A = 16 and a
    saturated softplus (10), -160 a token on half of the channels (e^-160
    is 0 in float32: a chunk's running sum reaches -10,240) beside
    channels that do not decay at all."""
    r = np.random.RandomState(seed)
    dk, dv = width, values or width
    q = r.randn(B, seq, H, dk)
    k = r.randn(B, seq, H, dk)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(B, seq, H, dv)
    if decay == "seeded":
        g = -r.uniform(1, 16, (1, 1, H, 1)) * np.exp(
            r.uniform(np.log(1e-3), np.log(1e-1), (B, seq, H, dk)))
    else:
        g = -160.0 * (r.rand(B, seq, H, dk) < 0.5)
    beta = r.rand(B, seq, H)
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


def weighted(fn, *args):
    """A scalar of both outputs with a weight on every entry."""
    o, state = fn(*args)
    return (jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))
            + jnp.sum(state * jnp.sin(jnp.arange(state.size)
                                      .reshape(state.shape))))


def outputs_and_gradients(fn, args):
    """(o, final state, dq, dk, dv, dg, dbeta) under ``weighted``."""
    return tuple(fn(*args)) + tuple(jax.grad(
        functools.partial(weighted, fn), argnums=(0, 1, 2, 3, 4))(*args))


# the rendering ``kda_chunked`` picks by the head width, and a size for it
# (the kernels run interpreted here, a grid step at a time)
PATHS = {"lax": dict(width=16), "kernel": dict(width=128, B=1, H=2)}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("decay", ["seeded", "strongest"])
@pytest.mark.parametrize("seq", [64, 128, 100, 7])
def test_chunked_kda_is_the_recurrence(seq, decay, path):
    """Output, final state and the gradients of q, k, v, g, beta, at
    lengths that are and are not whole chunks, of the ``lax`` form (heads
    of 16) and of the pallas kernels (heads of 128). Under the strongest
    decay nothing overflows and nothing is NaN (``close`` asserts finite);
    a running sum of -10,240 carries a float32 rounding of 1e-3 in
    absolute terms, so there the tolerance is 1e-4."""
    args = kda_inputs(seq, decay, **PATHS[path])
    assert kda_op.runs_as_kernels(args[0].shape[-1], args[2].shape[-1]) \
        is (path == "kernel")
    rtol = RTOL if decay == "seeded" else DEEP_RTOL
    with jax.default_matmul_precision("highest"):
        got = outputs_and_gradients(kda_op.kda_chunked, args)
        want = outputs_and_gradients(ref.delta_rule, args)
    for a, b in zip(got, want):
        close(a, b, rtol)


@pytest.mark.parametrize("dtype, rtol, heads", [
    (jnp.float32, RTOL, dict(H=2)),
    (jnp.bfloat16, 5e-2, dict(H=2)),
    # an odd number of heads (one a grid step, its triangular factor alone
    # in an MXU pass) with values twice as wide as the keys
    (jnp.float32, RTOL, dict(H=3, values=256))])
def test_the_kernels_gradients_are_autodiffs_of_the_lax_form(dtype, rtol,
                                                             heads):
    """The backward kernel (``jax.vjp`` of the forward chunk, the state's
    gradient carried in VMEM) against XLA's autodiff of the ``lax`` form on
    the same inputs, two chunks and a tail: the five gradients, the output
    and the final state. In float32 they differ by the order of sums; with
    bfloat16 matmul operands by bfloat16's rounding (the tolerance of
    ``tests/test_flash_attention.py``'s bfloat16 cases)."""
    args = kda_inputs(150, "seeded", seed=1, width=128, B=1, **heads)
    with jax.default_matmul_precision("highest"):
        got = outputs_and_gradients(
            functools.partial(kda_op._kda_pallas, dtype=dtype), args)
        want = outputs_and_gradients(
            functools.partial(kda_op._kda_lax, dtype=dtype), args)
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    for a, b in zip(got, want):
        assert a.shape == b.shape
        close(a.astype(jnp.float32), b.astype(jnp.float32), rtol)


def test_the_head_width_picks_the_rendering(monkeypatch):
    """Whole 128-lane tiles take the kernels, on a TPU compiled and on the
    CPU interpreted; the tiny model's heads take the ``lax`` form whatever
    the backend; a backend the kernels know nothing of raises, as for the
    flash kernel (``pallas_mode.interpret``)."""
    assert kda_op.runs_as_kernels(128, 128)
    assert kda_op.runs_as_kernels(256, 128)
    for narrow in ((16, 16), (64, 64), (128, 64), (192, 128)):
        assert not kda_op.runs_as_kernels(*narrow)
    args = kda_inputs(8, "seeded", width=16, B=1, H=1)
    assert "pallas_call" not in str(jax.make_jaxpr(kda_op.kda_chunked)(*args))
    args = kda_inputs(8, "seeded", width=128, B=1, H=1)
    assert "pallas_call" in str(jax.make_jaxpr(kda_op.kda_chunked)(*args))
    # compiled or interpreted follows ``pallas_mode`` through the kernels'
    # cached traces (they are ``jax.jit``s under a ``custom_vjp``)
    from autodist_tpu.ops import pallas_mode

    def traced():
        return str(jax.make_jaxpr(lambda *a: jax.grad(lambda *b: jnp.sum(
            kda_op.kda_chunked(*b)[0]))(*a))(*args))
    with pallas_mode.compiling_for_tpu():
        for_tpu = traced()
    assert for_tpu.count("interpret=False") == 2 == traced().count(
        "interpret=True")
    # the mixer's passes around the core follow the same rule: heads of 128
    # trace them, narrow heads trace none and keep the ``jnp`` form
    from tests.test_flash_attention import kernel_calls
    for (heads, width), fused in (((1, 128), 1), ((4, 16), 0)):
        mixer = layers.KimiDeltaAttention(
            layers.KDAConfig(heads, width, 4), 1e-5)
        x = jnp.zeros((1, 8, 48))
        params = mixer.init(jax.random.PRNGKey(0), x)
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda: mixer.init(jax.random.PRNGKey(0), x))())
        grad = jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(mixer.apply(p, x))))(params).jaxpr
        assert [kernel_calls(grad, name) for name in FUSED_PASSES] \
            == [fused] * 4
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not kda_op.runs_as_kernels(16, 16)
    with pytest.raises(RuntimeError, match="gpu"):
        kda_op.runs_as_kernels(128, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda_op.runs_as_kernels(128, 128)
    assert layers.KDA_CORE_OUT == kda_op.KEPT


GAUGE_CASES = {
    # the cell's layer pattern at the published head width, narrow otherwise
    "kimi_linear_train_1chip": (lambda: tiny_config(
        kda_num_heads=1, kda_head_dim=128), 4),
    "the tiny model's heads of 16": (lambda: tiny_config(), 0),
    "olmoe_train_1chip": (lambda: tiny_olmoe(), 0),
    "lm1b_train_1chip": (lm.LMConfig.tiny, 0),
}


@pytest.mark.parametrize("gauge", ["attention.kda_kernel_layers",
                                   "attention.kda_fused_mixer_layers"])
@pytest.mark.parametrize("cell", sorted(GAUGE_CASES))
def test_the_gauge_says_how_many_layers_took_the_kda_kernels(cell, gauge):
    """``attention.kda_kernel_layers`` and ``attention.kda_fused_mixer_layers``
    (the delta rule as kernels, the element-wise passes around it fused)
    are set when the loss is traced, from what ``runs_as_kernels`` said of
    the configuration's KDA heads."""
    assert gauges_of_a_traced_loss(cell)[gauge] == GAUGE_CASES[cell][1]


@functools.lru_cache(maxsize=None)
def gauges_of_a_traced_loss(cell):
    loss_fn, params, batch, _ = lm.make_train_setup(
        GAUGE_CASES[cell][0](), seq_len=16, batch_size=1, seed=0)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    return dict(telemetry.get_recorder().gauges())


# ------------------ the mixer's fused passes against the ``jnp`` form


FUSED_PASSES = ("kda_pre_fwd", "kda_pre_bwd", "kda_post_fwd", "kda_post_bwd")
# (sequence, rows a grid step works on, sequences, heads): the tiles are
# whole chunks of 64
FUSED_CASES = {
    "neither a whole row tile nor a whole chunk": (100, 64, 2, 2),
    "a row tile boundary cuts the filter's window": (130, 64, 1, 1),
    "the first K - 1 tokens": (3, 256, 1, 1),
}
FUSED_DTYPES = {"float32": (jnp.float32, RTOL), "bfloat16": (jnp.bfloat16, 5e-2)}


def fused_inputs(seq, dtype, H=2, d=128, K=4, B=2, seed=0):
    """What a KDA mixer hands its element-wise passes: the outputs of
    ``q_proj``, ``k_proj``, ``v_proj``, ``f_b_proj`` and ``g_b_proj``, the
    three filters, ``A_log`` and ``dt_bias`` as they are seeded, ``o_norm``;
    and a weight for every entry of every output."""
    r = np.random.RandomState(seed)
    wide = lambda t=dtype: jnp.asarray(r.randn(B, seq, H * d), t)  # noqa: E731
    xq, xk, xv, f, gate = (wide() for _ in range(5))
    filters = [jnp.asarray(r.uniform(-.5, .5, (K, H * d)), jnp.float32)
               for _ in range(3)]
    a_log = jnp.asarray(np.log(r.uniform(1, 16, H)), jnp.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), H * d))
    dt_bias = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    o_norm = jnp.asarray(r.uniform(.5, 1.5, d), jnp.float32)
    weights = [wide(jnp.float32) for _ in range(4)]
    return (xq, xk, xv, f, *filters, a_log, dt_bias), gate, o_norm, weights


def values_and_gradients(fn, args, weights, seq):
    """(the outputs' first ``seq`` rows as [B, seq, H * d], the gradient of
    their weighted sum by every argument)."""
    def outputs(*a):
        return [o.reshape(o.shape[:2] + (-1,))[:, :seq] for o in fn(*a)]

    def weighted_sum(*a):
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(outputs(*a), weights))
    return outputs(*args), jax.grad(
        weighted_sum, argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("dtype", sorted(FUSED_DTYPES))
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_the_fused_prologue_is_the_jnp_form(case, dtype, monkeypatch):
    """``kda_pre`` (``kda_pre_fwd`` / ``kda_pre_bwd``, interpreted, heads of
    128) against ``layers.kda_inputs``: q, k, v, g and EVERY gradient (the
    four projections' outputs, the three filters, ``A_log``, ``dt_bias``);
    zeros before the sequence's start, zeros in the rows that pad the last
    chunk. In bfloat16 the ``jnp`` form filters and gates in bfloat16 and
    the kernel in float32: bfloat16's own tolerance."""
    (seq, rows, B, H), (dt, rtol) = FUSED_CASES[case], FUSED_DTYPES[dtype]
    monkeypatch.setattr(kda_op, "ROW_TILE", rows)
    args, _, _, weights = fused_inputs(seq, dt, H=H, B=B)
    whole = kda_op.kda_pre(*args, dt)
    assert [o.dtype for o in whole] == [jnp.float32, jnp.float32, dt,
                                        jnp.float32]
    for o in whole:
        assert o.shape == (B, seq + -seq % kda_op.CHUNK, H * 128)
        assert not np.any(np.asarray(o[:, seq:], np.float32))
    got = values_and_gradients(
        lambda *a: kda_op.kda_pre(*a, dt), args, weights, seq)
    want = values_and_gradients(
        lambda *a: layers.kda_inputs(*a, dt), args, weights, seq)
    for a, b in zip(got[0] + list(got[1]), want[0] + list(want[1])):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a.astype(jnp.float32), b.astype(jnp.float32), rtol)


@pytest.mark.parametrize("dtype", sorted(FUSED_DTYPES))
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_the_fused_epilogue_is_the_jnp_form(case, dtype, monkeypatch):
    """``kda_post`` (``kda_post_fwd`` / ``kda_post_bwd``) against
    ``layers.kda_output``: the gated per-head RMSNorm from the core's
    padded output, and the gradients of the core's output (zeros in the
    padding rows), of the gate projection's output and of ``o_norm``."""
    (seq, rows, B, H), (dt, rtol) = FUSED_CASES[case], FUSED_DTYPES[dtype]
    monkeypatch.setattr(kda_op, "ROW_TILE", rows)
    _, gate, o_norm, weights = fused_inputs(seq, dt, H=H, B=B)
    padded = seq + -seq % kda_op.CHUNK
    o = jnp.asarray(np.random.RandomState(1).randn(B, padded, H * 128), dt)
    got = values_and_gradients(
        lambda *a: [kda_op.kda_post(*a, 1e-5, dt)], (o, gate, o_norm),
        weights, seq)
    want = values_and_gradients(
        lambda o, *a: [layers.kda_output(
            o[:, :seq].reshape(B, seq, H, 128), *a, 1e-5, dt)],
        (o, gate, o_norm), weights, seq)
    assert got[0][0].dtype == dt
    assert not np.any(np.asarray(got[1][0][:, seq:], np.float32))
    for a, b in zip(got[0] + list(got[1]), want[0] + list(want[1])):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a.astype(jnp.float32), b.astype(jnp.float32), rtol)


def test_a_filter_longer_than_the_halo_is_refused():
    args, _, _, _ = fused_inputs(8, jnp.float32, K=10)
    with pytest.raises(ValueError, match="10 taps"):
        kda_op.kda_pre(*args, jnp.float32)


def test_the_factored_form_would_overflow_where_the_sub_blocks_do_not():
    """What the sub-blocks are for: exp(-G) of the strongest decay is inf
    in float32 after one token."""
    _, _, _, g, _ = kda_inputs(64, "strongest")
    assert np.isinf(np.exp(-np.cumsum(np.asarray(g), axis=1))).any()


# ------------------------------------- latent attention through the kernel


@pytest.mark.parametrize("seq", [64, 40])
def test_flash_attention_takes_values_narrower_than_the_scores(seq):
    """MLA's shape: scores over 192 features, values of 128; forward and
    the three gradients against materialised scores (the kernel
    interpreted)."""
    r = np.random.RandomState(0)
    q, k = (jnp.asarray(r.randn(1, seq, 2, 192), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.randn(1, seq, 2, 128), jnp.float32)
    mask = jnp.tril(jnp.ones((seq, seq), bool))[None, None]
    weight = jnp.cos(jnp.arange(v.size).reshape(v.shape))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: jnp.sum(
            flash_attention(*a, causal=True) * weight), argnums=(0, 1, 2))(
            q, k, v)
        want = jax.value_and_grad(lambda *a: jnp.sum(
            reference_attention(*a, mask) * weight), argnums=(0, 1, 2))(
            q, k, v)
    close(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        close(a, b)


def test_the_latent_layer_through_the_kernel_gives_the_default_paths_gradients(
        tiny):
    cfg, _, params, _, batch = tiny
    kernel = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2, seed=0,
                                 attention="flash")[0]
    default = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2, seed=0,
                                  attention="default")[0]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(kernel))(params, batch)
        want = jax.jit(jax.value_and_grad(default))(params, batch)
    close(got[0], want[0])
    got, want = flat(got[1]), flat(want[1])
    for name in got:
        close(got[name], want[name], DEEP_RTOL)


@pytest.mark.parametrize("seq, backend, flash", [
    (8192, "tpu", True), (8192, "cpu", False), (2048, "tpu", True),
    (256, "tpu", False)])
def test_auto_attention_sees_the_latent_layers_192_features(seq, backend,
                                                            flash):
    assert lm.auto_flash_attention(seq, 128 + 64, backend) is flash


# ------------------------------------------------- the share of the experts


def routed_layer(rng, tokens, d, f, n_all):
    return (jnp.asarray(rng.randn(tokens, d), jnp.float32), {
        "router": jnp.asarray(rng.randn(d, n_all) / np.sqrt(d), jnp.float32),
        "e_score_correction_bias": jnp.zeros((n_all,), jnp.float32),
        "gate_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "up_proj": jnp.asarray(rng.randn(n_all, d, f) / np.sqrt(d), jnp.float32),
        "down_proj": jnp.asarray(rng.randn(n_all, f, d) / np.sqrt(f), jnp.float32),
        "shared": {n + "_proj": {"kernel": jnp.asarray(
            rng.randn(*s) / np.sqrt(s[0]), jnp.float32)}
            for n, s in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d)))}})


def program_share(x, m, held, top_k=TOP_K, bias=None):
    """The routed part one chip computes: its stacks hold ``held`` only."""
    idx = jnp.asarray(held)
    routing = expert.Routing(
        "sigmoid", True, ref.SCALING,
        m["e_score_correction_bias"] if bias is None else bias)
    return expert.dropless_moe_ffn(
        x, m["router"], m["gate_proj"][idx], m["up_proj"][idx],
        m["down_proj"][idx], top_k, routing=routing, held=tuple(held))


def test_all_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test: 16 experts over four chips of 4. The routed
    outputs of the four shares, summed, plus the shared expert counted
    ONCE, equal the reference's whole layer with every expert held; and
    each share is the reference's same share."""
    x, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 16)
    shares = [(0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12, 13, 14, 15)]
    with jax.default_matmul_precision("highest"):
        parts = [program_share(x, m, held) for held in shares]
        uncut = ref.routed_ffn(x, m, TOP_K, held=tuple(range(16)))
        shared = ref.swiglu(x, m["shared"])
        for held, (out, lb, z, counts) in zip(shares, parts):
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            close(out, ref.routed_ffn(x, cut, TOP_K, held, shared=False))
            assert float(lb) == float(z) == 0.0 and counts.shape == (4,)
    close(sum(p[0] for p in parts) + shared, uncut)
    # every chosen pair is held by exactly one chip
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * TOP_K


def test_a_share_in_any_order_and_every_expert_held_agree():
    x, m = routed_layer(np.random.RandomState(4), 24, 32, 16, 8)
    with jax.default_matmul_precision("highest"):
        whole = program_share(x, m, tuple(range(8)))[0]
        parts = [program_share(x, m, held)[0]
                 for held in ((7, 2, 5), (0, 1, 3, 4, 6))]
    close(parts[0] + parts[1], whole)


def test_gates_are_sigmoid_scores_renormalised_and_scaled_and_the_bias_only_chooses():
    r = np.random.RandomState(5)
    logits = jnp.asarray(r.randn(6, 8), jnp.float32)
    # a bias that lifts expert 7 into every token's choice
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 10.0], jnp.float32)
    _, gate, chosen = expert.Routing("sigmoid", True, 2.446, bias).choose(
        logits, 3)
    scores = 1 / (1 + np.exp(-np.asarray(logits)))
    by_hand = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    assert np.array_equal(np.sort(chosen, -1), np.sort(by_hand, -1))
    assert np.all(np.any(np.asarray(chosen) == 7, axis=-1))
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=-1)
    close(gate, picked / picked.sum(-1, keepdims=True) * 2.446)   # no bias in
    close(jnp.sum(gate, -1), np.full(6, 2.446))
    _, plain, _ = expert.Routing("sigmoid", False, 1.0, bias).choose(logits, 3)
    close(plain, picked)


def test_the_layers_load_equals_a_count_by_hand(tiny):
    """``routed_pairs`` = pairs that chose a HELD expert, ``chosen_pairs``
    = all T x k, ``max_expert_pairs`` = the fullest HELD expert's, per
    routed layer; the dense layer counts nothing."""
    cfg, _, params, _, batch = tiny
    ids = batch["tokens"][:, :-1]
    model = lm.TransformerLM(cfg)
    _, sown = model.apply(params, ids, mutable=["counters", "intermediates"],
                          capture_intermediates=lambda m, _: isinstance(
                              m, layers.MoEFeedForward))
    assert sorted(sown["counters"]) == ["layer_%d" % i for i in range(1, 5)]
    # layer 1's input is reproducible by hand: the reference's first layer
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][ids]
        x = ref.layer(x, p["layer_0"], TOP_K, HELD, 1e-5)
        x = x + ref.kda(ref.rms(x, p["layer_1"]["RMSNorm_0"]["scale"]),
                        p["layer_1"]["kda"], 1e-5)
        h = ref.rms(x, p["layer_1"]["RMSNorm_1"]["scale"]).reshape(-1, 48)
        scores = jax.nn.sigmoid(h @ p["layer_1"]["moe"]["router"])
    chosen = np.asarray(jax.lax.top_k(scores, TOP_K)[1])
    per_expert = np.bincount(chosen.reshape(-1), minlength=16)
    got = {k: int(v[0]) for k, v in sown["counters"]["layer_1"]["moe"].items()}
    assert got == {"chosen_pairs": 2 * SEQ * TOP_K,
                   "routed_pairs": int(per_expert[list(HELD)].sum()),
                   "max_expert_pairs": int(per_expert[list(HELD)].max())}


def cpu_spec(n):
    return autodist_tpu.resource_spec.ResourceSpec.from_dict({
        "nodes": [{"address": "127.0.0.1", "chief": True,
                   "cpus": list(range(n))}]})


def fit_two_steps(tiny, devices, pool):
    cfg, loss_fn, params, _, _ = tiny
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=S.AllReduce(),
                               resource_spec=cpu_spec(devices))
    runner = ad.build(loss_fn, optax.adam(1e-3), params, pool[0])
    runner.init(params)
    with jax.default_matmul_precision("highest"):
        history = runner.fit(iter(pool), steps=2)
    return runner, [float(m["loss"]) for m in history]


@pytest.mark.parametrize("devices", [1, 2])
def test_fit_gives_the_reference_losses_of_steps_0_and_1(tiny, devices):
    """make_train_setup -> AutoDist(AllReduce()).build -> Runner.fit, as
    lm1b and OLMoE go, against ``train_check`` (block-accumulated
    gradients, one float32 Adam step)."""
    _, _, params, _, _ = tiny
    pool = batches(2, rows=2)
    try:
        _, got = fit_two_steps(tiny, devices, pool)
    finally:
        autodist_tpu.reset()
    want = ref.train_check(
        lambda p, b: ref.nll_sum(p, b, TOP_K, HELD), ref.batch_weight,
        params, pool[0], pool[1], jax.devices())
    close(got[0], want[0])
    close(got[1], want[1], DEEP_RTOL)


@pytest.mark.parametrize("tracing", [True, False])
def test_the_share_reaches_the_counters_only_under_telemetry(tiny, tracing):
    telemetry.configure("1" if tracing else None)
    try:
        telemetry.get_recorder().clear()
        runner, _ = fit_two_steps(tiny, 1, batches(2, rows=2))
        counters = telemetry.get_recorder().counters()
        gauges = telemetry.get_recorder().gauges() \
            if hasattr(telemetry.get_recorder(), "gauges") else None
    finally:
        telemetry.configure(None)
        autodist_tpu.reset()
    moe = {k: v for k, v in counters.items() if k.startswith("moe.")}
    if not tracing:
        assert not moe
        return
    assert set(moe) == {"moe.routed_pairs", "moe.chosen_pairs",
                        "moe.max_expert_pairs"}
    # two steps x four routed layers x 2 x 32 tokens x top-4
    assert moe["moe.chosen_pairs"] == 2 * 4 * 2 * SEQ * TOP_K
    assert 0 < moe["moe.routed_pairs"] < moe["moe.chosen_pairs"]
    assert gauges is None or gauges.get("model.remat_blocks") == 0


def test_olmoes_loss_declares_the_counters_it_declared_before():
    loss_fn = lm.make_train_setup(tiny_olmoe(), seq_len=8, batch_size=2)[0]
    assert loss_fn.device_counters == ("moe.max_expert_pairs",
                                       "moe.routed_pairs")
    share = lm.make_train_setup(tiny_config(num_layers=2), seq_len=8,
                                batch_size=2)[0]
    assert share.device_counters == (
        "moe.max_expert_pairs", "moe.routed_pairs", "moe.chosen_pairs")
    dense_only = lm.make_train_setup(
        tiny_config(num_layers=1), seq_len=8, batch_size=2)[0]
    assert not hasattr(dense_only, "device_counters")


# ----------------------------------------------- per-block recompute, head


def test_recomputed_blocks_give_the_same_loss_and_gradients(tiny):
    cfg, _, params, _, batch = tiny
    ids, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]

    def loss(model, p):
        logits, sown = model.apply(p, ids, mutable=["counters"])
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                     targets[..., None], axis=-1)
        return -jnp.mean(picked), sown["counters"]

    with jax.default_matmul_precision("highest"):
        (want, load), want_g = jax.jit(jax.value_and_grad(functools.partial(
            loss, lm.TransformerLM(cfg)), has_aux=True))(params)
        (got, got_load), got_g = jax.jit(jax.value_and_grad(functools.partial(
            loss, lm.TransformerLM(cfg, remat_blocks=True)), has_aux=True))(
            params)
    close(got, want)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.all(a == b)), got_load, load))
    got_g, want_g = flat(got_g), flat(want_g)
    for name in got_g:
        close(got_g[name], want_g[name], DEEP_RTOL)
    remats = [str(jax.make_jaxpr(lambda p: model.apply(
        p, ids, mutable=["counters"])[0])(params)).count("remat2[")
        for model in (lm.TransformerLM(cfg),
                      lm.TransformerLM(cfg, remat_blocks=True))]
    assert remats[1] - remats[0] == 5   # one per block, beside the op's own


@pytest.mark.parametrize("against", ["blocks_not_recomputed", "jnp_form"])
def test_a_recomputed_block_of_wide_heads_runs_the_prologue_again_and_the_core_not(
        against, monkeypatch):
    """One KDA layer with a head of 128, its block recomputed: the
    gradient's jaxpr holds, by name, 1 ``kda_fwd``, 1 ``kda_bwd``, 2
    ``kda_pre_fwd`` and 1 ``kda_pre_bwd`` (the core's output and states
    are kept by name, its operands are made again), 2 ``kda_post_fwd`` and
    1 ``kda_post_bwd``. Loss and gradients are those of the same model
    with no block recomputed, and of the ``jnp`` form around the ``lax``
    core (what every head took until the passes were fused)."""
    from tests.test_flash_attention import kernel_calls
    cfg = tiny_config(num_layers=1, layer_types=("kda",), kda_num_heads=1,
                      kda_head_dim=128)
    params = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2, seed=0)[1]
    batch = batches(1)[0]
    ids, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]

    def run(remat_blocks):
        model = lm.TransformerLM(cfg, remat_blocks=remat_blocks)

        def loss(p):
            logits = model.apply(p, ids, mutable=["counters"])[0]
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), targets[..., None], axis=-1))

        with jax.default_matmul_precision("highest"):
            traced = jax.jit(jax.value_and_grad(loss)).trace(params)
            value, grads = traced.lower().compile()(params)
        return value, flat(grads), {
            name: kernel_calls(traced.jaxpr.jaxpr, name)
            for name in ("kda_fwd", "kda_bwd") + FUSED_PASSES}

    got, got_g, calls = run(True)
    assert calls == {"kda_fwd": 1, "kda_bwd": 1, "kda_pre_fwd": 2,
                     "kda_pre_bwd": 1, "kda_post_fwd": 2, "kda_post_bwd": 1}
    if against == "jnp_form":
        monkeypatch.setattr(kda_op, "runs_as_kernels", lambda dk, dv: False)
    want, want_g, want_calls = run(False)
    assert set(want_calls.values()) == ({0} if against == "jnp_form" else {1})
    close(got, want)
    for name in got_g:
        close(got_g[name], want_g[name], DEEP_RTOL)


def check_recomputed_flash_blocks(cfg, params, batch, against, monkeypatch,
                                 flash_layers, rtol):
    """``cfg``'s model with every block recomputed and the latent layers'
    cores on the flash kernel: its gradient holds one ``flash_fwd`` a
    flash layer, the forward pass's. Against the same model without
    recomputation ("blocks_not_recomputed") loss and gradients agree to
    ``rtol``; against the kernel's residuals left without their name, as
    before the name existed ("unnamed"), the recomputed blocks run the
    kernel again and loss and every gradient leaf are equal to the last
    bit (what is kept is what the second kernel call would have written)."""
    from autodist_tpu.ops import flash_attention as fa
    from tests.test_flash_attention import kernel_calls
    ids, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    attn_fn = fa.make_flash_attn_fn(causal=True)

    def run(remat_blocks):
        model = lm.TransformerLM(cfg, attn_fn=attn_fn,
                                 remat_blocks=remat_blocks)

        def loss(p):
            logits = model.apply(p, ids, mutable=["counters"])[0]
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), targets[..., None], axis=-1))

        with jax.default_matmul_precision("highest"):
            traced = jax.jit(jax.value_and_grad(loss)).trace(params)
            value, grads = traced.lower().compile()(params)
        return value, flat(grads), kernel_calls(traced.jaxpr.jaxpr,
                                                "flash_fwd")

    got, got_g, calls = run(True)
    unnamed = against == "unnamed"
    if unnamed:
        monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    want, want_g, want_calls = run(unnamed)
    assert calls == flash_layers
    assert want_calls == (2 * flash_layers if unnamed else flash_layers)
    if unnamed:
        assert got == want
    else:
        close(got, want)
    for name in got_g:
        if unnamed:
            np.testing.assert_array_equal(got_g[name], want_g[name])
        else:
            close(got_g[name], want_g[name], rtol)


@pytest.mark.parametrize("against", ["blocks_not_recomputed", "unnamed"])
def test_a_recomputed_block_runs_no_flash_forward_kernel(tiny, against,
                                                         monkeypatch):
    """One latent layer of five."""
    cfg, _, params, _, batch = tiny
    check_recomputed_flash_blocks(cfg, params, batch, against, monkeypatch,
                                  cfg.layer_types.count("mla"), DEEP_RTOL)


def test_serving_refuses_layers_whose_state_it_cannot_cache(tiny):
    cfg, _, params, _, batch = tiny
    with pytest.raises(NotImplementedError, match="recurrent state"):
        lm.TransformerLM(cfg).apply(
            params, batch["tokens"][:, :8], jnp.full((2,), 8),
            method=lm.TransformerLM.prefill)
