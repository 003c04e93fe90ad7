"""``ops/kda.py`` against the plain float32 reference
``benchmark/reference/kimi_linear.py`` and against its own ``lax`` and
``jnp`` forms: the chunked delta rule against the token-by-token
recurrence, the kernels' gradients against autodiff of the ``lax`` form, the
head width's choice of rendering and the gauges that report it, the mixer's
fused prologue and epilogue, the halo and the overflow the sub-blocks are
for. (``tests/test_kimi_linear.py`` holds the model that uses them; the
tolerances are its.)"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import layers, lm
from autodist_tpu.ops import kda as kda_op
from benchmark.reference import kimi_linear as ref
from tests.test_kimi_linear import (DEEP_RTOL, FUSED_PASSES, RTOL, close,
                                    tiny_config, tiny_olmoe)


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


@pytest.mark.parametrize("gauge", ["attention.kda_kernel_layers"])
@pytest.mark.parametrize("cell", sorted(GAUGE_CASES))
def test_the_gauge_says_how_many_layers_took_the_kda_kernels(cell, gauge):
    """``attention.kda_kernel_layers`` (the delta rule as kernels, and with
    it the element-wise passes around it fused: one rule for both) is set
    when the loss is traced, from what ``runs_as_kernels`` said of the
    configuration's KDA heads."""
    assert gauges_of_a_traced_loss(cell)[gauge] == GAUGE_CASES[cell][1]


@functools.lru_cache(maxsize=None)
def gauges_of_a_traced_loss(cell):
    loss_fn, params, batch, _ = lm.make_train_setup(
        GAUGE_CASES[cell][0](), seq_len=16, batch_size=1, seed=0)
    telemetry.reset()
    jax.eval_shape(loss_fn, params, batch)
    return dict(telemetry.get_recorder().gauges())


# ------------------ the mixer's fused passes against the ``jnp`` form


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
