"""Chunked softmax cross-entropy vs the standard log_softmax path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.kernel.common import op_info
from autodist_tpu.ops.xent import _layout, chunked_softmax_xent


def _ref_nll(x, w, b, targets):
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32) + b
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]


def _walk(jaxpr, visit):
    """``visit(eqn)`` on every equation, sub-jaxprs (scan bodies, the
    custom_vjp's calls) included."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in op_info.sub_jaxprs(eqn):
            _walk(sub, visit)


def _plan_columns(v, chunk):
    """``_chunk_view``'s offsets and dead masks replayed in numpy: per
    chunk, the vocabulary columns it holds LIVE."""
    width, n = _layout(v, chunk)
    live = []
    for ci in range(n):
        off = ci * width
        start = min(off, v - width)
        cols = start + np.arange(width)
        live.append(cols[cols >= off])
    return width, n, live


@pytest.mark.parametrize("v,chunk,expect", [
    (99183, 8192, (7680, 13)), (4096, 512, (512, 8)), (777, 256, None),
    (1000, 1000, (1000, 1)), (50257, 8192, None), (200000, 8192, None),
    (12397, 1024, None), (3001, 512, None), (300, 8192, (300, 1)),
    (129, 128, (128, 2))])
def test_layout_covers_every_column_once(v, chunk, expect):
    """The plan keeps ``chunk`` as the ceiling and the fewest chunks it
    allows, sizes them to the vocabulary in 128-lane steps, and the
    clamped reads with their dead masks visit every column once."""
    width, n, live = _plan_columns(v, chunk)
    if expect is not None:
        assert (width, n) == expect
    assert n == -(-v // chunk) and 0 < width <= min(chunk, v)
    assert width % 128 == 0 or width in (v, chunk)
    assert 0 <= n * width - v < 129 * n
    assert all(len(c) for c in live), "a chunk with no live column"
    seen = np.concatenate(live)
    np.testing.assert_array_equal(np.sort(seen), np.arange(v))


@pytest.mark.parametrize("vocab,chunk", [(1000, 256), (1000, 1000),
                                         (777, 256), (512, 512),
                                         (12397, 1024), (3001, 512)])
def test_matches_reference_fwd_and_grad(vocab, chunk):
    """Exact same nll and grads as log_softmax+gather, including the
    ragged final chunk (vocab not a chunk multiple, nor of 128)."""
    rng = np.random.RandomState(0)
    n, d = 64, 32
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, vocab) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(vocab) * 0.1, jnp.float32)
    t = jnp.asarray(rng.randint(0, vocab, (n,)), jnp.int32)

    nll = chunked_softmax_xent(x, w, b, t, chunk)
    np.testing.assert_allclose(nll, _ref_nll(x, w, b, t), rtol=1e-5,
                               atol=1e-5)

    def loss_c(x, w, b):
        return jnp.mean(chunked_softmax_xent(x, w, b, t, chunk))

    def loss_r(x, w, b):
        return jnp.mean(_ref_nll(x, w, b, t))

    gc = jax.grad(loss_c, (0, 1, 2))(x, w, b)
    gr = jax.grad(loss_r, (0, 1, 2))(x, w, b)
    for a, bb in zip(gc, gr):
        np.testing.assert_allclose(a, bb, rtol=2e-4, atol=1e-6)


def test_bf16_activations():
    """bf16 activations (the LM's dtype) accumulate in fp32."""
    rng = np.random.RandomState(1)
    n, d, vocab = 32, 16, 300
    x = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    w = jnp.asarray(rng.randn(d, vocab) * 0.1, jnp.bfloat16)
    b = jnp.asarray(np.zeros(vocab), jnp.float32)
    t = jnp.asarray(rng.randint(0, vocab, (n,)), jnp.int32)
    nll = chunked_softmax_xent(x, w, b, t, 128)
    ref = _ref_nll(x, w, b, t)
    np.testing.assert_allclose(nll, ref, rtol=2e-2, atol=2e-2)
    g = jax.grad(lambda w: jnp.mean(chunked_softmax_xent(x, w, b, t, 128)))(w)
    assert g.dtype == jnp.bfloat16 and bool(jnp.isfinite(
        g.astype(jnp.float32)).all())


def test_no_full_logits_in_program():
    """The jaxpr never holds an [N, V] buffer — the memory property the
    op exists for (V=4096, chunk=512: biggest vocab-dim tensor is the
    [N, 512] chunk; weight-shaped [D, V] tensors are params/grads)."""
    n, d, vocab, chunk = 128, 64, 4096, 512
    x = jnp.zeros((n, d), jnp.float32)
    w = jnp.zeros((d, vocab), jnp.float32)
    b = jnp.zeros((vocab,), jnp.float32)
    t = jnp.zeros((n,), jnp.int32)

    def loss(x, w, b):
        return jnp.mean(chunked_softmax_xent(x, w, b, t, chunk))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, w, b)
    shapes = set()

    def visit(eqn):
        for v in list(eqn.outvars) + list(eqn.invars):
            shape = tuple(getattr(getattr(v, "aval", None), "shape", ()))
            if shape:
                shapes.add(shape)
    _walk(jaxpr.jaxpr, visit)
    assert (n, vocab) not in shapes, "full logits materialized"
    assert any(s[-1] == chunk and s[0] in (n,) for s in shapes
               if len(s) == 2), shapes
    # the weights are read in place: no stacked [nchunks, D, C] copy of W
    assert (vocab // chunk, d, chunk) not in shapes, "chunked W copy"


def test_lm_lean_head_matches_standard_loss():
    """The LM's lean-head loss equals the standard log_softmax loss and
    trains identically (same grads to float tolerance)."""
    import optax
    from autodist_tpu.models import lm
    cfg = lm.LMConfig.tiny()
    lf_lean, p1, batch, _ = lm.make_train_setup(cfg, seq_len=16,
                                                batch_size=4,
                                                attention="default",
                                                lean_head=True)
    lf_std, p2, _, _ = lm.make_train_setup(cfg, seq_len=16, batch_size=4,
                                           attention="default",
                                           lean_head=False)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), p1, p2)
    # (each as ONE program: op by op the two losses and their gradients
    # were hundreds of small compiles)
    np.testing.assert_allclose(float(jax.jit(lf_lean)(p1, batch)),
                               float(jax.jit(lf_std)(p2, batch)), rtol=1e-5)
    g1 = jax.jit(jax.grad(lf_lean))(p1, batch)
    g2 = jax.jit(jax.grad(lf_std))(p2, batch)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-4, atol=1e-5), g1, g2)


def test_out_of_vocab_target_clamps_like_reference():
    """An out-of-range token id clamps to vocab-1 exactly as the standard
    take_along_axis path does — no silent nll = lse."""
    rng = np.random.RandomState(2)
    n, d, vocab = 16, 8, 100
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, vocab) * 0.1, jnp.float32)
    b = jnp.zeros((vocab,), jnp.float32)
    t = jnp.asarray([vocab + 5] * n, jnp.int32)  # all out of range
    nll = chunked_softmax_xent(x, w, b, t, 32)
    ref = _ref_nll(x, w, b, jnp.clip(t, 0, vocab - 1))
    np.testing.assert_allclose(nll, ref, rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda w: jnp.mean(chunked_softmax_xent(x, w, b, t, 32)))(w)
    gr = jax.grad(lambda w: jnp.mean(_ref_nll(
        x, w, b, jnp.clip(t, 0, vocab - 1))))(w)
    np.testing.assert_allclose(g, gr, rtol=2e-4, atol=1e-6)


def _ragged_case():
    """(x, w, b, vocab, chunk) whose final chunk is clamped: 4 x 256
    columns for 777 words."""
    n, d, vocab, chunk = 48, 16, 777, 256
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, vocab) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(vocab) * 0.1, jnp.float32)
    return x, w, b, vocab, chunk


def test_target_picked_without_a_gather():
    """The target's logit is a masked row sum riding on the exp pass:
    neither the forward nor the backward program holds a gather (on the
    TPU one cost 4 ms a step, a scalar pick per token per chunk)."""
    x, w, b, vocab, chunk = _ragged_case()
    t = jnp.zeros((x.shape[0],), jnp.int32)

    def loss(x, w, b):
        return jnp.mean(chunked_softmax_xent(x, w, b, t, chunk))

    for fn in (loss, jax.grad(loss, (0, 1, 2))):
        prims = set()
        _walk(jax.make_jaxpr(fn)(x, w, b).jaxpr,
              lambda eqn: prims.add(eqn.primitive.name))
        assert "dot_general" in prims, prims
        assert not {p for p in prims if "gather" in p}, prims


@pytest.mark.parametrize("where", ["first_live", "last_live", "overlap",
                                   "before_clamped_start", "all_edges"])
def test_targets_around_the_clamped_chunk(where):
    """The final chunk of a ragged vocabulary is read at the clamped
    offset ``v - width``: its first live column, its last, a column in
    its dead overlap (live in the chunk before) and the column just under
    its start all give the reference's nll and gradients."""
    x, w, b, vocab, chunk = _ragged_case()
    width, n, live = _plan_columns(vocab, chunk)
    start = vocab - width
    assert start < live[-1][0], "the case has no dead overlap"
    cols = {"first_live": [live[-1][0]], "last_live": [vocab - 1],
            "overlap": [start, live[-1][0] - 1],
            "before_clamped_start": [start - 1]}
    cols["all_edges"] = sorted(
        {c for cs in cols.values() for c in cs}
        | {c[0] for c in live} | {c[-1] for c in live})
    t = jnp.asarray(np.resize(cols[where], x.shape[0]), jnp.int32)

    np.testing.assert_allclose(chunked_softmax_xent(x, w, b, t, chunk),
                               _ref_nll(x, w, b, t), rtol=1e-5, atol=1e-5)
    gc = jax.grad(lambda *a: jnp.mean(
        chunked_softmax_xent(*a, t, chunk)), (0, 1, 2))(x, w, b)
    gr = jax.grad(lambda *a: jnp.mean(_ref_nll(*a, t)), (0, 1, 2))(x, w, b)
    for a, bb in zip(gc, gr):
        np.testing.assert_allclose(a, bb, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("vocab,chunk", [(777, 256), (4096, 512),
                                         (300, 8192)])
def test_plan_gauges_set_at_trace_time(vocab, chunk):
    """One trace of the loss leaves the plan in the ``lean_head.*``
    gauges: how many chunks, how wide, how many columns computed dead."""
    telemetry.reset()
    assert not [k for k in telemetry.get_recorder().gauges()
                if k.startswith("lean_head.")]
    x = jnp.zeros((8, 4), jnp.float32)
    w = jnp.zeros((4, vocab), jnp.float32)
    b = jnp.zeros((vocab,), jnp.float32)
    t = jnp.zeros((8,), jnp.int32)
    jax.make_jaxpr(lambda x: jnp.mean(
        chunked_softmax_xent(x, w, b, t, chunk)))(x)
    width, n = _layout(vocab, chunk)
    gauges = telemetry.get_recorder().gauges()
    assert gauges["lean_head.chunks"] == n
    assert gauges["lean_head.chunk_width"] == width
    assert gauges["lean_head.dead_cols"] == n * width - vocab
