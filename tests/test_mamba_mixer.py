"""``ops/ssd.py``'s passes around the scan (``mamba_pre_fwd`` /
``mamba_pre_bwd``, ``mamba_post_fwd`` / ``mamba_post_bwd``) at the published
widths (64 heads of 64 over 8 groups of 128 states, a filter of 4 taps),
interpreted on the CPU a grid step at a time, in token tiles of 128: against
``models/layers.py:mamba_inputs`` / ``mamba_output``, the ``jnp`` form a
narrower mixer runs (the oracle: float32 differs by the order of sums,
bfloat16 by the oracle's rounding after every op, which the kernels do not
make), values and every gradient; a sequence of several tiles (the filter
reaches over a tile's edge in both directions, and reads zeros before token
0) and one that is not whole chunks; the whole mixer fused against unfused;
the shapes' choice. (``tests/test_ssd_kernels.py`` sends the four kernels
through Mosaic at the cell's sizes.)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import layers
from autodist_tpu.ops import ssd
from tests.test_flash_attention import kernel_calls
from tests.test_kimi_linear import RTOL, close

H, P, G, N, TAPS, CHUNK = 64, 64, 8, 128, 4, 128    # the published widths
INNER, CONV = H * P, H * P + 2 * G * N
CFG = layers.Mamba2Config(H, P, G, N, TAPS, CHUNK)
TILE = 128
BF16_RTOL = 5e-2
EPS = 1e-5
DTYPES = {"float32": (jnp.float32, RTOL), "bfloat16": (jnp.bfloat16,
                                                      BF16_RTOL)}
# three tiles of whole chunks; two chunks and 44 tokens of a third
SEQS = {"three_tiles": 3 * TILE, "a_tail": 300}
PRE_OUT = ("x", "dt", "dtA", "B", "C")
PRE_LEAVES = ("xBC_and_dt", "filter", "bias", "dt_bias", "A_log")
POST_LEAVES = ("y", "z", "norm_weight")


def operands(seq, dtype, seed=0):
    """(``in_proj``'s output [1, seq, rows], filter, bias, dt_bias, A_log,
    the norm's weight, a scan's y [1, seq, H, P])."""
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)  # noqa: E731
    return ((0.7 * f(1, seq, INNER + CONV + H)).astype(dtype),
            0.4 * f(TAPS, CONV), 0.1 * f(CONV), 0.3 * f(H), 0.2 * f(H),
            1.0 + 0.1 * f(INNER), f(1, seq, H, P).astype(dtype))


def tokens_first(t, seq):
    """The scan's layout [B, G, rows, S'] -> [B, seq, G, rows]."""
    return jnp.moveaxis(t, -1, 1)[:, :seq]


def pre_oracle(dtype, zx, w, b, dt_bias, a_log):
    x, dt, a, bs, cs, _ = layers.mamba_inputs(zx, w, b, dt_bias, a_log, CFG,
                                              dtype)
    return x, dt, dt * a, bs, cs


def pre_fused(dtype, zx, w, b, dt_bias, a_log):
    seq = zx.shape[1]
    x, dt, dta, bs, cs, _ = ssd.mamba_pre(
        jnp.moveaxis(zx, 1, 2), w, b, dt_bias, a_log, G, N, CHUNK, dtype,
        TILE)
    assert x.shape == (1, G, H // G * P, seq + -seq % CHUNK)
    return (tokens_first(x, seq).reshape(1, seq, H, P),
            tokens_first(dt, seq).reshape(1, seq, H),
            tokens_first(dta, seq).reshape(1, seq, H),
            tokens_first(bs, seq), tokens_first(cs, seq))


def post_oracle(dtype, y, zx, scale):
    return layers.mamba_output(y, zx[..., :INNER], scale, G, EPS, dtype)


def post_fused(dtype, y, zx, scale):
    seq = y.shape[1]
    y = jnp.pad(y.reshape(1, seq, G, -1),
                [(0, 0), (0, -seq % CHUNK), (0, 0), (0, 0)])
    return jnp.moveaxis(ssd.mamba_post(
        jnp.moveaxis(y, 1, -1), jnp.moveaxis(zx, 1, 2), scale, EPS, dtype,
        TILE), 1, 2)


@functools.lru_cache(maxsize=None)
def both(which, dtype_name, seq_name):
    """{rendering: (the outputs, the gradients of a weighted sum of them)}
    of the passes before (``pre``) or after (``post``) the scan, computed
    once a (dtype, sequence)."""
    dtype, seq = DTYPES[dtype_name][0], SEQS[seq_name]
    zx, w, b, dt_bias, a_log, scale, y = operands(seq, dtype)
    forms, args = ((pre_oracle, pre_fused), (zx, w, b, dt_bias, a_log)) \
        if which == "pre" else ((post_oracle, post_fused), (y, zx, scale))
    out = {}
    for form in forms:
        form = functools.partial(form, dtype)
        r = np.random.RandomState(1)    # the same weights for both
        weights = [jnp.asarray(r.randn(*t.shape), jnp.float32)
                   for t in jax.tree_util.tree_leaves(
                       jax.eval_shape(form, *args))]

        def loss(*t):
            got = form(*t)
            return sum(jnp.sum(g.astype(jnp.float32) * wt) for g, wt in zip(
                jax.tree_util.tree_leaves(got), weights)), got

        (_, got), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
        out[form.func.__name__.split("_")[1]] = (
            jax.tree_util.tree_leaves(got), grads)
    return out


# ---------------------------------------------------- before the scan


@pytest.mark.parametrize("seq", sorted(SEQS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("out", PRE_OUT)
def test_mamba_pre_writes_the_scans_operands(out, dtype, seq):
    """x, B, C = silu(filter(xBC) + bias) in the model's dtype, dt =
    softplus(dt + dt_bias) and dt A in float32, in the scan's own layouts,
    zeros past the sequence's end."""
    got, want = (both("pre", dtype, seq)[form][0][PRE_OUT.index(out)]
                 for form in ("fused", "oracle"))
    assert got.shape == want.shape
    assert got.dtype == want.dtype == (
        jnp.float32 if out in ("dt", "dtA") else DTYPES[dtype][0])
    close(got.astype(jnp.float32), want.astype(jnp.float32),
          DTYPES[dtype][1])


@pytest.mark.parametrize("seq", sorted(SEQS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("leaf", PRE_LEAVES)
def test_mamba_pre_bwd_is_autodiff_of_mamba_inputs(leaf, dtype, seq):
    """xBC's and dt's rows of ``in_proj``'s output (a token's gradient
    comes from the three tokens after it, over a tile's edge too), the
    filter, its bias, ``dt_bias`` and ``A_log`` (the kernel's sums a tile,
    added up outside)."""
    got, want = (both("pre", dtype, seq)[form][1][PRE_LEAVES.index(leaf)]
                 for form in ("fused", "oracle"))
    if leaf == "xBC_and_dt":     # z's rows are ``mamba_post``'s to write
        got, want = got[..., INNER:], want[..., INNER:]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(np.asarray(want, np.float32)).max() > 0
    close(got.astype(jnp.float32), want.astype(jnp.float32),
          DTYPES[dtype][1])


def test_the_filter_reads_zeros_before_token_0_and_the_tile_before_after():
    """The first tokens of every tile, float32: token 0's are the last tap
    alone, a later tile's reach into the tile before."""
    zx, w, b, dt_bias, a_log, _, _ = operands(3 * TILE, jnp.float32)
    x = pre_fused(jnp.float32, zx, w, b, dt_bias, a_log)[0]
    xbc = zx[0, :, INNER:INNER + INNER]
    first = jax.nn.silu(xbc[0] * w[-1, :INNER] + b[:INNER])
    close(x[0, 0].reshape(-1), first)
    at = 2 * TILE
    edge = jax.nn.silu(jnp.sum(xbc[at - 3:at + 1] * w[:, :INNER], axis=0)
                       + b[:INNER])
    close(x[0, at].reshape(-1), edge)


def test_rows_past_the_sequences_end_are_idle_and_draw_no_gradient():
    """300 tokens in three chunks: the 84 rows of padding are zeros (dt = 0
    neither decays nor writes), whatever the cotangent there."""
    zx, w, b, dt_bias, a_log, _, _ = operands(300, jnp.float32)
    outs = ssd.mamba_pre(jnp.moveaxis(zx, 1, 2), w, b, dt_bias, a_log, G, N,
                         CHUNK, jnp.float32, TILE)
    for t in outs[:5]:
        assert t.shape[-1] == 3 * CHUNK
        np.testing.assert_array_equal(np.asarray(t[..., 300:]), 0.0)
    grad = jax.grad(lambda t: sum(jnp.sum(o) for o in ssd.mamba_pre(
        t, w, b, dt_bias, a_log, G, N, CHUNK, jnp.float32, TILE)[:5]))(
        jnp.moveaxis(zx, 1, 2))
    assert grad.shape == (1, INNER + CONV + H, 300)
    assert np.all(np.isfinite(np.asarray(grad[:, INNER:])))


# ----------------------------------------------------- after the scan


@pytest.mark.parametrize("seq", sorted(SEQS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_mamba_post_is_the_gated_grouped_norm(dtype, seq):
    (got,), (want,) = (both("post", dtype, seq)[form][0]
                       for form in ("fused", "oracle"))
    assert got.shape == want.shape and got.dtype == DTYPES[dtype][0]
    close(got.astype(jnp.float32), want.astype(jnp.float32),
          DTYPES[dtype][1])


@pytest.mark.parametrize("seq", sorted(SEQS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("leaf", POST_LEAVES)
def test_mamba_post_bwd_is_autodiff_of_mamba_output(leaf, dtype, seq):
    """The scan's y, z's rows of ``in_proj``'s output and the norm's
    weight, from the same three inputs (nothing new is kept)."""
    got, want = (both("post", dtype, seq)[form][1][POST_LEAVES.index(leaf)]
                 for form in ("fused", "oracle"))
    if leaf == "z":              # the other rows are ``mamba_pre``'s
        got, want = got[..., :INNER], want[..., :INNER]
    assert got.shape == want.shape and got.dtype == want.dtype
    close(got.astype(jnp.float32), want.astype(jnp.float32),
          DTYPES[dtype][1])


# -------------------------------------------------- the mixer, whole


@pytest.fixture(scope="module")
def mixers():
    """A mixer of the published widths on 24 features, two rows of 300
    tokens, float32:
    {rendering: (output, the gradients of its weighted sum by leaf, the
    loss's jaxpr)}; unfused is the scan's kernels between the ``jnp``
    passes, which is what the parent ran."""
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(2, 300, 24), jnp.float32)
    weight = jnp.asarray(r.randn(2, 300, 24), jnp.float32)
    mixer = layers.Mamba2Mixer(CFG, EPS)
    params = mixer.init(jax.random.PRNGKey(0), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jnp.asarray(r.randn(*p.shape), p.dtype), params)

    def loss(params, x):
        out = mixer.apply({"params": params}, x, mutable=["counters"])
        return jnp.sum(out[0] * weight), out

    def run():
        with jax.default_matmul_precision("highest"):
            (_, (out, sown)), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(params, x)
        return (out, dict(grads[0], x=grads[1]),
                sown["counters"]["chunk_carry"][0],
                jax.make_jaxpr(jax.grad(lambda *t: loss(*t)[0]))(params, x))

    fused = run()
    chosen = ssd.mixer_runs_fused
    ssd.mixer_runs_fused = lambda *shape: False
    try:
        return {"fused": fused, "unfused": run()}
    finally:
        ssd.mixer_runs_fused = chosen


@pytest.mark.parametrize("leaf", ["out", "x", "in_proj", "out_proj", "conv",
                                  "conv_bias", "A_log", "D", "dt_bias",
                                  "norm"])
def test_the_fused_mixer_is_the_unfused_one(mixers, leaf):
    (out, grads, _, _), (want_out, want_grads, _, _) = (
        mixers[form] for form in ("fused", "unfused"))
    got, want = (out, want_out) if leaf == "out" else (
        jax.tree_util.tree_leaves(g[leaf])[0] for g in (grads, want_grads))
    assert got.shape == want.shape and np.abs(want).max() > 0
    close(got, want)


def test_the_fused_mixer_holds_the_four_passes_and_sows_the_same_carry(
        mixers):
    (_, _, carry, jaxpr), (_, _, want, unfused) = (
        mixers[form] for form in ("fused", "unfused"))
    np.testing.assert_allclose(float(carry), float(want), rtol=1e-5)
    for name, here in (("mamba_pre_fwd", 1), ("mamba_pre_bwd", 1),
                       ("mamba_post_fwd", 1), ("mamba_post_bwd", 1),
                       ("ssd_fwd", 1), ("ssd_bwd", 1)):
        assert kernel_calls(jaxpr.jaxpr, name) == here, name
    for name in ("mamba_pre_fwd", "mamba_post_bwd"):
        assert kernel_calls(unfused.jaxpr, name) == 0
    assert kernel_calls(unfused.jaxpr, "ssd_bwd") == 1


def kernel_scopes(jaxpr, outer=""):
    """(kernel's name, the name stack it was traced under) of every
    ``pallas_call`` of a jaxpr and its inner ones."""
    for eqn in jaxpr.eqns:
        here = "%s/%s" % (outer, eqn.source_info.name_stack)
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], here
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from kernel_scopes(inner, here)


def test_the_passes_sit_under_mamba_and_the_scan_alone_under_ssd_scan():
    """Forward and backward rules of both ``custom_vjp``s keep the mixer's
    name stack: ``mamba_ms_per_step`` holds all four kernels and
    ``ssd_scan_ms_per_step`` reads the recurrence alone."""
    from autodist_tpu.telemetry import scopes
    x = jnp.zeros((1, CHUNK, 24), jnp.float32)
    mixer = layers.Mamba2Mixer(CFG, EPS)
    params = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)["params"]

    def loss(params, x):
        with scopes.scope(scopes.MAMBA):
            return jnp.sum(mixer.apply({"params": params}, x,
                                       mutable=["counters"])[0])

    found = dict(kernel_scopes(jax.make_jaxpr(jax.grad(loss))(params,
                                                              x).jaxpr))
    assert sorted(found) == ["mamba_post_bwd", "mamba_post_fwd",
                             "mamba_pre_bwd", "mamba_pre_fwd", "ssd_bwd",
                             "ssd_fwd"]
    for name, stack in found.items():
        # a component with its transform wrappers off: jvp(mamba) -> mamba
        inside = [part.rsplit("(", 1)[-1].rstrip(")")
                  for part in stack.split("/")]
        assert scopes.MAMBA in inside, (name, stack)
        assert (scopes.SSD_SCAN in inside) == name.startswith("ssd_"), (
            name, stack)
        assert ("transpose(" in stack) == name.endswith("_bwd"), (name, stack)


# ------------------------------------------------ the shapes' choice


@pytest.mark.parametrize("shape, fused", [
    ((64, 128, 8, 8, 128, 4), True),     # the published widths
    ((64, 128, 8, 16, 128, 4), True),    # twice the groups
    ((64, 128, 8, 8, 128, 2), True),     # a filter of two taps
    ((8, 16, 2, 2, 8, 4), False),        # tests/test_nemotron_h.py's tiny
    ((64, 128, 8, 8, 64, 4), False),     # the scan is no kernel
    ((64, 128, 8, 3, 128, 4), False),    # B's rows no whole blocks of 512
    ((16, 128, 8, 32, 128, 4), False),   # dt's 256 rows over a block of 128
    ((64, 128, 8, 8, 128, 1), False),    # no filter
    ((64, 128, 8, 8, 128, 200), False)])  # a filter past the tile before
def test_the_shapes_pick_the_fused_mixer(shape, fused):
    """(head_dim, states, heads a group, groups, chunk, taps): fused where
    the scan runs as kernels AND the rows of ``in_proj``'s output split at
    the edges of a group's block."""
    assert ssd.mixer_runs_fused(*shape) is fused
    if fused:
        assert ssd.runs_as_kernels(*shape[:3], shape[4])
