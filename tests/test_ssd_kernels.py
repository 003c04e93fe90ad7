"""``ops/ssd.py``'s pallas kernels (``ssd_fwd`` / ``ssd_bwd``) at the
published head shape (8 heads of 64 a group, 128 states, chunks of 128),
interpreted on the CPU a grid step at a time, so with few groups and two or
three chunks: against the file's own ``jnp`` form (the oracle: same
arithmetic, other order of sums) and against the token-by-token recurrence
of ``benchmark/reference/nemotron_h.py``; the shapes' choice of rendering,
the name a recomputed block keeps, and both kernels, and the mixer's four
passes around them (``tests/test_mamba_mixer.py`` has their values), through
Mosaic for a described v5e at the cell's sizes. (``tests/test_nemotron_h.py`` holds the
``jnp`` form and the model to the reference at the tiny widths.)

Tolerances, of the largest entry: float32 differs by the order of sums
(``RTOL``, the model tests'); with bfloat16 operands by bfloat16's rounding
(5e-2, ``tests/test_kda.py``'s and ``tests/test_flash_attention.py``'s).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import lm
from autodist_tpu.ops import ssd
from benchmark.reference import nemotron_h as ref
from tests.test_flash_attention import kernel_calls
from tests.test_kimi_linear import RTOL, close
from tests.test_nemotron_h import tiny_config

P, N, K, CHUNK = 64, 128, 8, 128     # the published head shape
BF16_RTOL = 5e-2
LEAVES = ("x", "dt", "A", "B", "C", "D")


def inputs(seq, seed=0, B=1, G=2):
    """``ssd_chunked``'s operands with G groups of the published heads: B
    and C at the size a normed projection gives them, dt and A as seeded
    parameters give them (a chunk forgets a good part, not all)."""
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)  # noqa: E731
    H = G * K
    return (f(B, seq, H, P), jnp.abs(f(B, seq, H)) * 0.05 + 0.001,
            -jnp.abs(f(H)) - 0.2, f(B, seq, G, N) * 0.3,
            f(B, seq, G, N) * 0.3, f(H))


def value_and_gradients(form, args, dtype, live=None):
    """(y, the six gradients of a weighted sum of y's first ``live`` rows)
    of one rendering."""
    weight = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)
                     ).reshape(args[0].shape)
    weight = weight * (jnp.arange(args[0].shape[1])
                       < (live or args[0].shape[1]))[:, None, None]

    def loss(*t):
        y = form(*t, CHUNK, dtype)[0]
        return jnp.sum(y.astype(jnp.float32) * weight), y

    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True))(*args)
    return (y,) + grads


@pytest.fixture(scope="module")
def two_chunks_and_a_tail():
    """300 tokens (two chunks and 44 of a third), float32: the kernels'
    and the ``jnp`` form's y and gradients, computed once."""
    args = inputs(300)
    assert ssd.runs_as_kernels(P, N, K, CHUNK)
    return {form: value_and_gradients(getattr(ssd, form), pad_to_chunks(args),
                                      jnp.float32, live=300)
            for form in ("_ssd_pallas", "_ssd_jnp")}


def pad_to_chunks(args):
    x, dt, a, b, c, d = args
    pad = -x.shape[1] % CHUNK
    x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                   for t in (x, dt, b, c))
    return x, dt, a, b, c, d


# ------------------------------------------- values against both oracles


@pytest.mark.parametrize("dtype, rtol", [(jnp.float32, RTOL),
                                         (jnp.bfloat16, BF16_RTOL)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("oracle", ["jnp_form", "recurrence"])
def test_the_kernels_y_and_carry_are_the_oracles(oracle, dtype, rtol):
    """Two whole chunks through ``ssd_chunked`` (the shapes pick the
    kernels): y and ``mamba_chunk_carry`` against the ``jnp`` form in the
    same ``dtype`` and against the float32 recurrence token by token."""
    args = inputs(2 * CHUNK, seed=1)
    with jax.default_matmul_precision("highest"):
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda *t: ssd.ssd_chunked(*t, CHUNK, dtype))(*args))
        got, carry = ssd.ssd_chunked(*args, CHUNK, dtype)
        if oracle == "jnp_form":
            want, totals = ssd._ssd_jnp(*args, CHUNK, dtype)
            want_carry = jnp.mean(jnp.exp(totals))
        else:
            want = ref.recurrence(*args)
            want_carry = np.mean(np.exp(np.sum(np.asarray(
                args[1] * args[2]).reshape(1, 2, CHUNK, -1), axis=2)))
    assert got.dtype == dtype and got.shape == args[0].shape
    close(got.astype(jnp.float32), want.astype(jnp.float32), rtol)
    np.testing.assert_allclose(float(carry), float(want_carry), rtol=1e-5)
    assert 0.0 < float(carry) < 1.0


# ------------------------------------- the six gradients, one case a leaf


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_backward_kernels_gradient_is_autodiffs_of_the_jnp_form(
        two_chunks_and_a_tail, leaf):
    """``ssd_bwd`` (``jax.vjp`` of the forward chunk in the kernel, the
    state's gradient carried in VMEM; A's and D's reduced outside) against
    XLA's autodiff of the ``jnp`` form, float32, a sequence that is not
    whole chunks: they differ by the order of sums."""
    i = 1 + LEAVES.index(leaf)
    got, want = (two_chunks_and_a_tail[form][i]
                 for form in ("_ssd_pallas", "_ssd_jnp"))
    assert got.shape == want.shape and np.abs(want).max() > 0
    close(got, want)


def test_a_sequence_that_is_not_whole_chunks_is_padded_with_idle_rows(
        two_chunks_and_a_tail):
    """y of the 300 tokens is the ``jnp`` form's and the recurrence's;
    the 84 rows of padding (dt = 0) neither decay nor write, so they draw
    no gradient."""
    got, want = (two_chunks_and_a_tail[form]
                 for form in ("_ssd_pallas", "_ssd_jnp"))
    close(got[0][:, :300], want[0][:, :300])
    close(got[0][:, :300], ref.recurrence(*inputs(300)))
    for g in got[1:3]:                     # dx, ddt past the sequence's end
        assert g.shape[1] == 3 * CHUNK
        np.testing.assert_array_equal(np.asarray(g[:, 300:]), 0.0)


@pytest.fixture(scope="module")
def bfloat16_gradients():
    args = inputs(2 * CHUNK, seed=2, G=1)
    return [value_and_gradients(form, args, jnp.bfloat16)
            for form in (ssd._ssd_pallas, ssd._ssd_jnp)]


@pytest.mark.parametrize("leaf", ["x", "B"])
def test_bfloat16_gradients_are_within_bfloat16s_rounding(
        bfloat16_gradients, leaf):
    """Operands AND cotangents of the kernels' products are bfloat16 (as
    XLA's default precision rounds the ``jnp`` form's on the chip);
    accumulation, decays and states stay float32."""
    got, want = (g[1 + LEAVES.index(leaf)] for g in bfloat16_gradients)
    close(got, want, BF16_RTOL)


# --------------------------------------------------------------- numerics


def test_a_chunk_that_decays_far_stays_finite_in_the_kernels():
    """Cumulative sums of -40 a token (-5,120 a chunk): the decay is the
    ``exp`` of masked DIFFERENCES in the kernel too, never a ratio of two
    exponentials, in value and in gradient."""
    x, dt, a, b, c, d = inputs(2 * CHUNK, seed=3, G=1)
    with jax.default_matmul_precision("highest"):
        got, carry = ssd.ssd_chunked(x, dt + 20.0, a - 2.0, b, c, d, CHUNK)
        close(got, ref.recurrence(x, dt + 20.0, a - 2.0, b, c, d))
        grads = jax.grad(lambda t, u: jnp.sum(ssd.ssd_chunked(
            x, t, u, b, c, d, CHUNK)[0]), argnums=(0, 1))(dt + 20.0, a - 2.0)
    assert all(np.all(np.isfinite(g)) for g in grads)
    assert float(carry) == 0.0


# -------------------------------------- the shapes' choice, the kept name


@pytest.mark.parametrize("shape, kernels", [
    ((64, 128, 8, 128), True),       # the published heads
    ((128, 128, 8, 256), True),      # heads of a whole tile, longer chunks
    ((8, 16, 2, 8), False),          # tests/test_nemotron_h.py's tiny widths
    ((64, 128, 8, 64), False),       # a chunk under a tile
    ((64, 64, 8, 128), False),       # states under a tile
    ((64, 128, 4, 128), False),      # a group's heads under a sublane tile
    ((40, 128, 8, 128), False)])     # a head's features no whole bf16 tile
def test_the_shapes_pick_the_rendering(shape, kernels):
    assert ssd.runs_as_kernels(*shape) is kernels


def test_the_tiny_model_keeps_the_jnp_form_and_says_so():
    """``tests/test_nemotron_h.py``'s widths (4 heads of 8 over 2 groups
    of 16 states, chunks of 8) run no kernel: gauge 0, no ``pallas_call``
    in the loss (``tests/test_lm_pins.py`` holds ``tiny_nemotron_h_step``'s
    program unmoved)."""
    cfg = tiny_config()
    assert not ssd.runs_as_kernels(
        cfg.mamba_head_dim, cfg.ssm_state_size,
        cfg.mamba_num_heads // cfg.mamba_n_groups, cfg.mamba_chunk)
    loss_fn, params, batch, _ = lm.make_train_setup(
        cfg, seq_len=16, batch_size=1, seed=0)
    telemetry.reset()
    assert "pallas_call" not in str(jax.make_jaxpr(loss_fn)(params, batch))
    gauges = telemetry.get_recorder().gauges()
    assert gauges["model.mamba_layers"] == 4
    assert gauges["model.ssd_kernel_layers"] == 0


@pytest.mark.parametrize("saved, forwards", [((ssd.KEPT,), 1), ((), 2)])
def test_a_recomputed_forward_that_keeps_the_name_runs_no_scan_kernel(
        saved, forwards):
    """Under ``save_only_these_names(ssd.KEPT)`` the gradient holds ONE
    ``ssd_fwd`` (y and the entering states are kept, the recomputed forward
    makes only the kernels' operands again) and one ``ssd_bwd``; with no
    name saved the forward kernel runs twice."""
    args = inputs(2 * CHUNK, G=1)
    scan = jax.checkpoint(
        lambda *t: ssd.ssd_chunked(*(2.0 * u for u in t), CHUNK)[0],
        policy=jax.checkpoint_policies.save_only_these_names(*saved))
    jaxpr = jax.make_jaxpr(jax.grad(lambda *t: jnp.sum(scan(*t)),
                                    argnums=tuple(range(6))))(*args).jaxpr
    assert kernel_calls(jaxpr, "ssd_fwd") == forwards
    assert kernel_calls(jaxpr, "ssd_bwd") == 1


def test_what_a_layer_keeps_by_closed_form():
    """y in the model's dtype and the float32 state that enters each chunk
    of the PADDED sequence: 67 + 134 MB a layer at the cell's sizes."""
    assert lm.ssd_kept_bytes(1, 8192, 64, 64, 128, 128) == (
        8192 * 4096 * 2 + 64 * 64 * 64 * 128 * 4) == 201326592
    y, states = jax.eval_shape(
        lambda *t: ssd._forward(*t, CHUNK, jnp.bfloat16, True),
        *(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
            ((2, 2, 512, 384), jnp.bfloat16), ((2, 2, 8, 384), jnp.float32),
            ((2, 2, 8, 384), jnp.float32), ((2, 2, 128, 384), jnp.bfloat16),
            ((2, 2, 128, 384), jnp.bfloat16), ((2, 8, 128), jnp.float32))))
    assert y.size * 2 + states.size * 4 == lm.ssd_kept_bytes(
        2, 300, 16, 64, 128, 128)


# ------------------------------ both kernels through Mosaic, for the cell


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 (no device attached)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to test
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def kernels_of_the_compiled_gradient(loss, args):
    """The names of the Mosaic calls in ``grad(loss)`` compiled by XLA:TPU
    for the described chip, sorted."""
    from autodist_tpu.ops import pallas_mode
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pallas_mode.compiling_for_tpu():
            text = jax.jit(jax.grad(
                loss, argnums=tuple(range(len(args))))).trace(*args).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    return sorted(line.split(" = ")[0].split("%")[-1].rsplit(".", 1)[0]
                  for line in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line)


def test_the_kernels_compile_for_a_v5e_at_the_cells_sizes(chip):
    """One sequence of 8,192 tokens, 64 heads of 64 over 8 groups of 128
    states in bfloat16: forward and backward through XLA:TPU and Mosaic for
    a described chip (a group step's eight decays, their gradients and the
    state fit VMEM; the transposes, the pads and the spreads of the
    chunk's ``jax.vjp`` lower)."""
    S, H, G = 8192, 64, 8
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in (((1, S, H, P), jnp.bfloat16),
                                 ((1, S, H), jnp.float32),
                                 ((H,), jnp.float32),
                                 ((1, S, G, N), jnp.bfloat16),
                                 ((1, S, G, N), jnp.bfloat16),
                                 ((H,), jnp.float32))]

    def loss(x, dt, a, b, c, d):
        return jnp.sum(ssd.ssd_chunked(x, dt, a, b, c, d, CHUNK,
                                       jnp.bfloat16)[0].astype(jnp.float32))

    assert kernels_of_the_compiled_gradient(loss, args) == [
        "ssd_bwd", "ssd_fwd"]


def test_the_mixers_passes_compile_for_a_v5e_at_the_cells_sizes(chip):
    """``in_proj``'s output of 8,192 tokens tokens last ([1, 10304, 8192]
    bfloat16) through ``mamba_pre``, the scan and ``mamba_post`` at the
    tile the program uses: the lane rolls of the filter, the row loops and
    the blocks that a step leaves as they were lower, and the forward
    gate is made again for ``out_proj``'s weight gradient."""
    S, H, G = 8192, 64, 8
    inner, conv = H * P, H * P + 2 * G * N
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in (((1, inner + conv + H, S), jnp.bfloat16),
                                 ((4, conv), jnp.float32),
                                 ((conv,), jnp.float32), ((H,), jnp.float32),
                                 ((H,), jnp.float32), ((H,), jnp.float32),
                                 ((inner,), jnp.float32),
                                 ((inner, 16), jnp.bfloat16))]

    def loss(zx, w, b, dt_bias, a_log, d, scale, w_out):
        x, dt, dta, bs, cs, zx = ssd.mamba_pre(zx, w, b, dt_bias, a_log, G,
                                               N, CHUNK, jnp.bfloat16)
        y, _ = ssd.ssd_tokens_last(x, dt, dta, bs, cs, d, CHUNK,
                                   jnp.bfloat16)
        y = ssd.mamba_post(y, zx, scale, 1e-5, jnp.bfloat16)
        return jnp.sum(jnp.einsum("bfs,fd->bsd", y, w_out,
                                  preferred_element_type=jnp.float32))

    assert kernels_of_the_compiled_gradient(loss, args) == [
        "mamba_post_bwd", "mamba_post_fwd", "mamba_pre_bwd", "mamba_pre_fwd",
        "ssd_bwd", "ssd_fwd"]
