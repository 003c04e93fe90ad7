"""Pallas flash attention vs. the XLA reference implementation.

Runs in interpret mode on the CPU backend (conftest forces cpu); the same
kernels compile for real on TPU. Mirrors the reference's numeric-assertion
style (tests/integration/cases/c0.py:92-121): exactness is checked against
an independently computed ground truth, not just for finiteness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry as tel
from autodist_tpu.ops.attention import reference_attention
from autodist_tpu.ops import flash_attention as fa
from autodist_tpu.ops.flash_attention import flash_attention, make_flash_attn_fn


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _mask(s, causal):
    return jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None] if causal else None


def kernel_calls(jaxpr, name):
    """How many ``pallas_call`` equations named ``name`` a jaxpr holds,
    those of its inner jaxprs (a checkpoint's, a jit's) included."""
    return sum(
        (eqn.primitive.name == "pallas_call" and eqn.params["name"] == name)
        + sum(kernel_calls(inner, name)
              for inner in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 128, 2, 32), (2, 256, 4, 64)])
def test_forward_matches_reference(causal, shape):
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    out = flash_attention(q, k, v, causal)
    ref = reference_attention(q, k, v, _mask(shape[1], causal))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    shape = (1, 256, 2, 32)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    mask = _mask(shape[1], causal)

    def loss_flash(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(reference_attention(q, k, v, mask) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_uneven_block_sizes():
    # Sq != Sk and blocks smaller than the 128 default (64-divisible seqs)
    q = _rand((1, 64, 2, 32), seed=0)
    k = _rand((1, 192, 2, 32), seed=1)
    v = _rand((1, 192, 2, 32), seed=2)
    out = flash_attention(q, k, v, causal=False)
    ref = reference_attention(q, k, v, None)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_bfloat16_forward():
    shape = (1, 128, 2, 32)
    q, k, v = (_rand(shape, jnp.bfloat16, seed=i) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), _mask(shape[1], True))
    # atol 3e-2: bf16 has 8 mantissa bits (~2-3 decimal digits); outputs
    # are O(1) softmax-weighted averages, so one-ulp rounding is ~4e-3
    # and the row-sum accumulation ~1e-2 — 3e-2 holds across seeds
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_bfloat16_grads_match_f32_reference(causal):
    """bf16 grads vs the f32 XLA reference — the correctness baseline
    the analyzer's future attention-impl axis (and today's bf16 compute
    tier, which runs this kernel in half precision) needs. Tolerances:
    bf16 carries 8 mantissa bits, so single ops round at ~4e-3 relative;
    the backward pass chains two matmuls and a softmax rescale per
    block, compounding to ~1e-2 relative on O(1) gradients — atol/rtol
    5e-2 gives ~4x margin over the observed worst case without masking a
    wrong-formula bug (any algebraic error is O(1), not O(1e-2))."""
    shape = (1, 256, 2, 32)
    q, k, v = (_rand(shape, jnp.bfloat16, seed=i) for i in range(3))
    mask = _mask(shape[1], causal)

    def loss_flash(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal)
                        .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(reference_attention(q.astype(jnp.float32),
                                            k.astype(jnp.float32),
                                            v.astype(jnp.float32),
                                            mask) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b in zip(gf, gr):
        # grads w.r.t. bf16 inputs come out bf16 — compare in f32
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(jnp.float32), b,
                                   atol=5e-2, rtol=5e-2)


def test_untileable_seq_falls_back():
    # 100 has no power-of-two divisor >= 8 above 4 -> XLA reference fallback,
    # still differentiable
    shape = (1, 100, 2, 16)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, _mask(shape[1], True))
    np.testing.assert_allclose(out, ref, atol=1e-5)
    g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, True) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_attn_fn_adapter_in_model_layer():
    from autodist_tpu.models.layers import MultiHeadAttention
    attn = make_flash_attn_fn(causal=True)
    layer = MultiHeadAttention(num_heads=2, head_dim=16, attn_fn=attn)
    x = _rand((2, 128, 32))
    params = layer.init(jax.random.PRNGKey(0), x)
    out = layer.apply(params, x)
    assert out.shape == x.shape
    # same layer with the XLA mask path must agree
    ref_layer = MultiHeadAttention(num_heads=2, head_dim=16)
    ref = ref_layer.apply(params, x, jnp.tril(
        jnp.ones((1, 1, 128, 128), jnp.bool_)))
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_flash_kind_registered():
    from autodist_tpu.ops.attention import make_attn_fn
    fn = make_attn_fn("flash", causal=True)
    shape = (1, 128, 2, 32)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    out = fn(q, k, v)
    ref = reference_attention(q, k, v, _mask(shape[1], True))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# -------------------------------------------------------- segments / masks


def _seg_mask(q_seg, kv_seg):
    return (np.asarray(q_seg)[:, :, None]
            == np.asarray(kv_seg)[:, None, :])[:, None]


@pytest.mark.parametrize("causal", [False, True])
def test_padding_mask_matches_reference(causal):
    """BERT-style key padding as segment ids: fwd + grads equal the XLA
    reference under the equivalent q_seg==kv_seg mask."""
    B, S = 2, 256
    shape = (B, S, 2, 32)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    rng = np.random.RandomState(7)
    lengths = rng.randint(S // 4, S, (B,))
    seg = (np.arange(S)[None, :] < lengths[:, None]).astype(np.int32)
    mask = jnp.asarray(_seg_mask(seg, seg))
    if causal:
        mask = jnp.logical_and(mask, _mask(S, True))

    out = flash_attention(q, k, v, causal, segment_ids=jnp.asarray(seg))
    ref = reference_attention(q, k, v, mask)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, segment_ids=jnp.asarray(seg))
        return jnp.mean(o ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(reference_attention(q, k, v, mask) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_packed_sequences_do_not_cross_attend():
    """Two packed documents in one row: tokens never attend across the
    segment boundary (the sequence-packing use, beyond padding)."""
    B, S = 1, 256
    shape = (B, S, 2, 32)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    seg = np.zeros((B, S), np.int32)
    seg[:, S // 2:] = 1  # two docs, split mid-sequence
    out = flash_attention(q, k, v, False, segment_ids=jnp.asarray(seg))
    ref = reference_attention(q, k, v, jnp.asarray(_seg_mask(seg, seg)))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # doc-0 queries must be independent of doc-1 keys/values entirely
    k2 = k.at[:, S // 2:].set(0.0)
    v2 = v.at[:, S // 2:].set(0.0)
    out2 = flash_attention(q, k2, v2, False, segment_ids=jnp.asarray(seg))
    np.testing.assert_allclose(out[:, :S // 2], out2[:, :S // 2],
                               atol=1e-6, rtol=1e-6)


def test_attn_fn_adapter_accepts_padding_mask():
    """The layers' attn_fn slot: a [B, 1, 1, S] boolean key-padding mask
    routes through the segment path and matches the reference."""
    B, S = 2, 128
    shape = (B, S, 2, 32)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    valid = np.ones((B, S), np.int32)
    valid[:, S - 32:] = 0
    mask4 = jnp.asarray(valid, jnp.bool_)[:, None, None, :]
    attn = make_flash_attn_fn(causal=False)
    out = attn(q, k, v, mask4)
    ref = reference_attention(q, k, v, jnp.asarray(_seg_mask(valid, valid)))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_empty_query_rows_emit_zeros_with_zero_grads():
    """A (q_seg, kv_seg) pair where some query segment matches NO key: the
    empty rows output zeros (not a garbage average of values) and their
    gradients vanish — guarded in both the forward and backward kernels
    (advisor finding, flash_attention.py empty-row case)."""
    B, S = 1, 128
    shape = (B, S, 2, 32)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    q_seg = np.zeros((B, S), np.int32)
    q_seg[:, S // 2:] = 7           # segment 7 appears in NO key
    kv_seg = np.zeros((B, S), np.int32)
    out = flash_attention(q, k, v, False,
                          segment_ids=(jnp.asarray(q_seg),
                                       jnp.asarray(kv_seg)))
    # live rows match the reference; empty rows are exactly zero
    ref = reference_attention(q, k, v, jnp.asarray(_seg_mask(q_seg, kv_seg)))
    np.testing.assert_allclose(out[:, :S // 2], ref[:, :S // 2],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(out[:, S // 2:]), 0.0)

    def loss(q, k, v):
        o = flash_attention(q, k, v, False,
                            segment_ids=(jnp.asarray(q_seg),
                                         jnp.asarray(kv_seg)))
        return jnp.sum(o * o)
    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.all(np.isfinite(np.asarray(g)))
    # empty query rows contribute nothing anywhere
    np.testing.assert_array_equal(np.asarray(dq[:, S // 2:]), 0.0)


# ------------------------------------------- the tiles the chooser returns


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 5e-2)])
def test_seq_2048_heads_of_128_at_the_choosers_tiles(dtype, tol):
    """Forward and every gradient, causal, at the tiling ``_tiles`` gives
    ``olmoe_train_1chip``'s attention (seq 2048, heads of 128: four
    512-row tiles a side): the rows of tiles hold a
    first tile, a tile wholly under the diagonal, tiles the diagonal
    crosses, a tile above it (skipped, its blocks not fetched) and the
    last tile. A cotangent of O(1) keeps the gradients O(1), so
    the tolerance (float32: sum order; bfloat16: the file's 5e-2 against
    the float32 reference) is not vacuous."""
    shape = (1, 2048, 2, 128)
    bq, bk = fa._tiles(shape[1], shape[1])
    assert bq == bk and shape[1] // bq >= 2, (bq, bk)
    q, k, v, cot = (_rand(shape, dtype, seed=i) for i in range(4))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    mask = _mask(shape[1], True)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32)
                       * cot.astype(jnp.float32))

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, mask)
                       * cot.astype(jnp.float32))

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))(q, k, v)
    assert out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32),
                               reference_attention(*f32, mask),
                               atol=tol, rtol=tol)
    got = jax.jit(jax.grad(loss_flash, (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss_ref, (0, 1, 2)))(*f32)
    for a, b in zip(got, want):
        assert a.dtype == dtype and float(jnp.max(jnp.abs(b))) > 0.5
        np.testing.assert_allclose(a.astype(jnp.float32), b,
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("shape, tiles", [
    ((2048, 2048), (512, 512)),    # olmoe_train_1chip
    ((256, 256), (256, 256)),      # lm1b's cells: one tile
    ((8, 1024), (8, 512)),         # the decode loop's 8-row query
    ((8192, 8192), (512, 512)),    # no wider however long
    ((1536, 1536), (512, 512)),    # three whole tiles
    ((192, 192), (64, 64)),        # the largest power of two that divides
    ((100, 100), (0, 0)),          # nothing of 8 rows divides
])
def test_tiles_come_from_the_shapes(shape, tiles):
    assert fa._tiles(*shape) == tiles
    assert fa.full_tiles(shape[0]) == (tiles[0] == 512)


# ------------------------------------------ the table of tiles a kernel walks


# (n_q, n_kv, block_q, block_k)
TABLES = {
    "seq_8192": (16, 16, 512, 512),
    "seq_2048": (4, 4, 512, 512),
    "one_tile": (1, 1, 256, 256),
    "more_queries_than_keys": (3, 2, 512, 512),
    "more_keys_than_queries": (2, 3, 512, 512),
    "q_tiles_of_512_kv_tiles_of_256": (3, 3, 512, 256),
    "q_tiles_of_64_kv_tiles_of_256": (6, 2, 64, 256),
    "the_decode_loops_8_rows": (1, 2, 8, 512),
}


@pytest.mark.parametrize("kv_major", [False, True],
                         ids=["q_major", "kv_major"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("case", sorted(TABLES))
def test_the_tile_table_lists_every_live_tile_once(case, causal, kv_major):
    """``_tile_table``: every tile ``_tile_live`` finds a visible entry in
    appears exactly once, in the walk's row order, and none that it calls
    dead; ``CROSSED`` exactly where some column passes some row; the first
    / last bits mark each q block's and each kv block's first and last
    tile of the walk; ``_Q_OUT`` names every q block over ONE run of steps
    that ends at the block's last tile (where the kernel writes its dq)."""
    n_q, n_kv, bq, bk = TABLES[case]
    table = fa._tile_table(n_q, n_kv, bq, bk, causal, kv_major)
    assert table.dtype == np.int32 and table.shape[0] == 4
    q, kv, flags, q_out = (table[c].tolist() for c in (
        fa._Q, fa._KV, fa._FLAGS, fa._Q_OUT))
    rect = ([(qi, ki) for ki in range(n_kv) for qi in range(n_q)] if kv_major
            else [(qi, ki) for qi in range(n_q) for ki in range(n_kv)])
    walk = list(zip(q, kv))
    assert walk == [t for t in rect if fa._tile_live(*t, bq, bk, causal)]

    def passes(qi, ki):     # some column of the tile lies right of some row
        rows = qi * bq + np.arange(bq)[:, None]
        cols = ki * bk + np.arange(bk)[None, :]
        assert not causal or (rows >= cols).any()       # ... and it is live
        return bool(causal and (cols > rows).any())
    assert [bool(f & fa.CROSSED) for f in flags] == [
        passes(*t) for t in walk]
    for bit_first, bit_last, blocks in ((fa.Q_FIRST, fa.Q_LAST, q),
                                        (fa.KV_FIRST, fa.KV_LAST, kv)):
        first = {b: blocks.index(b) for b in set(blocks)}
        last = {b: len(blocks) - 1 - blocks[::-1].index(b)
                for b in set(blocks)}
        assert [bool(f & bit_first) for f in flags] == [
            first[b] == t for t, b in enumerate(blocks)]
        assert [bool(f & bit_last) for f in flags] == [
            last[b] == t for t, b in enumerate(blocks)]
    # every q block has a tile (it sees kv block 0) and leaves once
    runs = [b for t, b in enumerate(q_out) if t == 0 or q_out[t - 1] != b]
    assert sorted(runs) == list(range(n_q))
    ends = [t for t in range(len(q_out))
            if t == len(q_out) - 1 or q_out[t + 1] != q_out[t]]
    assert all(q[t] == q_out[t] and flags[t] & fa.Q_LAST for t in ends)
    if not kv_major:
        assert q_out == q
    if case == "seq_8192":
        assert (len(walk), sum(bool(f & fa.CROSSED) for f in flags)) == (
            (136, 16) if causal else (256, 0))


@pytest.mark.parametrize("seq, causal, launches, tiles, masked", [
    (8192, True, 2, 136, 16),       # the three cells at 8,192 positions
    (8192, False, 2, 256, 0),       # every tile, none masked
    (2048, True, 2, 10, 4),         # olmoe_train_1chip
    (256, True, 2, 1, 1),           # one tile: the diagonal crosses it
    (131072, True, 3, 32896, 256),  # the far side: flash_dkdv and flash_dq
])
def test_the_counters_say_how_many_tiles_a_launch_walks(seq, causal, launches,
                                                        tiles, masked):
    """``attention.flash_tiles`` / ``attention.flash_tiles_masked``: steps
    of the tile table a head, and those that pay the positional mask, added
    once a traced launch (forward, backward): read as the gradient is
    TRACED, nothing runs."""
    x = jax.ShapeDtypeStruct((1, seq, 2, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal))
    before = tel.counters()
    jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x)
    after = tel.counters()
    assert {name: after[name] - before.get(name, 0) for name in (
        "attention.flash_tiles", "attention.flash_tiles_masked")} == {
            "attention.flash_tiles": launches * tiles,
            "attention.flash_tiles_masked": launches * masked}


# ------------------------------ what a recomputing checkpoint keeps by name


@pytest.mark.parametrize("segments", [False, True],
                         ids=["no_segments", "segment_ids"])
def test_a_checkpoint_that_keeps_the_name_runs_the_forward_kernel_once(
        segments):
    """Latent attention's widths (scores over 192, values of 128), causal,
    under ``jax.checkpoint``: with the policy that saves ``fa.KEPT`` the
    gradient's jaxpr holds the forward kernel ONCE (its output and
    log-sum-exp come from memory, so the recomputed one is dead code),
    without a policy twice; the backward kernel once either way, and the
    three gradients are the unwrapped function's to the last bit."""
    q, k = (_rand((1, 128, 2, 192), seed=i) for i in range(2))
    v, cot = (_rand((1, 128, 2, 128), seed=i) for i in (2, 3))
    seg = jnp.asarray([[0] * 80 + [1] * 48], jnp.int32) if segments else None

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, seg) * cot)

    keep = jax.checkpoint_policies.save_only_these_names(fa.KEPT)
    grads = {"plain": jax.grad(f, (0, 1, 2)),
             "kept": jax.grad(jax.checkpoint(f, policy=keep), (0, 1, 2)),
             "recomputed": jax.grad(jax.checkpoint(f), (0, 1, 2))}
    calls = {how: {kernel: kernel_calls(
        jax.make_jaxpr(g)(q, k, v).jaxpr, kernel)
        for kernel in ("flash_fwd", "flash_bwd", "flash_dq", "flash_dkdv")}
        for how, g in grads.items()}
    one_backward = {"flash_bwd": 1, "flash_dq": 0, "flash_dkdv": 0}
    assert calls == {
        "plain": dict(one_backward, flash_fwd=1),
        "kept": dict(one_backward, flash_fwd=1),
        "recomputed": dict(one_backward, flash_fwd=2)}
    want = jax.jit(grads["plain"])(q, k, v)
    assert all(float(jnp.max(jnp.abs(g))) > 0.1 for g in want)
    for how in ("kept", "recomputed"):
        for got, ref in zip(jax.jit(grads[how])(q, k, v), want):
            np.testing.assert_array_equal(got, ref)


# ------------------------------- one backward kernel, or two, by the shapes


def _segments(kind, B, Sq, Sk):
    """(q_seg, kv_seg) int32 [B, S] of a named layout, or None."""
    if kind is None:
        return None
    if kind == "padding":       # BERT's key padding: 1 = token, 0 = pad
        lengths = np.random.RandomState(7).randint(Sq // 4, Sq, (B,))
        seg = (np.arange(Sq)[None, :] < lengths[:, None]).astype(np.int32)
        return seg, seg
    if kind == "packed":        # three documents in a row, uneven
        seg = np.zeros((B, Sq), np.int32)
        seg[:, Sq // 3:] = 1
        seg[:, Sq - Sq // 5:] = 2
        return seg, seg
    assert kind == "empty_rows"  # the last third of the queries sees NO key
    q_seg = np.zeros((B, Sq), np.int32)
    q_seg[:, Sq - Sq // 3:] = 7
    return q_seg, np.zeros((B, Sk), np.int32)


# (Sq, Sk, D, Dv, dtype, causal, segments): tiles of 512 rows from seq 1,024
# on, so dq sums over several kv blocks and dk / dv over several q blocks
BACKWARD_CASES = {
    "float32": (1024, 1024, 32, 32, jnp.float32, False, None),
    "float32_causal": (1024, 1024, 32, 32, jnp.float32, True, None),
    "bfloat16": (1024, 1024, 32, 32, jnp.bfloat16, False, None),
    "bfloat16_causal": (1024, 1024, 32, 32, jnp.bfloat16, True, None),
    "latent_192_128_causal": (1024, 1024, 192, 128, jnp.float32, True, None),
    "latent_192_128_bfloat16": (1024, 1024, 192, 128, jnp.bfloat16, True,
                                None),
    "more_queries_than_keys": (1536, 1024, 32, 32, jnp.float32, False, None),
    "uneven_blocks_of_64": (64, 192, 32, 32, jnp.float32, False, None),
    "one_tile": (256, 256, 32, 32, jnp.float32, True, None),
    # the causal triangle where the tile table is no square: keys past the
    # last query (kv blocks with no tile: dk = dv = 0 there), and q tiles
    # of 512 rows over kv tiles of 256
    "more_keys_than_queries_causal": (1024, 1536, 32, 32, jnp.float32, True,
                                      None),
    "tiles_of_512_by_256_causal": (1536, 768, 32, 32, jnp.float32, True,
                                   None),
    "padding_mask": (1024, 1024, 32, 32, jnp.float32, False, "padding"),
    "padding_mask_causal": (1024, 1024, 32, 32, jnp.float32, True, "padding"),
    "packed_segments": (1024, 1024, 32, 32, jnp.float32, False, "packed"),
    "packed_segments_causal": (1536, 1536, 32, 32, jnp.float32, True,
                               "packed"),
    "rows_with_no_visible_key": (1024, 1024, 32, 32, jnp.float32, False,
                                 "empty_rows"),
    # a sparse attention's choice of keys (a selection [B, Sq, Sk], every
    # head alike), and K/V heads that two query heads share
    "chosen_keys_causal": (1024, 1024, 32, 32, jnp.float32, True, "chosen"),
    "chosen_keys_bfloat16": (1024, 1024, 128, 128, jnp.bfloat16, True,
                             "chosen"),
    "chosen_keys_packed": (1024, 1024, 32, 32, jnp.float32, True,
                           "chosen_packed"),
    "grouped_heads_causal": (1024, 1024, 32, 32, jnp.float32, True,
                             "grouped"),
    "chosen_keys_grouped_heads": (1024, 1024, 32, 32, jnp.float32, True,
                                  "chosen_grouped"),
}


def _selection(kind, B, Sq, Sk):
    """None, or [B, Sq, Sk] int8: about a third of the keys, always the
    query's own (every query keeps a key); the last queries keep none of
    the first tile's keys, so their rows are empty until a later tile."""
    if kind is None or "chosen" not in kind:
        return None
    chosen = np.random.RandomState(11).rand(B, Sq, Sk) < 0.3
    chosen[:, 700:, :512] = False
    chosen[:, np.arange(Sq), np.arange(Sq)] = True
    return jnp.asarray(chosen, jnp.int8)


def _backward_case(name):
    """(inputs in the kernels' [B, H, S, .] layout, segs, causal, the
    reference's float32 gradients, tolerance)."""
    Sq, Sk, D, Dv, dtype, causal, kind = BACKWARD_CASES[name]
    B, H = 2, 2
    grouped = kind is not None and "grouped" in kind
    kv_heads = 1 if grouped else H
    q = _rand((B, H, Sq, D), dtype, seed=0)
    k = _rand((B, kv_heads, Sk, D), dtype, seed=1)
    v = _rand((B, kv_heads, Sk, Dv), dtype, seed=2)
    do = _rand((B, H, Sq, Dv), dtype, seed=3)
    segs = _segments({"chosen_packed": "packed", "chosen": None, "grouped": None,
                      "chosen_grouped": None}.get(kind, kind), B, Sq, Sk)
    sel = _selection(kind, B, Sq, Sk)
    mask = None
    if causal:
        mask = (jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :])[None, None]
    if segs is not None:
        seg_mask = jnp.asarray(_seg_mask(*segs))
        mask = seg_mask if mask is None else jnp.logical_and(mask, seg_mask)
    if sel is not None:
        mask = jnp.logical_and(mask, (sel != 0)[:, None])

    def loss_ref(q, k, v):
        # (reference_attention takes the models' [B, S, H, .] and as many
        # K/V heads as query heads: a shared head is repeated, and its
        # gradient is the sum over the heads that read it)
        k, v = (jnp.repeat(x, H // kv_heads, axis=1) for x in (k, v))
        out = reference_attention(*(x.transpose(0, 2, 1, 3)
                                    for x in (q, k, v)), mask)
        return jnp.sum(out.transpose(0, 2, 1, 3) * do.astype(jnp.float32))

    want = jax.grad(loss_ref, (0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    if kind == "empty_rows":
        # XLA's softmax averages over an all-masked row; the kernels give
        # such a query no weight anywhere
        empty = np.asarray(segs[0] == 7)[:, None, :, None]
        mask = jnp.logical_and(mask, ~jnp.asarray(empty))
        want = jax.grad(lambda q, k, v: jnp.sum(jnp.where(
            empty, 0.0, reference_attention(
                *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), mask)
            .transpose(0, 2, 1, 3)) * do), (0, 1, 2))(q, k, v)
    segs = None if segs is None else tuple(jnp.asarray(s) for s in segs)
    tol = (dict(atol=5e-5, rtol=5e-4) if dtype == jnp.float32
           else dict(atol=5e-2, rtol=5e-2))     # the file's two tolerances
    return (q, k, v, do), (segs, sel), causal, want, tol


def _backward(inputs, masks, causal):
    """(dq, dk, dv) of ``fa._bwd`` on the forward kernel's own residuals."""
    q, k, v, do = inputs
    segs, sel = masks
    out, lse = fa._fwd(q, k, v, segs, sel, causal)
    q_seg, kv_seg = (None, None) if segs is None else segs
    return fa._bwd(causal, (q, k, v, out, lse, q_seg, kv_seg, sel), do)


@pytest.mark.parametrize("case", sorted(BACKWARD_CASES))
def test_the_fused_backward_matches_the_references_gradients(case):
    """dq, dk and dv of the ONE backward kernel (``flash_bwd``: every live
    tile's scores, P and dS once, dq summed in VMEM across the head's kv
    blocks) against the XLA reference's, over dtypes, causal or not, the
    latent widths 192 / 128, Sq != Sk, blocks under 512 rows, a padding
    mask, packed documents and queries that see no key (zero gradients)."""
    inputs, segs, causal, want, tol = _backward_case(case)
    q, k, v, do = inputs
    before = tel.counters().get("attention.flash_bwd_fused", 0)
    got = _backward(inputs, segs, causal)
    assert tel.counters()["attention.flash_bwd_fused"] == before + 1
    for g, x, w in zip(got, (q, k, v), want):
        assert g.dtype == x.dtype and g.shape == x.shape
        assert float(jnp.max(jnp.abs(w))) > 0.1     # not vacuous
        np.testing.assert_allclose(g.astype(jnp.float32), w, **tol)
    if case == "rows_with_no_visible_key":
        np.testing.assert_array_equal(
            np.asarray(got[0][:, :, q.shape[2] - q.shape[2] // 3:]), 0.0)


@pytest.mark.parametrize("case", sorted(BACKWARD_CASES))
def test_both_backward_forms_give_the_same_gradients(case, monkeypatch):
    """The two kernels that stay for heads whose dq accumulator does not fit
    VMEM (``flash_dq`` + ``flash_dkdv``) sum the same products in the same
    order as the fused one: the three gradients agree to the last bit."""
    inputs, segs, causal, _, _ = _backward_case(case)
    fused = _backward(inputs, segs, causal)
    monkeypatch.setattr(fa, "_VMEM", 0)      # no head fits: the far side
    split = _backward(inputs, segs, causal)
    for a, b in zip(fused, split):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape, dv, dtype, segments, fused", [
    # the two latent cells, OLMoE's and the widest of the VMEM-fit cases
    # (tests/test_olmoe.py::test_the_kernels_tiles_fit_the_v5es_vmem)
    ((1, 8192, 16, 192), 128, jnp.bfloat16, False, True),
    ((4, 2048, 16, 128), 128, jnp.bfloat16, False, True),
    ((1, 32768, 8, 128), 128, jnp.bfloat16, True, True),
    ((2, 8192, 8, 256), 256, jnp.float32, True, True),
    ((8, 512, 12, 64), 64, jnp.bfloat16, True, True),
    # the far side: dq's float32 accumulator alone is 134 MB, 67 MB
    ((1, 131072, 2, 192), 128, jnp.bfloat16, False, False),
    ((1, 131072, 2, 64), 64, jnp.float32, True, False),
    # ... and the longest that still fits half of VMEM
    ((1, 65536, 2, 128), 128, jnp.bfloat16, False, True),
])
def test_the_backward_form_follows_from_the_shapes(shape, dv, dtype, segments,
                                                   fused):
    """One backward kernel wherever the head's float32 dq accumulator, the
    blocks and a tile's temporaries fit half of a v5e core's VMEM, the two
    kernels past that: read off the shapes alone as the gradient is TRACED
    (nothing runs), and counted by form in the telemetry."""
    B, S, H, D = shape
    need = fa._fused_vmem(S, D, dv, 512, 512, dtype, segments)
    assert (need <= 64 << 20) == fused
    # the accumulator's lanes are padded to whole 128s: 192 takes 256
    assert need > S * -(-D // 128) * 128 * 4
    x = jax.ShapeDtypeStruct(shape, dtype)
    vv = jax.ShapeDtypeStruct((B, S, H, dv), dtype)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32) if segments else None

    def loss(q, k, v, seg):
        return jnp.sum(flash_attention(q, k, v, True, seg)
                       .astype(jnp.float32))
    before = tel.counters()
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, vv, seg).jaxpr
    calls = {name: kernel_calls(jaxpr, name)
             for name in ("flash_fwd", "flash_bwd", "flash_dq", "flash_dkdv")}
    assert calls == {"flash_fwd": 1, "flash_bwd": int(fused),
                     "flash_dq": int(not fused), "flash_dkdv": int(not fused)}
    after = tel.counters()
    counted = {form: after.get("attention.flash_bwd_" + form, 0)
               - before.get("attention.flash_bwd_" + form, 0)
               for form in ("fused", "split")}
    assert counted == {"fused": int(fused), "split": int(not fused)}


# ------------------------- a selection of keys, and the calls without one


PARENT_CALLS = {
    "causal_2x64x4x16": ((2, 64, 4, 16), True, False),
    "causal_seg_1x32x2x32": ((1, 32, 2, 32), True, True),
    "full_1x32x2x16": ((1, 32, 2, 16), False, False)}


@pytest.mark.parametrize("case", sorted(PARENT_CALLS))
def test_a_call_without_a_selection_traces_to_the_parents_kernels(case):
    """``flash_attention`` without ``select`` and with as many K/V heads as
    query heads, differentiated: the same jaxpr, equation for equation and
    kernel body for kernel body, as when it was last pinned (its text's
    hash, ``flash_attention`` in ``tests/data/lm_pins.json``). The
    selection operand and the grouped heads changed no program that does
    not ask for them; a later change to these programs shows here."""
    import hashlib
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "lm_pins.json")) as f:
        before = json.load(f)["flash_attention"][case]
    shape, causal, seg = PARENT_CALLS[case]
    q = jnp.zeros(shape, jnp.float32)
    ids = jnp.zeros(shape[:2], jnp.int32) if seg else None

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, ids))
    text = str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(q, q, q))
    assert {"jaxpr_sha256": hashlib.sha256(text.encode()).hexdigest(),
            "jaxpr_lines": text.count("\n")} == before


@pytest.mark.parametrize("kv_heads", [4, 1])
def test_a_selection_through_the_public_op_and_the_adapter(kv_heads):
    """[B, S, H, D] in, a bool selection and K/V heads shared four ways:
    values and gradients of XLA's masked scores; the ``attn_fn`` adapter
    hands ``select`` on; an untileable length takes the XLA path with the
    same answer."""
    B, S, H, D = 2, 64, 4, 16
    q = _rand((B, S, H, D), seed=0)
    k, v = (_rand((B, S, kv_heads, D), seed=i) for i in (1, 2))
    chosen = np.random.RandomState(3).rand(B, S, S) < 0.4
    chosen[:, np.arange(S), np.arange(S)] = True
    chosen = jnp.asarray(chosen)
    mask = jnp.logical_and(_mask(S, True), chosen[:, None])

    def want(q, k, v):
        k, v = (jnp.repeat(x, H // kv_heads, axis=2) for x in (k, v))
        return jnp.sum(jnp.sin(reference_attention(q, k, v, mask)))

    def got(q, k, v):
        return jnp.sum(jnp.sin(make_flash_attn_fn(True)(q, k, v, None,
                                                        select=chosen)))
    a = jax.value_and_grad(got, (0, 1, 2))(q, k, v)
    b = jax.value_and_grad(want, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(a[0], b[0], rtol=1e-5)
    for x, y in zip(a[1], b[1]):
        np.testing.assert_allclose(x, y, atol=3e-5, rtol=1e-4)
    odd = slice(0, 60)                       # 60 rows: tiles of 4, no kernel
    np.testing.assert_allclose(
        flash_attention(q[:, odd], k[:, odd], v[:, odd], True,
                        select=chosen[:, odd, odd]),
        reference_attention(q[:, odd], jnp.repeat(k[:, odd], H // kv_heads, 2),
                            jnp.repeat(v[:, odd], H // kv_heads, 2),
                            mask[:, :, odd, odd]), atol=2e-5, rtol=2e-5)
