"""Softmax attention's q / k prologue: one pallas pass a direction.

Between a softmax-attention layer's projections and its flash core
(``ops/flash_attention.py``) lies element-wise work alone: a per-head
RMSNorm of q and of k (Qwen3's QK-norm), the rotation by position and the
cast to the model's dtype, on the way from the projections' ``[B, S, H, D]``
to the ``[B, H, S, D]`` the kernels index. XLA runs it as a dozen float32
passes over ``[T, H, D]`` a layer and direction (the widened normed q,
``rotate_half``'s halves as arrays of their own, the norm's backward sums;
22.7 GB a step in Trinity-Mini's cell where one read and one write a tensor
are 4.3: PERF.md section 6, PR 54). Here it is ONE pass over HBM a tensor
and direction, ``attn_pre_fwd`` / ``attn_pre_bwd`` under one
``jax.custom_vjp``, heads-first on BOTH sides:

- layout: the pass reads the projection's output transposed to
  ``[B, H, S, D]`` and writes the same shape. The transposition costs no
  pass: XLA lays the projection's product heads-major when its reader wants
  it so (it did before this pass, for its own rotation), and takes the
  gradient back heads-major into the projection's two backward products.
  Read tokens-first (``[rows, H D]`` blocks, the head a lane slice) the
  forward was as good and the backward's result cost a ``[T, H, D]`` copy a
  layer on its way to those products (device-less, PR 54).
- forward: every head's ``[rows, D]`` block of a token tile, a head at a
  time: float32 statistics, ``x * (rsqrt(mean(x^2) + eps) * w)`` rounded to
  the model's dtype (the rounding point ``nn.RMSNorm`` has), the rotation
  ``y cos + rotate_half(y) sin`` in float32 (``rotate_half`` is a roll by
  D / 2 lanes, its sign folded into the sin table), rounded once more. cos
  and sin are read as ``[S, D]`` float32 tables that XLA makes from the
  positions exactly as ``models/layers.py:rotate`` does (16 MB a launch
  beside q's 268 at 16,384 tokens, and every head of a step shares the
  tile's), so the angles are the ``jnp`` form's to the bit.
- backward: from the cotangent and, where the layer norms, the RAW
  projection (its only large residual: what a recomputed block makes again
  today, for the same norm's backward): the rotation's transpose ``g cos +
  roll(g sin)``, the norm's backward with the statistics made again, all in
  float32 with ONE rounding at the output (the ``jnp`` form rounds the
  normed value's cotangent to the model's dtype in between: never a lower
  precision here); the norm's weight gradient as one ``[1, D]`` partial sum
  a grid step, summed outside.

v has no arithmetic and stays out: its caller transposes it, which XLA
folds into the value projection the same way.

Which layers run it is :func:`runs_fused`'s to say, from the layer's own
fields and shapes alone; every other layer and mode keeps
``models/layers.py:attn_inputs``, the ``jnp`` form these kernels are tested
against (``tests/test_attn_pre.py``).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import flash_attention, pallas_mode
from autodist_tpu.telemetry import spans as tel

_LANES = 128
TOKEN_TILE = 512        # tokens one grid step works on, at most ...
_BLOCK_BYTES = 4 << 20  # ... and no more than this of an operand, all heads
# The loop inside a step works on a slice of rows of ALL heads in one array
# op: ``_SUB`` rows of every head (a bfloat16 tile) or, of few heads, as many
# more as make ``_SLICE`` rows in all. A head at a time, unrolled in Python,
# the same equations ran 3.8 times slower on the v5e (2.00 against 0.53 ms a
# launch at [1, 32, 16384, 128], 142 against 537 GB/s; 32 or 64 rows of 32
# heads read the same 0.53, the row sums as products with ones on the MXU
# 0.82) and traced for twice as long (PERF.md section 6, PR 54)
_SUB = 16
_SLICE = 512
_PASS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=64 << 20)


def _rsqrt_mean_square(x, eps):
    """[heads, rows, D] float32 -> [heads, rows, 1]: ``rsqrt(mean(x^2) +
    eps)`` over each head's features."""
    return jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / x.shape[-1]) + eps)


def _operands(refs, normed, rotated, n_first):
    """(the first ``n_first`` refs, the norm's weight or None, the cos and
    sin tables or None, the rest) of a kernel's refs."""
    first, refs = refs[:n_first], refs[n_first:]
    w_ref = cos_ref = sin_ref = None
    if normed:
        w_ref, refs = refs[0], refs[1:]
    if rotated:
        (cos_ref, sin_ref), refs = refs[:2], refs[2:]
    return first, w_ref, cos_ref, sin_ref, refs


def _slice_rows(heads, rows_a_step):
    """Rows of every head the loop inside a step works on at a time:
    ``_SUB``, or as many more as keep a slice at ``_SLICE`` rows over all
    its heads (k's four heads take slices of 128 where q's 32 take 16: at
    16 k's pass ran at 255 GB/s where q's ran at 638)."""
    sub = _SUB
    while 2 * sub * heads <= _SLICE and rows_a_step % (2 * sub) == 0:
        sub *= 2
    return sub


def _row_loop(sub, rows_a_step, body, init):
    """``body(rows, first row, carry)`` over a step's rows, ``sub`` at a
    time."""
    def step(i, carry):
        return body(pl.ds(pl.multiple_of(i * sub, sub), sub), i * sub, carry)
    return jax.lax.fori_loop(0, rows_a_step // sub, step, init)


def _fwd_kernel(*refs, eps, normed, rotated):
    """Step (b, t): token tile t of every head, ``x_ref`` [heads, rows, D]
    to ``out_ref`` of the same shape, a slice of rows of ALL heads at a
    time."""
    f32 = jnp.float32
    (x_ref,), w_ref, cos_ref, sin_ref, (out_ref,) = _operands(
        refs, normed, rotated, 1)
    heads, rows_a_step, d = x_ref.shape

    def rows(r, _, carry):
        x = x_ref[:, r, :].astype(f32)
        if normed:
            x = (x * (_rsqrt_mean_square(x, eps) * w_ref[...])
                 ).astype(out_ref.dtype).astype(f32)
        if rotated:
            x = x * cos_ref[r, :] + pltpu.roll(x, d // 2, 2) * sin_ref[r, :]
        out_ref[:, r, :] = x.astype(out_ref.dtype)
        return carry
    _row_loop(_slice_rows(heads, rows_a_step), rows_a_step, rows, 0)


def _bwd_kernel(*refs, eps, seq, normed, rotated):
    """Step (b, t): the cotangent's tile ``g_ref`` [heads, rows, D] to the
    projection's gradient's; with a norm, from the raw projection's own
    tile, and the weight's gradient summed over the tile's live rows and
    heads into this step's [1, D]."""
    f32 = jnp.float32
    first, w_ref, cos_ref, sin_ref, outs = _operands(
        refs, normed, rotated, 1 + normed)
    g_ref, dx_ref = first[0], outs[0]
    heads, rows_a_step, d = g_ref.shape
    sub = _slice_rows(heads, rows_a_step)
    start = pl.program_id(1) * rows_a_step

    def rows(r, row0, dw):
        g = g_ref[:, r, :].astype(f32)
        if rotated:
            g = g * cos_ref[r, :] + pltpu.roll(g * sin_ref[r, :], d // 2, 2)
        if normed:
            x = first[1][:, r, :].astype(f32)
            scale = _rsqrt_mean_square(x, eps)
            gw = g * w_ref[...]
            along = jnp.sum(gw * x, axis=-1, keepdims=True) * (1.0 / d)
            live = (start + row0 + jax.lax.broadcasted_iota(
                jnp.int32, (sub, 1), 0)) < seq
            dw = dw + jnp.sum(jnp.where(live, g * x * scale, 0.0), axis=0)
            g = scale * (gw - x * (scale * scale * along))
        dx_ref[:, r, :] = g.astype(dx_ref.dtype)
        return dw
    dw = _row_loop(sub, rows_a_step, rows, jnp.zeros((sub, d), f32))
    if normed:
        outs[1][...] = jnp.sum(dw, axis=0, keepdims=True)


def _specs(x, tile, tables):
    """(grid (b, t), every head's token tile [heads, rows, D] of ``x``
    [B, heads, S, D], the norm's weight [1, D], a table's [rows, D] of
    [1 or B, S, D], a step's own [1, D] of [B, tiles, 1, D])."""
    B, heads, S, d = x.shape
    rows = min(tile, _BLOCK_BYTES // (heads * d * x.dtype.itemsize),
               S + -S % _SUB)
    rows = max(rows - rows % _SUB, _SUB)
    per_row = tables is not None and tables[0].shape[0] > 1
    return ((B, pl.cdiv(S, rows)),
            pl.BlockSpec((None, heads, rows, d), lambda b, t: (b, 0, t, 0)),
            pl.BlockSpec((1, d), lambda b, t: (0, 0)),
            pl.BlockSpec((None, rows, d),
                         lambda b, t: (b if per_row else 0, t, 0)),
            pl.BlockSpec((None, None, 1, d), lambda b, t: (b, t, 0, 0)))


def _forward(x, w, tables, eps, tile, interpret):
    normed, rotated = w is not None, tables is not None
    grid, tile_of, weight, table, _ = _specs(x, tile, tables)
    tel.counter_add("attention.pre_passes")
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, normed=normed,
                          rotated=rotated),
        grid=grid,
        in_specs=[tile_of] + [weight] * normed + [table] * (2 * rotated),
        out_specs=tile_of,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_PASS,
        interpret=interpret,
        name="attn_pre_fwd",
    )(x, *((w,) if normed else ()), *(tables or ()))


def _backward(eps, tile, interpret, res, g):
    x, w, tables = res
    normed, rotated = w is not None, tables is not None
    grid, tile_of, weight, table, own = _specs(g, tile, tables)
    tel.counter_add("attention.pre_passes")
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, seq=g.shape[2],
                          normed=normed, rotated=rotated),
        grid=grid,
        in_specs=([tile_of] + [tile_of, weight] * normed
                  + [table] * (2 * rotated)),
        out_specs=[tile_of] + [own] * normed,
        out_shape=[jax.ShapeDtypeStruct(g.shape, g.dtype)]
        + [jax.ShapeDtypeStruct((g.shape[0], grid[1], 1, g.shape[3]),
                                jnp.float32)] * normed,
        compiler_params=_PASS,
        interpret=interpret,
        name="attn_pre_bwd",
    )(g, *((x, w) if normed else ()), *(tables or ()))
    dw = jnp.sum(outs[1], axis=(0, 1)).astype(w.dtype) if normed else None
    return outs[0], dw, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _pre(x, w, tables, eps, tile, interpret):
    return _forward(x, w, tables, eps, tile, interpret)


def _pre_fwd(x, w, tables, eps, tile, interpret):
    # (the raw projection is a residual only where the norm's backward
    # reads it: a layer that rotates alone keeps the tables and no more)
    return (_forward(x, w, tables, eps, tile, interpret),
            (None if w is None else x, w, tables))


_pre.defvjp(_pre_fwd, _backward)


def rotary_tables(positions, inv_freq):
    """(cos, sin) [1 or B, S, D] float32 of ``models/layers.py:rotate``'s
    angles, made as it makes them (positions [S] or [B, S], ``inv_freq``
    [D / 2]); sin carries ``rotate_half``'s sign, minus on the first half
    of the lanes: ``y cos + roll(y, D / 2) sin`` is the rotation."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    half = inv_freq.shape[0]
    sign = jnp.where(jnp.arange(2 * half) < half, -1.0, 1.0)
    cos, sin = jnp.cos(ang), jnp.sin(ang) * sign
    return tuple(t.reshape((-1,) + t.shape[-2:]) for t in (cos, sin))


def attn_pre(q, k, scales, eps, positions, inv_freq, tile=TOKEN_TILE):
    """Everything element-wise between a softmax attention's q and k
    projections and its flash core, ONE pass over HBM a tensor and
    direction: the projections' outputs q [B, S, H, D] and k [B, S, Hkv, D]
    -> q and k heads-first, [B, H, S, D] and [B, Hkv, S, D], as
    ``flash_attention(..., heads_first=True)`` reads them. ``scales``: None
    or the (q's, k's) learned [D] weights of a per-head RMSNorm with
    ``eps``; ``positions`` [S] or [B, S] and ``inv_freq`` [D / 2]: None or
    the rotation's (``models/layers.py:rotate``'s arguments). The values
    are ``models/layers.py:attn_inputs``'s, transposed. (v has no
    arithmetic: its caller transposes it, which XLA folds into the value
    projection as it does q's and k's here.)"""
    tables = None if inv_freq is None else jax.lax.stop_gradient(
        rotary_tables(positions, inv_freq))
    wq, wk = (None, None) if scales is None else (
        w.astype(jnp.float32)[None] for w in scales)
    return tuple(_pre(x.transpose(0, 2, 1, 3), w, tables, eps, tile,
                      pallas_mode.interpret()) for x, w in ((q, wq), (k, wk)))


def runs_fused(attn_fn, seq: int, head_dim: int, head_norm: bool,
               rotated: bool, full_width_norm: bool = False) -> bool:
    """Does a softmax-attention layer of these fields run :func:`attn_pre`
    in training and evaluation? Where its core runs through the flash
    kernels (``attn_fn`` is ``flash_attention.make_flash_attn_fn``'s
    adapter, which takes operands heads-first, and the sequence tiles),
    its heads are whole 128-lane tiles, and it has a per-head norm or a
    rotation to fuse: a layer with neither (a global NoPE layer without a
    norm) keeps today's program, and so does a norm over ALL projected
    features (OLMoE's: one statistic over H D lanes is another kernel)."""
    return (getattr(attn_fn, "heads_first", False)
            and flash_attention.tileable(seq)
            and head_dim % _LANES == 0
            and (head_norm or rotated) and not full_width_norm)
