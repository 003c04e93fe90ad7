"""Mamba-2's selective state-space recurrence in its chunked dual form
(state-space duality, arXiv 2405.21060).

Per head h with a scalar decay (P = head_dim features, N = state size; the
heads share B and C in groups of ``K = H / G``), in float32:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        S in R^{P x N}, S_0 = 0
    y_t = S_t C_t + D x_t                     a_t = exp(dt_t A), A < 0

Token by token that is S sequential steps of rank-one updates. The dual
form cuts the sequence into chunks of ``chunk`` tokens and turns all but
the carry between chunks into products (:func:`ssd_chunked`):

- inside a chunk ``Y_intra = (L o C B^T) (dt x)`` with the masked decay
  ``L_ts = exp(sum_{s < r <= t} dt_r A)`` for s <= t, 0 above the
  diagonal: the cumulative sums' DIFFERENCES, masked BEFORE the ``exp``
  (never a ratio of two exponentials, which overflows where a chunk decays
  far), and ``C B^T`` made once a GROUP;
- a chunk's own state ``sum_s exp(sum_{r > s} dt_r A) dt_s x_s B_s^T``;
- the states carried from chunk to chunk in float32,
  ``S_c = exp(sum_chunk dt A) S_{c-1} + (chunk c's own)``;
- ``Y_inter = exp(sum_{r <= t} dt_r A) C_t S_prev`` from the state that
  ENTERS the chunk.

The products take operands of ``dtype`` (the model's: bfloat16 on the
chip) and accumulate in float32; decays, cumulative sums and the carried
states are float32 whatever ``dtype``. A sequence that is not whole chunks
is padded here with ``dt = 0`` rows, which neither decay nor write. No
state is reset inside a sequence (a packed document's boundary is not
known here).

Two renderings of these equations, chosen by the shapes
(:func:`runs_as_kernels`; no argument, flag or environment variable):

- chunks and states of whole 128-lane tiles, a head's features and a
  group's heads whole sublane tiles (the published 8 heads of 64 a group,
  128 states, chunks of 128): two PALLAS KERNELS under a
  ``jax.custom_vjp``, TOKENS IN LANES. A grid step is one chunk of one
  GROUP's heads: x a ``[K P, chunk]`` block of ``[B, G, K P, S]``, B and C
  ``[N, chunk]`` blocks of ``[B, G, N, S]``, ``dt`` and ``dt A`` ``[K,
  chunk]`` blocks of ``[B, G, K, S]``: a head is P sublanes, and what it
  has one of a token (its step, its running decay) is a row that spreads
  over them. Tokens last is how XLA lays the projections' outputs and
  their cotangents out on the chip when it is free to (the weight
  gradients contract over tokens), so the ``moveaxis`` around the kernels
  is a change of name, not a pass over HBM: kernels that read the model's
  ``[B, S, H P]`` row-major pinned that layout on both projections, whose
  matmuls then ran 12 ms a step slower than the kernels saved (PERF.md
  section 6, PR 48). The chunks of a group follow each other
  ("arbitrary") with the group's state ``[K P, N]`` float32 in a VMEM
  scratch that never leaves the chip between them. The forward kernel
  (``ssd_fwd``) writes y and, as the backward pass's residual, the state
  that ENTERS each chunk (float32 ``[B, n, G, K P, N]``: 134 MB for 64
  heads of 64 x 128 and 64 chunks). Both carry the name ``KEPT``, so a
  recomputed block keeps them and runs no scan kernel. The backward kernel
  (``ssd_bwd``) walks the chunks in reverse with the state's gradient in
  VMEM: it takes ``jax.vjp`` of the same chunk function from the inputs
  and the entering state, so the ``[K, chunk, chunk]`` decays, ``C B^T``
  and the masked square are remade in the kernel and neither they nor
  their gradients pass through HBM. The gradients of A and D are reduced
  outside from per-token and per-lane partial sums (two small ``jnp``
  sums).
- anything narrower (the tiny test models'), and the oracle the kernels
  are tested against: the ``jnp`` form, differentiated by JAX, the carry a
  ``lax.scan``.

Where the scan runs as kernels and the rows of the mixer's ``in_proj``
output split at the edges of a group's block (:func:`mixer_runs_fused`:
the shapes again, the published widths), what lies AROUND the scan in a
Mamba-2 mixer runs here too, ONE pass over HBM a direction and a side
(``models/layers.py:Mamba2Mixer``; ``mamba_inputs`` / ``mamba_output``
there are the ``jnp`` form and the oracle):

- :func:`mamba_pre` (``mamba_pre_fwd`` / ``mamba_pre_bwd`` under one
  ``custom_vjp``) reads ``in_proj``'s output tokens last, [B, rows, S] with
  rows z | x | B | C | dt, in blocks of a group's K P rows (512) by a tile
  of tokens, and writes the scan's operands in the scan's own layouts: the
  causal filter (a step keeps its row block's last 128 tokens in VMEM for
  the block's next tile; zeros before token 0), its bias, SiLU, ``softplus
  (dt + dt_bias)`` and ``dt A``. The split is a choice of row block: a
  step that is not x's (B's, C's, dt's) leaves that output's block as it
  was. Backward, the tiles run last to first (a token's gradient comes
  from the K - 1 tokens AFTER it, which wait in VMEM), the intermediates
  are made again from ``in_proj``'s output, and the parameters' gradients
  add up in one resident block.
- :func:`mamba_post` (``mamba_post_fwd`` / ``mamba_post_bwd``) reads the
  scan's y as it leaves the kernel and z's rows of the same ``in_proj``
  output: the gate and the grouped RMS norm, whose statistic is a sum
  over the rows of ONE block.
- ``in_proj``'s output has ONE gradient array: ``mamba_pre`` hands its
  input on as its last output, ``mamba_post`` reads z from that and its
  backward pass writes z's rows of a new array, which ``mamba_pre``'s
  backward pass gets as that output's cotangent, ALIASES, and fills the
  other rows of. No pass over HBM adds two arrays of that size or
  concatenates three.

Inside a step a loop walks the block 16 rows at a time, so that the
intermediates of a row slice stay near the registers; arithmetic is float32
(where the ``jnp`` form rounds to ``dtype`` after every op).
"""
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_mode

# what a recomputed block keeps of the core: the kernels' y and the state
# that enters each chunk carry this one name, so the backward kernel finds
# both and no scan kernel runs twice (``models/lm.py:TransformerLM._block``)
KEPT = "ssd_core_kept"


def _ssd_jnp(x, dt, a, b, c, d, chunk, dtype):
    """The ``jnp`` form on whole chunks: ``ssd_chunked``'s operands ->
    (y [B, S, H, P] float32 before its cast, the chunks' totals
    [B, n, G, K])."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    K = H // G
    n = S // chunk
    f32 = jnp.float32

    def chunks(t, *head):
        """[B, S, heads.., f] -> [B, n, heads.., L, f]: a chunk's tokens
        beside its features, the two minor axes of every product."""
        t = t.reshape((B, n, chunk) + head + t.shape[t.ndim - 1:])
        return jnp.moveaxis(t, 2, -2)

    # a head is (its group, its place in the group)
    xc = chunks(x, G, K)                                     # [B,n,G,K,L,P]
    dtc = chunks(dt.astype(f32)[..., None], G, K)[..., 0]    # [B,n,G,K,L]
    bc, cc = (chunks(t.astype(dtype), G) for t in (b, c))    # [B,n,G,L,N]
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(G, K, 1), axis=-1)
    total = cum[..., -1]                                     # [B,n,G,K]
    xdt32 = xc.astype(f32) * dtc[..., None]
    xdt = xdt32.astype(dtype)

    # inside a chunk: (L o C B^T)(dt x), L masked before its exp
    decay = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((chunk, chunk), bool)),
        cum[..., :, None] - cum[..., None, :], -jnp.inf))    # [B,n,G,K,L,L]
    cb = jnp.einsum("bngts,bngus->bngtu", cc, bc,
                    preferred_element_type=f32)              # once a group
    y = jnp.einsum("bngktu,bngkup->bngktp",
                   (cb[:, :, :, None] * decay).astype(dtype), xdt,
                   preferred_element_type=f32)

    # a chunk's own state, and the states carried from chunk to chunk
    to_end = jnp.exp(total[..., None] - cum)                 # [B,n,G,K,L]
    own = jnp.einsum("bngkup,bngus->bngkps",
                     (xdt32 * to_end[..., None]).astype(dtype), bc,
                     preferred_element_type=f32)             # [B,n,G,K,P,N]

    def carry(state, chunk_c):
        own_c, total_c = chunk_c
        return jnp.exp(total_c)[..., None, None] * state + own_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((B, G, K, P, N), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                  # [B,n,G,K,P,N]
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bngts,bngkps->bngktp", cc, entering.astype(dtype),
        preferred_element_type=f32)
    y = y + d.astype(f32).reshape(G, K, 1, 1) * xc.astype(f32)
    return jnp.moveaxis(y, -2, 2).reshape(B, S, H, P), total


# ------------------------------------------------------------ the kernels

_LANES = 128
_ROWS = {4: 8, 2: 16}    # sublanes of a tile, by the bytes of an entry
_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b
# a product's gradients are products of the same three forms:
# form -> ((left operand, right operand, form) of da, the same of db),
# the operands named among a, b and the cotangent g
_GRADS = {_NN: (("g", "b", _NT), ("a", "g", _TN)),
          _NT: (("g", "b", _NN), ("g", "a", _TN)),
          _TN: (("b", "g", _NT), ("a", "g", _NN))}


def _dot(a, b, dt, dims):
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), dims, preferred_element_type=jnp.float32,
        precision=_HI if dt == jnp.float32 else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(a, b, dt, dims=_NN):
    """Operands in the model's dtype, float32 accumulation: an einsum of
    the ``jnp`` form. So are its gradients' products (the cotangent cast
    to ``dt`` as an operand, as XLA's default precision does on the chip):
    left to JAX's transpose rule they would multiply a float32 cotangent,
    several passes of the MXU each."""
    return _dot(a, b, dt, dims)


def _mm_fwd(a, b, dt, dims):
    a, b = a.astype(dt), b.astype(dt)
    return _dot(a, b, dt, dims), (a, b)


def _mm_bwd(dt, dims, res, g):
    named = dict(zip("abg", res + (g,)))
    return tuple(_dot(named[l], named[r], dt, form)
                 for l, r, form in _GRADS[dims])


_mm.defvjp(_mm_fwd, _mm_bwd)


def _iotas(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


@functools.partial(jax.jit, static_argnums=(7,))
def _head(x, dt, cum_r, cum_c, cbT, inter, d, dtype):
    """One head of a chunk, TOKENS IN LANES: x [P, L]; dt, the running
    sums and D a lane as rows [1, L], the sums as a column [L, 1] too;
    ``(C B^T)^T`` [L, L] and ``S_prev C^T`` [P, L] -> (y [P, L], dt x
    decayed to the chunk's end [P, L]). The decay is the exp of the sums'
    DIFFERENCES, masked before it. (A ``jax.jit`` so that a kernel's trace
    holds it once, not once a head: the lowering inlines it.)"""
    L = x.shape[1]
    row, col = _iotas((L, L))
    xdt = x * dt
    decayT = jnp.exp(jnp.where(col >= row, cum_r - cum_c, -jnp.inf))
    y = _mm(xdt, cbT * decayT, dtype) + jnp.exp(cum_r) * inter + d * x
    return y, xdt * jnp.exp(cum_r[:, L - 1:] - cum_r)


def _chunk(S, x, dt, dta, b, c, d, dtype):
    """One chunk of the K heads of one group, TOKENS IN LANES, from ops
    Mosaic lowers (and differentiates: the backward kernel takes
    ``jax.vjp`` of this). S [K P, N] float32, the group's state that
    enters the chunk; x [K P, L] float32; dt, dt A and D a lane [K, L]
    float32, a row a head; b, c [N, L] float32 -> (y [K P, L] float32, the
    state that leaves). The header's equations, transposed: a head is P
    sublanes, what it has one of a token is a row that spreads over them."""
    f32 = jnp.float32
    (W, L), K = x.shape, dt.shape[0]
    P = W // K
    row, col = _iotas((L, L))
    # running sums by a 0/1 matmul at float32 precision; the rows turned
    # into columns by ONE transpose, so that a column is its row's number
    # bit for bit (the diagonal's difference is 0)
    sums = lambda ones: jax.lax.dot_general(  # noqa: E731
        dta, ones, _NN, precision=_HI, preferred_element_type=f32)
    cum = sums(jnp.where(row <= col, 1.0, 0.0))
    # (the chunk's total in every lane of the state, by the MXU as well:
    # Mosaic spreads no single number over lanes and sublanes at once)
    total = sums(jnp.ones((L, S.shape[1]), f32))
    cols = jnp.concatenate([cum, jnp.zeros((L - K, L), f32)], axis=0).T
    cbT = _mm(b, c, dtype, _TN)                        # once a group
    inter = _mm(S, c, dtype)                           # [K P, L]
    heads = [_head(x[k * P:(k + 1) * P], dt[k:k + 1], cum[k:k + 1],
                   cols[:, k:k + 1], cbT, inter[k * P:(k + 1) * P],
                   d[k:k + 1], dtype) for k in range(K)]
    survives = jnp.concatenate([
        jnp.broadcast_to(jnp.exp(total[k:k + 1]), (P, S.shape[1]))
        for k in range(K)], axis=0)
    return (jnp.concatenate([y for y, _ in heads], axis=0),
            S * survives + _mm(jnp.concatenate([e for _, e in heads], axis=0),
                               b, dtype, _NT))


def _fwd_kernel(x_ref, dt_ref, dta_ref, b_ref, c_ref, d_ref, y_ref,
                states_ref, state, *, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    f32 = jnp.float32
    states_ref[...] = state[...]
    y, end = _chunk(state[...], x_ref[...].astype(f32), dt_ref[...],
                    dta_ref[...], b_ref[...].astype(f32),
                    c_ref[...].astype(f32), d_ref[...], dtype)
    y_ref[...] = y.astype(y_ref.dtype)
    state[...] = end


def _bwd_kernel(x_ref, dt_ref, dta_ref, b_ref, c_ref, d_ref, states_ref,
                dy_ref, dx_ref, ddt_ref, ddta_ref, db_ref, dc_ref, dd_ref,
                dstate, *, dtype):
    @pl.when(pl.program_id(2) == 0)        # the LAST chunk: reversed grid
    def _():
        dstate[...] = jnp.zeros_like(dstate)    # no state leaves the scan
        dd_ref[...] = jnp.zeros_like(dd_ref)

    f32 = jnp.float32
    _, vjp = jax.vjp(
        functools.partial(_chunk, dtype=dtype), states_ref[...],
        x_ref[...].astype(f32), dt_ref[...], dta_ref[...],
        b_ref[...].astype(f32), c_ref[...].astype(f32), d_ref[...])
    dS, dx, ddt, ddta, db, dc, dd = vjp((dy_ref[...].astype(f32),
                                         dstate[...]))
    dstate[...] = dS
    dx_ref[...] = dx.astype(dx_ref.dtype)
    ddt_ref[...] = ddt
    ddta_ref[...] = ddta
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    dd_ref[...] += dd


# (the limit is room for other widths: at the published ones both kernels
# pass Mosaic under its 16 MiB default too)
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


def _grid(x, dt, b, chunk, chunk_of):
    """(grid (b, g, c), the state scratch, BlockSpecs) for x [B, G, K P, S],
    dt [B, G, K, S] and b [B, G, N, S]: a chunk of a group's x, of its
    heads' rows, of its B, a group's rows of what has no token axis
    ([G, K, chunk], and [B, G, K, chunk] that adds up over the chunks), and
    the state that enters the chunk ([B, n, G, K P, N]); ``chunk_of(c)`` is
    the chunk step c works on."""
    B, G, W, S = x.shape
    K, N = dt.shape[2], b.shape[2]

    def columns(rows):
        return pl.BlockSpec((None, None, rows, chunk),
                            lambda b, g, c: (b, g, 0, chunk_of(c)))
    return ((B, G, S // chunk), pltpu.VMEM((W, N), jnp.float32),
            (columns(W), columns(K), columns(N),
             pl.BlockSpec((None, K, chunk), lambda b, g, c: (g, 0, 0)),
             pl.BlockSpec((None, None, K, chunk),
                          lambda b, g, c: (b, g, 0, 0)),
             pl.BlockSpec((None, None, None, W, N),
                          lambda b, g, c: (b, chunk_of(c), g, 0, 0))))


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _forward(x, dt, dta, b, c, d, chunk, dtype, interpret):
    """TOKENS LAST: x [B, G, K P, S], dt and dt A [B, G, K, S] float32, b,
    c [B, G, N, S], d [G, K, chunk] float32 (D of a head in every lane), S
    whole chunks -> (y [B, G, K P, S] in ``dtype``, the state that enters
    each chunk [B, n, G, K P, N] float32). (A ``jax.jit``, like
    ``_backward``: the layers of a model share one trace and one lowering
    of each kernel; compiled or interpreted is an argument of both and of
    the ``custom_vjp``, not read inside, or a cached trace would outlive
    ``pallas_mode.compiling_for_tpu``.)"""
    grid, scratch, (wide, one, tall, flat, _, per_chunk) = _grid(
        x, dt, b, chunk, lambda c: c)
    return tuple(pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=dtype),
        grid=grid,
        in_specs=[wide, one, one, tall, tall, flat],
        out_specs=[wide, per_chunk],
        out_shape=[jax.ShapeDtypeStruct(x.shape, dtype),
                   jax.ShapeDtypeStruct(grid[:1] + (grid[2], grid[1])
                                        + scratch.shape, jnp.float32)],
        scratch_shapes=[scratch],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_fwd",
    )(x, dt, dta, b, c, d))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _backward(chunk, dtype, interpret, res, dy):
    x, dt, dta, b, c, d, states = res
    n_chunks = states.shape[1]
    grid, scratch, (wide, one, tall, flat, summed, per_chunk) = _grid(
        x, dt, b, chunk, lambda c: n_chunks - 1 - c)
    *grads, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=dtype),
        grid=grid,
        in_specs=[wide, one, one, tall, tall, flat, per_chunk, wide],
        out_specs=[wide, one, one, tall, tall, summed],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (x, dt, dta, b, c)]
        + [jax.ShapeDtypeStruct(dt.shape[:3] + (chunk,), jnp.float32)],
        scratch_shapes=[scratch],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_bwd",
    )(x, dt, dta, b, c, d, states, dy)
    return (*grads, jnp.sum(dd, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd_kernels(x, dt, dta, b, c, d, chunk, dtype, interpret):
    return _forward(x, dt, dta, b, c, d, chunk, dtype, interpret)[0]


def _ssd_fwd(x, dt, dta, b, c, d, chunk, dtype, interpret):
    y, states = _forward(x, dt, dta, b, c, d, chunk, dtype, interpret)
    return y, (x, dt, dta, b, c, d, checkpoint_name(states, KEPT))


_ssd_kernels.defvjp(_ssd_fwd, _backward)


def runs_as_kernels(P: int, N: int, K: int, chunk: int) -> bool:
    """Do K heads of P a group, with N states, in chunks of ``chunk`` run
    through the pallas kernels? Where a chunk's tokens and the states are
    whole 128-lane tiles and a head's features and a group's heads whole
    sublane tiles (of bfloat16: 16 rows; of float32: 8): the published 8
    heads of 64 with 128 states in chunks of 128. On a TPU and,
    interpreted, on the CPU test backend; any other backend raises
    (``pallas_mode.interpret``). Anything narrower (the tiny test models')
    takes the ``jnp`` form."""
    if (N % _LANES or chunk % _LANES or P % _ROWS[2] or K % _ROWS[4]
            or K > chunk):
        return False
    pallas_mode.interpret()
    return True


def ssd_tokens_last(x, dt, dta, b, c, d, chunk, dtype):
    """The kernels on operands already in their layout, TOKENS LAST and
    whole chunks: x [B, G, K P, S] and b, c [B, G, N, S] in ``dtype``, dt
    and dt A [B, G, K, S] float32 (rows past the sequence's end neither
    decay nor write: zeros), d [H] -> (y [B, G, K P, S] in ``dtype``,
    carrying ``KEPT``; the chunks' totals of dt A [B, G, K, n])."""
    G, K = dt.shape[1:3]
    y = _ssd_kernels(
        x, dt, dta, b, c,
        jnp.broadcast_to(d.astype(jnp.float32).reshape(G, K, 1),
                         (G, K, chunk)),
        chunk, dtype, pallas_mode.interpret())
    total = jnp.sum(dta.reshape(dta.shape[:3] + (-1, chunk)), axis=-1)
    return checkpoint_name(y, KEPT), total


def _ssd_pallas(x, dt, a, b, c, d, chunk, dtype):
    """The kernels on ``_ssd_jnp``'s operands, and the layout passes made
    for them: the kernels read TOKENS LAST (a chunk's tokens are lanes,
    which is how XLA lays the projections' outputs out on the chip when it
    is free to), ``dt A`` and D a lane, so that A's and D's gradients are
    ``jnp`` sums of the kernels' partial ones. (A mixer at the published
    widths never comes here: ``mamba_pre`` writes these layouts itself.)"""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    K = H // G

    def tokens_last(t, rows):
        """[B, S, G, .., f] -> [B, G, ``rows``, S]."""
        return jnp.moveaxis(t.reshape(B, S, G, rows), 1, -1)

    dt = dt.astype(jnp.float32)
    y, total = ssd_tokens_last(
        tokens_last(x.astype(dtype), K * P), tokens_last(dt, K),
        tokens_last(dt * a.astype(jnp.float32), K),
        tokens_last(b.astype(dtype), N), tokens_last(c.astype(dtype), N),
        d, chunk, dtype)
    return jnp.moveaxis(y, -1, 1).reshape(B, S, H, P), total    # [B, S, G, K P]


# ------------------------------------- the mixer's passes around the scan
#
# ``in_proj``'s output TOKENS LAST, [B, inner + conv_dim + H, S], is rows
# of z | x | B | C | dt. Where B's and C's G N rows are whole blocks of a
# group's R = K P rows (the published 1,024 and 512), every part starts at
# a block's edge, and the split is a choice of row block, not a copy.

TOKEN_TILE = 1024   # tokens (lanes) one grid step of a pass works on
_SUB = 16           # rows the loop inside a step works on (a bfloat16
#                     tile). What the traced runs of PR 50 ran; the kernels
#                     alone read 14.9 ms a step so, 12.4 at 32 rows and
#                     12.0 at tiles of 2,048 (PERF.md section 6): longer
#                     slices are more independent work for the scheduler,
#                     not yet measured end to end
# (the grid's ORDER is part of every pass: a step leaves its tile's edge to
# the next, and an output block a step does not write stays as it was)
_PASS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


def _sigmoid(x):
    """1 / (1 + exp(-x)) as one transcendental and no divide."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _live(tile, lanes, seq):
    """[1, lanes]: which tokens of tile ``tile`` lie inside the sequence."""
    return (tile * lanes + jax.lax.broadcasted_iota(
        jnp.int32, (1, lanes), 1)) < seq


def _sub(i):
    return pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB)


def _row_loop(rows, body, init=0):
    return jax.lax.fori_loop(0, rows // _SUB, body, init)


def _taps(before, x, K):
    """The K shifted views of ``_SUB`` rows of a tile behind the 128 tokens
    before it: tap k is the input of K - 1 - k tokens ago."""
    ext = jnp.concatenate([before, x], axis=1)
    return [pltpu.roll(ext, K - 1 - k, 1)[:, _LANES:]
            for k in range(K - 1)] + [x]


def _filter(w, bias, taps):
    return bias + sum(w[:, k:k + 1] * tap for k, tap in enumerate(taps))


def _lane_sums(columns):
    """[rows, 128] float32 with column k's sum over its tokens in lane k:
    what a step adds to a parameter's gradient, a row a channel."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return sum(jnp.where(lane == k, jnp.sum(c, axis=1, keepdims=True), 0.0)
               for k, c in enumerate(columns))


def _folded(x):
    """[rows, T] -> [rows, 128]: the 128-token blocks added up."""
    return sum(x[:, k:k + _LANES] for k in range(0, x.shape[1], _LANES))


def _parts(j, nx, nb, refs, body):
    """``body(ref)`` with the one of x's, B's and C's refs that row block
    ``j`` of xBC belongs to."""
    for lo, hi, ref in ((0, nx, refs[0]), (nx, nx + nb, refs[1]),
                        (nx + nb, nx + 2 * nb, refs[2])):
        pl.when((j >= lo) & (j < hi))(functools.partial(body, ref))


def _pre_fwd_kernel(zx_ref, w_ref, bias_ref, dtb_ref, a_ref, x_ref, b_ref,
                    c_ref, dt_ref, dta_ref, tail, *, seq, nx, nb):
    """Step (b, t, j): row block j of xBC over token tile t, a row block's
    tiles first to last; step nx + 2 nb is dt's rows. ``tail`` keeps a row
    block's last 128 raw tokens for its next tile."""
    f32 = jnp.float32
    t, j = pl.program_id(1), pl.program_id(2)
    R, T = zx_ref.shape
    K, H = w_ref.shape[1], dt_ref.shape[0]
    live = _live(t, T, seq)

    def filtered(out_ref):
        def rows(i, _):
            r = _sub(i)
            x = zx_ref[r, :].astype(f32)
            before = jnp.where(t == 0, 0.0, tail[j, r, :].astype(f32))
            tail[j, r, :] = zx_ref[r, T - _LANES:]
            y = _filter(w_ref[r, :], bias_ref[r, :], _taps(before, x, K))
            out_ref[r, :] = jnp.where(live, y * _sigmoid(y), 0.0
                                      ).astype(out_ref.dtype)
            return 0
        _row_loop(R, rows)

    _parts(j, nx, nb, (x_ref, b_ref, c_ref), filtered)

    @pl.when(j == nx + 2 * nb)
    def _():
        dt = jnp.where(live, jax.nn.softplus(
            zx_ref[:H, :].astype(f32) + dtb_ref[...]), 0.0)
        dt_ref[...] = dt
        dta_ref[...] = dt * a_ref[...]


def _pre_bwd_kernel(dx_ref, db_ref, dc_ref, ddt_ref, ddta_ref, zx_ref,
                    head_ref, w_ref, bias_ref, dtb_ref, a_ref, _, dzx_ref,
                    small_ref, ahead, *, seq, nx, nb, tiles):
    """Step (b, t, j): row block j over token tile ``tiles - 1 - t``, a
    row block's tiles LAST to first: what a tile's filter gradient owes
    the K - 1 tokens before it waits in ``ahead`` for the next step. The
    gradients of what has no token axis add up in ``small_ref``'s resident
    block: lane k of row block j is the filter's tap k (lane K the bias),
    of dt's step ``dt_bias`` and A. z's rows of ``dzx_ref`` (the aliased
    last input) stay as they came."""
    f32 = jnp.float32
    t, j = pl.program_id(1), pl.program_id(2)
    tile = tiles - 1 - t
    R, T = zx_ref.shape
    K, H = w_ref.shape[1], ddt_ref.shape[0]
    live = _live(tile, T, seq)

    @pl.when((pl.program_id(0) == 0) & (t == 0) & (j == 0))
    def _():
        small_ref[...] = jnp.zeros_like(small_ref)

    def filtered(cot_ref):
        def rows(i, _):
            r = _sub(i)
            x = jnp.where(live, zx_ref[r, :].astype(f32), 0.0)
            before = jnp.where(tile == 0, 0.0, head_ref[r, :].astype(f32))
            taps = _taps(before, x, K)
            w = w_ref[r, :]
            y = _filter(w, bias_ref[r, :], taps)
            gate = _sigmoid(y)
            dy = jnp.where(live, cot_ref[r, :].astype(f32)
                           * gate * (1.0 + y * (1.0 - gate)), 0.0)
            later = jnp.concatenate(
                [dy, jnp.where(t == 0, 0.0, ahead[j, r, :])], axis=1)
            ahead[j, r, :] = dy[:, :_LANES]
            dzx_ref[r, :] = (w[:, K - 1:] * dy + sum(
                w[:, k:k + 1] * pltpu.roll(
                    later, T + _LANES - (K - 1 - k), 1)[:, :T]
                for k in range(K - 1))).astype(dzx_ref.dtype)
            small_ref[j, r, :] += _lane_sums(
                [dy * tap for tap in taps] + [dy])
            return 0
        _row_loop(R, rows)

    _parts(j, nx, nb, (dx_ref, db_ref, dc_ref), filtered)

    @pl.when(j == nx + 2 * nb)
    def _():
        z = jnp.where(live, zx_ref[:H, :].astype(f32) + dtb_ref[...], 0.0)
        ddta = jnp.where(live, ddta_ref[...], 0.0)
        draw = (jnp.where(live, ddt_ref[...], 0.0)
                + ddta * a_ref[...]) * _sigmoid(z)
        dzx_ref[:H, :] = draw.astype(dzx_ref.dtype)
        small_ref[j, :H, :] += _lane_sums([draw, ddta * jax.nn.softplus(z)])


def _pre_specs(zx, dims, padded, tile, last_first):
    """(grid (b, t, j), BlockSpecs) of a pass over xBC's and dt's rows of
    ``zx`` [B, rows, S] in blocks of R rows and tiles of T tokens:
    ``zx``'s own block, the 128 tokens before it, x's, B's, C's and dt's
    blocks of the scan's operands (a step that is not theirs keeps the
    block of their nearest step), a block of what has R rows and no token
    axis, and H rows whole. Step t works on tile t, or with ``last_first``
    on tile ``tiles - 1 - t``."""
    inner, GN, H, R = dims
    nx, nb = inner // R, GN // R
    J = nx + 2 * nb
    T = min(tile, padded)
    tiles = pl.cdiv(padded, T)
    tile_of = (lambda t: tiles - 1 - t) if last_first else (lambda t: t)

    def tokens(rows, block):
        return pl.BlockSpec((None, rows, T),
                            lambda b, t, j: (b, block(j), tile_of(t)))
    return (
        (zx.shape[0], tiles, J + 1),
        tokens(R, lambda j: nx + j),
        pl.BlockSpec((None, R, _LANES), lambda b, t, j: (
            b, nx + j, jnp.maximum(tile_of(t) * (T // _LANES) - 1, 0))),
        (tokens(R, lambda j: jnp.minimum(j, nx - 1)),
         tokens(R, lambda j: jnp.clip(j - nx, 0, nb - 1)),
         tokens(R, lambda j: jnp.clip(j - nx - nb, 0, nb - 1)),
         tokens(H, lambda j: 0), tokens(H, lambda j: 0)),
        lambda n: pl.BlockSpec(
            (R, n), lambda b, t, j: (jnp.minimum(j, J - 1), 0)),
        pl.BlockSpec((H, 1), lambda b, t, j: (0, 0)))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _pre_forward(zx, w, bias, dtb, a, dims, padded, dtype, tile, interpret):
    inner, GN, H, R = dims
    grid, own, _, outs, flat, heads = _pre_specs(zx, dims, padded, tile,
                                                 False)
    B, S = zx.shape[0], zx.shape[2]
    return tuple(pl.pallas_call(
        functools.partial(_pre_fwd_kernel, seq=S, nx=inner // R,
                          nb=GN // R),
        grid=grid,
        in_specs=[own, flat(w.shape[1]), flat(1), heads, heads],
        out_specs=list(outs),
        out_shape=[jax.ShapeDtypeStruct((B, rows, padded), dt)
                   for rows, dt in ((inner, dtype), (GN, dtype), (GN, dtype),
                                    (H, jnp.float32), (H, jnp.float32))],
        scratch_shapes=[pltpu.VMEM((grid[2] - 1, R, _LANES), zx.dtype)],
        compiler_params=_PASS,
        interpret=interpret,
        name="mamba_pre_fwd",
    )(zx, w, bias, dtb, a))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _pre_backward(dims, padded, dtype, tile, interpret, res, cot):
    zx, w, bias, dtb, a = res
    inner, GN, H, R = dims
    grid, own, head, cots, flat, heads = _pre_specs(zx, dims, padded, tile,
                                                    True)
    K = w.shape[1]
    tiles, steps = grid[1:]
    dzx, small = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, seq=zx.shape[2], nx=inner // R,
                          nb=GN // R, tiles=tiles),
        grid=grid,
        in_specs=list(cots) + [own, head, flat(K), flat(1), heads, heads,
                               pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[own, pl.BlockSpec((steps, R, _LANES),
                                     lambda b, t, j: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(zx.shape, zx.dtype),
                   jax.ShapeDtypeStruct((steps, R, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((steps - 1, R, _LANES), jnp.float32)],
        input_output_aliases={len(cots) + 6: 0},
        compiler_params=_PASS,
        interpret=interpret,
        name="mamba_pre_bwd",
    )(*cot[:5], zx, zx, w, bias, dtb, a, cot[5])
    taps = small[:-1].reshape(-1, _LANES)
    return (dzx, taps[:, :K], taps[:, K:K + 1], small[-1, :H, :1],
            small[-1, :H, 1:2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _mamba_pre(zx, w, bias, dtb, a, dims, padded, dtype, tile, interpret):
    return _pre_forward(zx, w, bias, dtb, a, dims, padded, dtype, tile,
                        interpret) + (zx,)


def _mamba_pre_fwd(*args):
    return _pre_forward(*args) + args[:1], args[:5]


_mamba_pre.defvjp(_mamba_pre_fwd, _pre_backward)


def mixer_runs_fused(P: int, N: int, K: int, G: int, chunk: int,
                     taps: int) -> bool:
    """Does a mixer of these shapes run ``mamba_pre`` / ``mamba_post``
    around its scan? Where the scan runs as kernels
    (:func:`runs_as_kernels`) and the rows of ``in_proj``'s output are
    whole blocks of a group's K P: B's and C's G N rows, the G K rows of
    dt inside one, whole bfloat16 tiles of them, and a filter no longer
    than the 128 tokens a tile sees of the tile before it."""
    R, H = K * P, G * K
    if G * N % R or H > R or H % _ROWS[2] or not 1 < taps <= _LANES:
        return False
    return runs_as_kernels(P, N, K, chunk)


def mamba_pre(zx, w, b, dt_bias, a_log, groups, state, chunk, dtype,
              tile=TOKEN_TILE):
    """Everything element-wise between ``in_proj`` and the scan, ONE pass
    over HBM a direction, TOKENS IN LANES: ``in_proj``'s output tokens
    last [B, inner + conv_dim + H, S] (rows z | x | B | C | dt), the filter
    [taps, conv_dim] and its bias, ``dt_bias`` and ``A_log`` [H] ->
    :func:`ssd_tokens_last`'s x [B, G, K P, S'], dt and dt A [B, G, K, S']
    (float32), B and C [B, G, N, S'] with S' whole chunks and zeros past
    the sequence's end, and ``zx`` itself once more, for
    :func:`mamba_post` to read z's rows of: ``x, B, C = silu(filter(xBC)
    + bias)`` (zeros before token 0), ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``. Float32 arithmetic in the tile; the backward pass
    makes the intermediates again from ``zx``, its only residual, and
    WRITES xBC's and dt's rows of ``zx``'s gradient into the array that
    comes back as the last output's: ``mamba_post`` fills z's rows of it
    and no other, so the two passes share one array and nothing adds two
    of that size. (Handing the last output to anything else leaves its
    gradient's other rows unread.)"""
    B, rows, S = zx.shape
    H, GN = a_log.shape[0], groups * state
    inner = (rows - H - 2 * GN) // 2
    R = inner // groups
    f32 = jnp.float32
    column = lambda t: t.astype(f32)[:, None]  # noqa: E731
    x, bs, cs, dt, dta, zx = _mamba_pre(
        zx, w.astype(f32).T, column(b), column(dt_bias),
        column(-jnp.exp(a_log)), (inner, GN, H, R), S + -S % chunk, dtype,
        tile, pallas_mode.interpret())
    per_group = lambda t: t.reshape(B, groups, -1, t.shape[-1])  # noqa: E731
    return tuple(per_group(t) for t in (x, dt, dta, bs, cs)) + (zx,)


# ---- after the scan: the gate and the grouped RMS norm


def _post_fwd_kernel(y_ref, z_ref, w_ref, out_ref, gated, *, eps):
    """Step (b, g, t): one group's R rows over token tile t; the mean
    square is a sum over the block's rows."""
    f32 = jnp.float32
    R, T = y_ref.shape

    def gate(i, squares):
        r = _sub(i)
        z = z_ref[r, :].astype(f32)
        u = y_ref[r, :].astype(f32) * z * _sigmoid(z)
        gated[r, :] = u
        return squares + u * u

    squares = _row_loop(R, gate, jnp.zeros((_SUB, T), f32))
    scale = jax.lax.rsqrt(
        jnp.sum(squares, axis=0, keepdims=True) * (1.0 / R) + eps)

    def norm(i, _):
        r = _sub(i)
        out_ref[r, :] = (gated[r, :] * scale * w_ref[r, :]
                         ).astype(out_ref.dtype)
        return 0
    _row_loop(R, norm)


def _post_bwd_kernel(dout_ref, y_ref, z_ref, w_ref, dy_ref, dzx_ref, dw_ref,
                     gated, *, seq, eps):
    f32 = jnp.float32
    t = pl.program_id(2)
    R, T = y_ref.shape
    live = _live(t, T, seq)

    @pl.when(t == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def dout(r):
        return jnp.where(live, dout_ref[r, :].astype(f32), 0.0)

    def gate(i, sums):
        r = _sub(i)
        z = jnp.where(live, z_ref[r, :].astype(f32), 0.0)
        u = y_ref[r, :].astype(f32) * z * _sigmoid(z)
        gated[r, :] = u
        return sums[0] + u * u, sums[1] + dout(r) * w_ref[r, :] * u

    zeros = jnp.zeros((_SUB, T), f32)
    squares, along = (jnp.sum(s, axis=0, keepdims=True) * (1.0 / R)
                      for s in _row_loop(R, gate, (zeros, zeros)))
    scale = jax.lax.rsqrt(squares + eps)
    along = along * scale * scale     # mean(dn n) n = this times u, scaled

    def grads(i, _):
        r = _sub(i)
        u, d = gated[r, :], dout(r)
        du = scale * (d * w_ref[r, :] - u * along)
        z = jnp.where(live, z_ref[r, :].astype(f32), 0.0)
        s = _sigmoid(z)
        dy_ref[r, :] = (du * z * s).astype(dy_ref.dtype)
        dzx_ref[r, :] = (du * y_ref[r, :].astype(f32) * s
                         * (1.0 + z * (1.0 - s))).astype(dzx_ref.dtype)
        dw_ref[r, :] += _folded(d * u * scale)
        return 0
    _row_loop(R, grads)


def _post_specs(y, tile):
    """(grid (b, g, t), a group's rows over a token tile of [B, G, R, S'],
    of [B, rows, S], the group's rows of [inner, n], and of [B, inner,
    128] that adds up over the tiles)."""
    B, G, R, padded = y.shape
    T = min(tile, padded)
    return ((B, G, pl.cdiv(padded, T)),
            pl.BlockSpec((None, None, R, T), lambda b, g, t: (b, g, 0, t)),
            pl.BlockSpec((None, R, T), lambda b, g, t: (b, g, t)),
            pl.BlockSpec((R, 1), lambda b, g, t: (g, 0)),
            pl.BlockSpec((None, R, _LANES), lambda b, g, t: (b, g, 0)),
            pltpu.VMEM((R, T), jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _post_forward(y, zx, w, eps, dtype, tile, interpret):
    grid, group, rows, flat, _, scratch = _post_specs(y, tile)
    B, G, R, _ = y.shape
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[group, rows, flat],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((B, G * R, zx.shape[2]), dtype),
        scratch_shapes=[scratch],
        compiler_params=_PASS,
        interpret=interpret,
        name="mamba_post_fwd",
    )(y, zx, w)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _post_backward(eps, dtype, tile, interpret, res, dout):
    y, zx, w = res
    grid, group, rows, flat, summed, scratch = _post_specs(y, tile)
    B, G, R, _ = y.shape
    dy, dzx, dw = pl.pallas_call(
        functools.partial(_post_bwd_kernel, seq=zx.shape[2], eps=eps),
        grid=grid,
        in_specs=[rows, group, rows, flat],
        out_specs=[group, rows, summed],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(zx.shape, zx.dtype),
                   jax.ShapeDtypeStruct((B, G * R, _LANES), jnp.float32)],
        scratch_shapes=[scratch],
        compiler_params=_PASS,
        interpret=interpret,
        name="mamba_post_bwd",
    )(dout, y, zx, w)
    return dy, dzx, jnp.sum(dw, axis=(0, 2))[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mamba_post(y, zx, w, eps, dtype, tile, interpret):
    return _post_forward(y, zx, w, eps, dtype, tile, interpret)


def _mamba_post_fwd(*args):
    return _post_forward(*args), args[:3]


_mamba_post.defvjp(_mamba_post_fwd, _post_backward)


def mamba_post(y, zx, scale, eps, dtype, tile=TOKEN_TILE):
    """Everything element-wise between the scan and ``out_proj``, one pass
    a direction, TOKENS IN LANES: the scan's y [B, G, K P, S'] as it
    leaves the kernel, ``mamba_pre``'s last output [B, rows, S] (z is its
    first rows) and the norm's weight [inner] -> ``rms_normalize(y *
    silu(z))`` over each group's K P rows, times the weight, [B, inner, S]
    in ``dtype``; a group's rows are one block, so the mean square stays in
    the tile. The backward pass reads the same three; of ``zx``'s gradient
    it WRITES z's rows and leaves the others to ``mamba_pre``'s (they hold
    no number until then)."""
    return _mamba_post(y, zx, scale.astype(jnp.float32)[:, None], eps, dtype,
                       tile, pallas_mode.interpret())


def ssd_chunked(x, dt, a, b, c, d, chunk: int, dtype=jnp.float32):
    """``x`` [B, S, H, P], ``dt`` [B, S, H] float32 (positive: after its
    softplus), ``a`` [H] float32 (negative), ``b`` / ``c`` [B, S, G, N]
    with H a multiple of G (head h reads group ``h // (H / G)``), ``d``
    [H] -> (y [B, S, H, P] in ``dtype``, the mean over batch, chunks and
    heads of ``exp(sum_chunk dt A)``: what of a chunk's incoming state
    survives the chunk). One algorithm, two renderings chosen by the
    shapes (:func:`runs_as_kernels`)."""
    S, H, P = x.shape[1:]
    G, N = b.shape[-2:]
    if H % G:
        raise ValueError("%d heads do not share %d groups of B and C"
                         % (H, G))
    pad = -S % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    form = _ssd_pallas if runs_as_kernels(P, N, H // G, chunk) else _ssd_jnp
    y, total = form(x, dt, a, b, c, d, chunk, dtype)
    return y[:, :S].astype(dtype), jnp.mean(jnp.exp(total))
