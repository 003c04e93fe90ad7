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


def _ssd_pallas(x, dt, a, b, c, d, chunk, dtype):
    """The kernels on ``_ssd_jnp``'s operands, and the layout passes made
    for them: the kernels read TOKENS LAST (a chunk's tokens are lanes,
    which is how XLA lays the projections' outputs out on the chip when it
    is free to), ``dt A`` and D a lane, so that A's and D's gradients are
    ``jnp`` sums of the kernels' partial ones."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    K = H // G
    f32 = jnp.float32

    def tokens_last(t, rows):
        """[B, S, G, .., f] -> [B, G, ``rows``, S]."""
        return jnp.moveaxis(t.reshape(B, S, G, rows), 1, -1)

    dt = dt.astype(f32)
    dta = dt * a.astype(f32)
    y = _ssd_kernels(
        tokens_last(x.astype(dtype), K * P), tokens_last(dt, K),
        tokens_last(dta, K), tokens_last(b.astype(dtype), N),
        tokens_last(c.astype(dtype), N),
        jnp.broadcast_to(d.astype(f32).reshape(G, K, 1), (G, K, chunk)),
        chunk, dtype, pallas_mode.interpret())
    y = jnp.moveaxis(checkpoint_name(y, KEPT), -1, 1)       # [B, S, G, K P]
    total = jnp.sum(dta.reshape(B, S // chunk, chunk, G, K), axis=2)
    return y.reshape(B, S, H, P), total


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
