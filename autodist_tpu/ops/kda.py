"""Kimi Delta Attention's core: the gated delta rule with a per-channel
decay, in its CHUNKED form (arXiv 2510.26692; the delta rule's WY / UT
representation of arXiv 2406.06484 with a diagonal decay).

Per head, with a state S [d_k, d_v] that starts at zero:

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``g`` <= 0 is the log of the decay, one per key CHANNEL; ``b`` in [0, 1]
is the write strength. Token by token that is 8,192 dependent steps of
tiny matmuls; here a sequence is cut into chunks of ``CHUNK`` tokens and
only the state is carried from chunk to chunk (``lax.scan``). Inside a
chunk, with G the running sum of g from the chunk's start and w_j = b_j
(v_j - S_{j-1}^T (exp(g_j) k_j)) the corrected value each token writes:

    (I + L) W = b (V - (K exp(G)) S_0),  L_jl = b_j M_jl (l < j),
                                         M_jl = sum_c k_jc k_lc exp(G_jc - G_lc)
    O = (Q exp(G)) S_0 + P W,            P_il = sum_c q_ic k_lc exp(G_ic - G_lc)  (l <= i)
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T W

so the work is matmuls of [C, d] x [d, C] and [C, C] x [C, d], one
unit-lower-triangular solve of size C, and one state read and update per
chunk.

Two renderings of these equations, chosen by the head widths
(:func:`runs_as_kernels`; no argument, flag or environment variable):

- heads of whole 128-lane tiles (the published 128): PALLAS KERNELS under
  a ``jax.custom_vjp``. A grid step is one chunk of ``_heads_per_step``
  heads; the chunks of a head follow each other ("arbitrary") with the
  state in a VMEM scratch that never leaves the chip between them. The
  forward kernel (``kda_fwd``) writes o and, as the backward pass's
  residual, the state each chunk starts from (float32 [B, H, N, d_v, d_k]:
  268 MB for 32 heads of 128 x 128 and 128 chunks; named ``KEPT`` so that
  a recomputed block keeps it and the forward runs once a step). The
  backward kernel (``kda_bwd``) walks the chunks in reverse with the
  state's gradient in VMEM: it takes ``jax.vjp`` of the same chunk
  function from the inputs and that state, so M, P and the solve are
  recomputed in the kernel and no [C, C], [SUB, SUB, d] or per-chunk
  float32 transient passes through HBM. The solve is an explicit inverse
  of the factor by float32 matmuls (``_unit_lower_inverse``), no
  ``triangular-solve`` call. The kernels read q, k, v, g in the model's
  own [B, S, H * d] layout: nothing is transposed around them.
- narrower heads (the tiny test models'), and the oracle the kernels'
  gradients are tested against: the ``lax`` form. What the chunks do not
  share (M, P, the solve) is computed for all chunks at once, the state is
  carried by ``lax.scan``; autodiff's residuals of the scan are one state
  per chunk.

Around the core, by the same rule: where the delta rule runs as kernels,
everything element-wise the mixer does between its projections and the
core (``kda_pre``: the K-tap causal filter, SiLU, the per-head L2 norm, the
decay's softplus) and between the core and ``o_proj`` (``kda_post``: the
per-head RMSNorm and its sigmoid gate) is ONE pass over HBM a direction,
each a ``jax.custom_vjp`` of two pallas kernels (``kda_pre_fwd`` /
``kda_pre_bwd``, ``kda_post_fwd`` / ``kda_post_bwd``) that read and write
the layout the core's kernels read: [B, S, H * d], lane-dense, padded to
whole chunks by the kernel that writes it. A grid step is ``ROW_TILE`` rows
of ``HEAD_LANES`` lanes (whole heads: a head's sum of squares is reduced
and spread again inside the tile and never passes through HBM); the filter
reads the last rows of the tile before its own through a second BlockSpec
on the same array (zeros before the sequence's start), its gradient walks
the tiles last to first and carries what a tile owes the rows before it
in VMEM; the gradients of what has no row axis (the filters, ``dt_bias``,
``A_log``, ``o_norm``) add up in a block that stays resident over a lane
block's tiles. The backward kernels make the forward's intermediates again
from the projections' outputs, their only residuals: no float32 tensor is
kept. Arithmetic in the tile is float32 whatever ``dtype`` (XLA's form, which
narrower heads keep in ``models/layers.py:kda_inputs`` / ``kda_output`` and
which the passes are tested against, filters and gates in ``dtype``).

Numerics. Only the DIFFERENCES exp(G_i - G_l), l <= i, are <= 1: written
as (q exp(G)) (k exp(-G))^T the second factor overflows float32 once a
channel decays by e^-88 inside a chunk (g = -1.4 a token does). So M and P
are built from sub-blocks of ``SUB`` tokens. Between two sub-blocks the
difference is split at the later one's first token a: exp(G_i - G_a)
exp(G_a - G_l), both <= 1, a matmul. Inside a sub-block the [SUB, SUB, d_k]
differences are formed and summed as they stand. Everything elementwise
and the solve run in float32; matmul operands are cast to ``dtype``
(bfloat16 in the model) and accumulate in float32.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_mode

CHUNK = 64   # tokens per chunk: one state update per chunk
SUB = 16     # tokens per sub-block of the decay differences
HEAD_GROUP = 8   # heads worked on together (the ``lax`` form)
# what a recomputed block keeps of the core: ``models/layers.py`` gives the
# output this name, the kernels' forward gives it to the state each chunk
# starts from, so the backward kernel finds both and no forward runs twice
KEPT = "kda_core_out"


@jax.checkpoint
def _within_sub_blocks(rows, cols, G):
    """sum_c rows[i, c] cols[l, c] exp(G[i, c] - G[l, c]) for i, l of ONE
    sub-block, l <= i (0 above the diagonal): [..., n, SUB, d] ->
    [..., n, SUB, SUB]. Recomputed in the backward pass: the
    [SUB, SUB, d] differences are never stored."""
    diff = G[..., :, None, :] - G[..., None, :, :]
    lower = np.tril(np.ones((SUB, SUB), bool))[..., None]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))  # > 1 only where masked
    return jnp.sum(rows[..., :, None, :] * cols[..., None, :, :] * decay,
                   axis=-1)


def _decayed_products(rows, k, G, mm):
    """[..., C, C] of sum_c rows[i, c] k[l, c] exp(G[i, c] - G[l, c]) for
    l <= i, zero above the diagonal; rows, k, G [..., C, d]."""
    C, d = G.shape[-2:]
    n = C // SUB
    sub = lambda t: t.reshape(t.shape[:-2] + (n, SUB, d))  # noqa: E731
    Gs = sub(G)
    anchor = Gs[..., 0, :]                                     # [..., n, d]
    # a later sub-block I against every token l before it
    to_anchor = jnp.exp(Gs - anchor[..., None, :])             # <= 1
    before = (np.arange(C)[None, :] < SUB * np.arange(n)[:, None])[..., None]
    from_anchor = jnp.exp(jnp.where(                           # [..., n, C, d]
        before, anchor[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    off = mm(sub(rows) * to_anchor,
             jnp.swapaxes(k[..., None, :, :] * from_anchor, -1, -2))
    off = off.reshape(off.shape[:-3] + (C, C))                 # [..., C, C]
    diag = _within_sub_blocks(sub(rows), sub(k), Gs)       # [..., n, SUB, SUB]
    eye = jnp.eye(n, dtype=diag.dtype)
    diag = jnp.einsum("...nil,nm->...niml", diag, eye).reshape(off.shape)
    return off + diag


def _chunked(q, k, v, g, beta, dt):
    """``kda_chunked`` of whole chunks: [B, H, N, C, .] float32 inputs
    (beta [B, H, N, C, 1]) -> (o [B, H, N, C, d_v], final state)."""
    f32 = jnp.float32
    dv = v.shape[-1]

    def mm(a, b):
        return jnp.matmul(a.astype(dt), b.astype(dt),
                          preferred_element_type=f32)

    G = jnp.cumsum(g, axis=-2)
    decay_in = jnp.exp(G)                    # from the chunk's start, <= 1
    decay_out = jnp.exp(G[..., -1:, :] - G)  # to the chunk's end, <= 1

    # ---- what the chunks do not share
    # (two calls: XLA forms the decays they share once; k and q stacked
    # into one call cost 0.47 GB more scratch in the cell's step)
    M = _decayed_products(k, k, G, mm)
    P = _decayed_products(q, k, G, mm)
    unit_lower = jnp.eye(CHUNK, dtype=f32) + jnp.tril(beta * M, -1)
    rhs = jnp.concatenate([beta * v, beta * k * decay_in], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        unit_lower, rhs, lower=True, unit_diagonal=True)
    w_v, w_k = solved[..., :dv], solved[..., dv:]

    # ---- the state, chunk after chunk
    def step(state, c):
        w_v, w_k, q_in, P, k_out, decay_end = c
        w = w_v - mm(w_k, state)                          # [B, H, C, d_v]
        o = mm(q_in, state) + mm(P, w)
        state = decay_end[..., None] * state \
            + mm(jnp.swapaxes(k_out, -1, -2), w)
        return state, o

    per_chunk = (w_v, w_k, q * decay_in, P, k * decay_out,
                 decay_in[..., -1, :])
    state, o = jax.lax.scan(
        step, jnp.zeros(q.shape[:2] + (q.shape[-1], dv), f32),
        jax.tree_util.tree_map(
            functools.partial(jnp.moveaxis, source=2, destination=0),
            per_chunk))
    return jnp.moveaxis(o, 0, 2), state


def _kda_lax(q, k, v, g, beta, dtype=None):
    """q, k [B, S, H, d_k] (normalised and scaled by the caller), v
    [B, S, H, d_v], g [B, S, H, d_k] float32 log-decay (<= 0), beta
    [B, S, H]: (o [B, S, H, d_v] in ``dtype``, final state
    [B, H, d_k, d_v] float32). Any S: the tail is padded with tokens that
    neither decay nor write. The ``lax`` form: heads are worked
    ``HEAD_GROUP`` at a time (``lax.map``), each group recomputed in the
    backward pass, so the float32 transients of all heads never live
    together (3 GB a layer at 32 heads of 128 and 8,192 tokens)."""
    dt = dtype or q.dtype
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    pad = -S % CHUNK
    N = (S + pad) // CHUNK
    hg = math.gcd(H, HEAD_GROUP)

    def grouped(t):
        """[B, S, H, ...] -> [H / hg, B, hg, N, CHUNK, ...] float32."""
        t = jnp.pad(t.astype(jnp.float32),
                    [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        t = t.reshape((B, N, CHUNK, H // hg, hg) + t.shape[3:])
        return jnp.moveaxis(t, (3, 4), (0, 2))

    o, state = jax.lax.map(
        jax.checkpoint(lambda x: _chunked(*x, dt)),
        tuple(grouped(t) for t in (q, k, v, g, beta[..., None])))
    # [H / hg, B, hg, N, C, d_v] -> [B, S, H, d_v]
    o = jnp.moveaxis(o, (0, 2), (3, 4)).reshape(B, N * CHUNK, H, dv)[:, :S]
    state = jnp.moveaxis(state, 0, 1).reshape(B, H, dk, dv)
    return o.astype(dt), state


# ------------------------------------------------------------ the kernels

_LANES = 128
HEAD_LANES = 512     # lanes of heads one grid step of a kernel works on
_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _exact(a, b, dims=_NN):
    """A float32 matmul at float32 precision on the MXU (the inverse of
    the unit-triangular factor and its product with the right-hand side:
    what the ``lax`` form leaves to float32 ops)."""
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _iotas(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _sum_with(ones, x):
    """``ones @ x`` for a 0/1 matrix and float32 x, exactly: x is split
    into three bfloat16 parts that hold all of its mantissa, and a product
    with 0 or 1 rounds nothing: three MXU passes where a float32 matmul
    takes six."""
    bf = jnp.bfloat16
    hi = x.astype(bf)
    rest = x - hi.astype(x.dtype)
    mid = rest.astype(bf)
    low = (rest - mid.astype(x.dtype)).astype(bf)
    part = lambda t: jnp.dot(ones.astype(bf), t,   # noqa: E731
                             preferred_element_type=jnp.float32)
    return part(hi) + part(mid) + part(low)


@jax.custom_vjp
def _running_sum(g):
    """cumsum over the rows of one chunk [C, d], float32."""
    row, col = _iotas((g.shape[0],) * 2)
    return _sum_with(jnp.where(row >= col, 1.0, 0.0), g)


def _running_sum_bwd(_, dG):
    row, col = _iotas((dG.shape[0],) * 2)
    return (_sum_with(jnp.where(row <= col, 1.0, 0.0), dG),)


_running_sum.defvjp(lambda g: (_running_sum(g), None), _running_sum_bwd)


def _per_head(Xp):
    """[C, h C] (h matrices side by side in the lanes) -> the [h C, h C]
    block-diagonal matrix of them: ``Ap @ _per_head(Bp)`` is every head's
    own product, side by side again, in ONE pass of C rows through the
    MXU (a 64 x 64 product alone uses a quarter of the array)."""
    C, W = Xp.shape
    if W == C:
        return Xp
    row, col = _iotas((W, W))
    return jnp.where(row // C == col // C,
                     jnp.concatenate([Xp] * (W // C), axis=0), 0.0)


@jax.custom_vjp
def _unit_lower_inverse(Np):
    """(I + N)^-1 of strictly lower-triangular N [C, C], float32, for the
    h matrices of Np [C, h C], by matmuls alone: the ``SUB``-wide diagonal
    blocks by the finite Neumann product (N_d^SUB = 0), then block pairs
    merged twice over ((D + E)^-1 = D^-1 - D^-1 E D^-1 where E holds one
    block below each diagonal block of D)."""
    C = Np.shape[0]
    row, col = _iotas(Np.shape)
    col = col % C
    Nd = jnp.where(row // SUB == col // SUB, Np, 0.0)
    T = jnp.where(row == col, 1.0, 0.0) - Nd
    power, per_head, width = Nd, _per_head(Nd), 2
    while width < SUB:
        power = _exact(power, per_head)
        per_head = _per_head(power)
        T = T + _exact(T, per_head)
        width *= 2
    width = SUB
    while width < C:
        E = jnp.where((row // width == col // width + 1)
                      & (row // (2 * width) == col // (2 * width)), Np, 0.0)
        T = T - _exact(_exact(T, _per_head(E)), _per_head(T))
        width *= 2
    return T


def _unit_lower_inverse_fwd(Np):
    T = _unit_lower_inverse(Np)
    return T, T


def _unit_lower_inverse_bwd(T, dT):
    """dN = -T^T dT T^T, below the diagonal, head by head."""
    C, W = T.shape
    row, col = _iotas(T.shape)
    both = _exact(T, dT, _TN)               # [h C, h C]: every head pair
    own = both[:C]
    for h in range(1, W // C):
        own = jnp.where(col // C == h, both[h * C:(h + 1) * C], own)
    dN = -_exact(own, _per_head(T), _NT)
    return (jnp.where(row > col % C, dN, 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)
_invert = jax.jit(_unit_lower_inverse)


def _products(q, k, G, dt):
    """M (rows of k) and P (rows of q) against the columns of k, [C, C]
    each, zero above the diagonal: ``_decayed_products`` from ops Mosaic
    lowers."""
    f32 = jnp.float32
    C, dk = q.shape
    n = C // SUB
    row, col = _iotas((C, C))
    r = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    # a later sub-block against every token before it: split at its anchor
    off = [jnp.zeros((2 * SUB, C), f32)]
    for i in range(1, n):
        rows = slice(i * SUB, (i + 1) * SUB)
        anchor = G[i * SUB:i * SUB + 1]
        to_anchor = jnp.exp(G[rows] - anchor)                    # <= 1
        from_anchor = jnp.exp(jnp.where(r < i * SUB, anchor - G, -jnp.inf))
        off.append(_mm(jnp.concatenate([k[rows] * to_anchor,
                                        q[rows] * to_anchor]),
                       k * from_anchor, dt, _NT))
    M = jnp.concatenate([o[:SUB] for o in off])
    P = jnp.concatenate([o[SUB:] for o in off])
    # inside a sub-block: column l of all n blocks at once, the
    # [SUB, SUB, d] differences as they stand
    own = lambda t, l: jnp.concatenate([   # noqa: E731
        jnp.broadcast_to(t[i * SUB + l:i * SUB + l + 1], (SUB, dk))
        for i in range(n)])
    for l in range(SUB):
        decay = jnp.exp(jnp.where(r % SUB >= l, G - own(G, l), -jnp.inf))
        cols = own(k, l) * decay
        hit = col == row - row % SUB + l
        M = M + jnp.where(hit, jnp.sum(k * cols, axis=-1, keepdims=True), 0.0)
        P = P + jnp.where(hit, jnp.sum(q * cols, axis=-1, keepdims=True), 0.0)
    return M, P


def _mm(a, b, dt, dims=_NN):
    """Operands in the model's dtype, float32 accumulation: ``mm`` of the
    ``lax`` form."""
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), dims, preferred_element_type=jnp.float32,
        precision=_HI if dt == jnp.float32 else None)


@functools.partial(jax.jit, static_argnums=4)
def _factors(q, k, g, beta, dt):
    """What one head's chunk does not share with the chunk before it: (the
    running sum G of g, P, and the strictly lower part N of the
    unit-triangular factor I + tril(beta M, -1))."""
    row, col = _iotas((CHUNK, CHUNK))
    G = _running_sum(g)
    M, P = _products(q, k, G, dt)
    return G, P, jnp.where(row > col, beta * M, 0.0)


@functools.partial(jax.jit, static_argnums=8)
def _through_state(St, q, k, v, beta, G, P, T, dt):
    """(o, the transposed state the next chunk starts from) of one head
    from the state its chunk starts from and ``_factors``' parts, T the
    factor's inverse."""
    G_end = G[CHUNK - 1:]
    decay_in = jnp.exp(G)                   # from the chunk's start, <= 1
    decay_out = jnp.exp(G_end - G)          # to the chunk's end, <= 1
    w = _exact(T, beta * (v - _mm(k * decay_in, St, dt, _NT)))
    return (_mm(q * decay_in, St, dt, _NT) + _mm(P, w, dt),
            St * jnp.exp(G_end) + _mm(w, k * decay_out, dt, _TN))


def _chunk(heads, dt):
    """One chunk of the heads of a grid step, from ops Mosaic lowers (and
    differentiates: the backward kernel takes ``jax.vjp`` of this). Per
    head: the state it starts from, TRANSPOSED (St [d_v, d_k] float32, so
    the chunk's decay scales lanes), and q, k, g [C, d_k], v [C, d_v],
    beta [C, 1] float32 -> (o [C, d_v] float32, the transposed state the
    next chunk starts from). The header's equations; what the chunks do
    not share first, head by head, then the triangular factors' inverses
    two heads to an MXU pass, then the states. (The parts are ``jax.jit``s
    so that a kernel's trace holds each once, not once a head: the
    lowering inlines them.)"""
    C = CHUNK
    parts = [_factors(q, k, g, beta, dt) for _, q, k, _, g, beta in heads]
    pack = _LANES // C              # factors side by side in one MXU pass
    if len(heads) % pack:
        pack = 1
    inverses = []
    for i in range(0, len(heads), pack):
        Tp = _invert(jnp.concatenate(
            [N for _, _, N in parts[i:i + pack]], axis=1))
        inverses += [Tp[:, h * C:(h + 1) * C] for h in range(pack)]
    return [_through_state(St, q, k, v, beta, G, P, T, dt)
            for (St, q, k, v, _, beta), (G, P, _), T
            in zip(heads, parts, inverses)]


def _lanes(h, width):
    """Head h's lanes of a block [CHUNK, heads * width]."""
    return (slice(None), slice(h * width, (h + 1) * width))


def _inputs(refs, starts, h):
    """Head h's arguments of ``_chunk`` from a grid step's blocks."""
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    dv, dk = starts.shape[-2:]
    return (starts[h], q_ref[_lanes(h, dk)], k_ref[_lanes(h, dk)],
            v_ref[_lanes(h, dv)].astype(jnp.float32), g_ref[_lanes(h, dk)],
            b_ref[h])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, states_ref,
                final_ref, state, *, dt, n_chunks):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    heads = range(state.shape[0])
    dv = state.shape[1]
    states_ref[...] = state[...]
    ends = _chunk([_inputs((q_ref, k_ref, v_ref, g_ref, b_ref), state, h)
                   for h in heads], dt)
    for h, (o, end) in zip(heads, ends):
        o_ref[_lanes(h, dv)] = o.astype(o_ref.dtype)
        state[h] = end

    @pl.when(c == n_chunks - 1)
    def _():
        final_ref[...] = state[...]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, states_ref, do_ref,
                dfinal_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate,
                *, dt):
    @pl.when(pl.program_id(2) == 0)        # the LAST chunk: reversed grid
    def _():
        dstate[...] = dfinal_ref[...]

    heads = range(dstate.shape[0])
    dv, dk = dstate.shape[1:]
    _, vjp = jax.vjp(
        lambda a: _chunk(a, dt),
        [_inputs((q_ref, k_ref, v_ref, g_ref, b_ref), states_ref, h)
         for h in heads])
    grads, = vjp([(do_ref[_lanes(h, dv)].astype(jnp.float32), dstate[h])
                  for h in heads])
    for h, (dS, dq, dk_, dv_, dg, db) in zip(heads, grads):
        dstate[h] = dS
        dq_ref[_lanes(h, dk)] = dq
        dk_ref[_lanes(h, dk)] = dk_
        dv_ref[_lanes(h, dv)] = dv_.astype(dv_ref.dtype)
        dg_ref[_lanes(h, dk)] = dg
        db_ref[h] = db


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(hb, dk, dv, chunk_of):
    """BlockSpecs of a grid (b, h, c), ``hb`` heads a step: a chunk of the
    heads' k-wide and v-wide rows, side by side in the lanes of
    [B, N * CHUNK, H * width] (the model's own layout: no transpose
    around the kernels), of their one-wide rows ([B, H, N * CHUNK, 1]),
    the states their chunk starts from ([B, H, N, d_v, d_k]) and their one
    state; ``chunk_of(c)`` is the chunk step c works on."""
    def rows(width):
        return pl.BlockSpec((None, CHUNK, hb * width),
                            lambda b, h, c: (b, chunk_of(c), h))
    return (rows(dk), rows(dv),
            pl.BlockSpec((None, hb, CHUNK, 1),
                         lambda b, h, c: (b, h, chunk_of(c), 0)),
            pl.BlockSpec((None, hb, None, dv, dk),
                         lambda b, h, c: (b, h, chunk_of(c), 0, 0)),
            pl.BlockSpec((None, hb, dv, dk), lambda b, h, c: (b, h, 0, 0)))


def _heads_per_step(H, dk, dv):
    """As many heads as fill ``HEAD_LANES`` (4 of 128: independent chains
    of small matmuls side by side fill each other's latency, and two
    triangular factors share an MXU pass; the v5e read 30.8 / 24.2 /
    21.8 ms a layer forward + backward at 1 / 2 / 4 heads and its VMEM
    refused 8: PERF.md section 6, PR 30)."""
    return math.gcd(H, max(HEAD_LANES // max(dk, dv), 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_kernels(q, k, v, g, beta, dt, interpret):
    return _kda_fwd(q, k, v, g, beta, dt, interpret)[0]


def _grid(q, v, beta, chunk_of):
    """(grid, the state scratch, ``_specs``) for these operands."""
    B, H, S, _ = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    hb = _heads_per_step(H, dk, dv)
    return ((B, H // hb, S // CHUNK), pltpu.VMEM((hb, dv, dk), jnp.float32),
            _specs(hb, dk, dv, chunk_of))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, g, beta, dt, interpret):
    """q, k, g [B, N * CHUNK, H * d_k] float32, v [.., H * d_v], beta
    [B, H, N * CHUNK, 1] float32 -> (o [B, N * CHUNK, H * d_v] in ``dt``,
    the state each chunk starts from and the final state, TRANSPOSED
    [.., d_v, d_k]). (A ``jax.jit``, like ``_backward``: the layers of a
    model share one trace and one lowering of each kernel; compiled or
    interpreted is an argument of both and of the ``custom_vjp``, not
    read inside, or a cached trace would outlive
    ``pallas_mode.compiling_for_tpu``.)"""
    grid, scratch, (wide, tall, one, per_chunk, per_head) = _grid(
        q, v, beta, lambda c: c)
    B, H, N = grid[0], beta.shape[1], grid[2]
    o, states, final = pl.pallas_call(
        functools.partial(_fwd_kernel, dt=dt, n_chunks=N),
        grid=grid,
        in_specs=[wide, wide, tall, wide, one],
        out_specs=[tall, per_chunk, per_head],
        out_shape=[jax.ShapeDtypeStruct(v.shape, dt),
                   jax.ShapeDtypeStruct((B, H, N) + scratch.shape[1:],
                                        jnp.float32),
                   jax.ShapeDtypeStruct((B, H) + scratch.shape[1:],
                                        jnp.float32)],
        scratch_shapes=[scratch],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="kda_fwd",
    )(q, k, v, g, beta)
    return o, states, final


def _kda_fwd(q, k, v, g, beta, dt, interpret):
    o, states, final = _forward(q, k, v, g, beta, dt, interpret)
    return (o, final), (q, k, v, g, beta, checkpoint_name(states, KEPT))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _backward(dt, interpret, res, cot):
    q, k, v, g, beta, states = res
    n_chunks = states.shape[2]
    grid, scratch, (wide, tall, one, per_chunk, per_head) = _grid(
        q, v, beta, lambda c: n_chunks - 1 - c)
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, dt=dt),
        grid=grid,
        in_specs=[wide, wide, tall, wide, one, per_chunk, tall, per_head],
        out_specs=[wide, wide, tall, wide, one],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (q, k, v, g, beta)],
        scratch_shapes=[scratch],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, g, beta, states, *cot))


_kda_kernels.defvjp(_kda_fwd, _backward)


def runs_as_kernels(dk: int, dv: int) -> bool:
    """Do heads of these widths run through the pallas kernels? Where both
    are whole 128-lane tiles (the published 128), on a TPU and, interpreted,
    on the CPU test backend; any other backend raises
    (``pallas_mode.interpret``). Narrower heads (the tiny test models')
    take the ``lax`` form."""
    if dk % _LANES or dv % _LANES:
        return False
    pallas_mode.interpret()
    return True


def kda_whole_chunks(q, k, v, g, beta, dtype):
    """The kernels on operands already in their layout: q, k, g float32
    [B, N * CHUNK, H * d_k] and v [.., H * d_v] (rows past the sequence's
    end neither decay nor write: zeros), beta [B, S, H] ->
    (o [B, N * CHUNK, H * d_v] in ``dtype``, the final state TRANSPOSED
    [B, H, d_v, d_k] float32)."""
    beta = jnp.pad(beta.astype(jnp.float32),
                   [(0, 0), (0, q.shape[1] - beta.shape[1]), (0, 0)])
    return _kda_kernels(q, k, v, g, jnp.swapaxes(beta, 1, 2)[..., None],
                        dtype, pallas_mode.interpret())


def _kda_pallas(q, k, v, g, beta, dtype=None):
    dt = dtype or q.dtype
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    pad = -S % CHUNK

    def chunks(t, to):
        """[B, S, H, width] -> [B, N * CHUNK, H * width]."""
        t = jnp.pad(t.astype(to), [(0, 0), (0, pad), (0, 0), (0, 0)])
        return t.reshape(B, S + pad, -1)

    f32 = jnp.float32
    o, final = kda_whole_chunks(
        chunks(q, f32), chunks(k, f32), chunks(v, v.dtype), chunks(g, f32),
        beta, dt)
    return (o[:, :S].reshape(B, S, H, dv), jnp.swapaxes(final, -1, -2))


# ------------------------------------- the mixer's passes around the core

ROW_TILE = 256   # rows of [B, S, H * d] one grid step of a pass works on
_HALO = 16       # rows a step reads of the tile before its own: one
#                  bfloat16 tile, of which the filter's K - 1 <= 8 count
_PASS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


def _head_sums(x, d):
    """[R, h * d] float32 -> the same shape, every lane holding the sum
    over its own head's d lanes: reduced and spread again inside the
    tile, so a row statistic never passes through HBM."""
    return jnp.concatenate([jnp.broadcast_to(jnp.sum(
        x[:, h:h + d], axis=1, keepdims=True), (x.shape[0], d))
        for h in range(0, x.shape[1], d)], axis=1)


def _sigmoid(x):
    """1 / (1 + exp(-x)) as one transcendental and no divide."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _live_rows(ref, tile, seq):
    """[rows, 1]: which rows of tile ``tile``, shaped like ``ref``'s block,
    lie inside the sequence."""
    rows = ref.shape[0]
    return (tile * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0)) < seq


def _taps(ext, K, rows):
    """The K shifted views of a tile behind 8 rows of the tile before it:
    tap j is the input of K - 1 - j rows ago."""
    return [ext[8 - (K - 1) + j:8 - (K - 1) + j + rows] for j in range(K)]


def _filtered(x_ref, halo_ref, w_ref, live, first):
    """The K-tap causal filter of one tile, float32: (y, sigmoid(y), the
    taps). Rows past the sequence's end read as zeros, and so do the rows
    before its start."""
    f32 = jnp.float32
    x = jnp.where(live, x_ref[...].astype(f32), 0.0)
    halo = jnp.where(first, 0.0, halo_ref[...].astype(f32)[_HALO - 8:])
    w = w_ref[...].astype(f32)
    taps = _taps(jnp.concatenate([halo, x]), w.shape[0], x.shape[0])
    y = sum(w[j:j + 1] * tap for j, tap in enumerate(taps))
    return y, _sigmoid(y), taps


def _l2(s, d):
    """(1 / sqrt(sum over the head of s^2 + 1e-6), s times it)."""
    r = jax.lax.rsqrt(_head_sums(s * s, d) + 1e-6)
    return r, s * r


def _pre_fwd_kernel(xq_ref, xk_ref, xv_ref, f_ref, hq_ref, hk_ref, hv_ref,
                    wq_ref, wk_ref, wv_ref, a_ref, bias_ref,
                    q_ref, k_ref, v_ref, g_ref, *, seq, d):
    live = _live_rows(q_ref, pl.program_id(2), seq)
    first = pl.program_id(2) == 0

    def conv_silu(x_ref, halo_ref, w_ref):
        y, gate, _ = _filtered(x_ref, halo_ref, w_ref, live, first)
        return jnp.where(live, y * gate, 0.0)

    q_ref[...] = _l2(conv_silu(xq_ref, hq_ref, wq_ref), d)[1] * d ** -0.5
    k_ref[...] = _l2(conv_silu(xk_ref, hk_ref, wk_ref), d)[1]
    v_ref[...] = conv_silu(xv_ref, hv_ref, wv_ref).astype(v_ref.dtype)
    g_ref[...] = jnp.where(live, a_ref[...] * jax.nn.softplus(
        f_ref[...].astype(jnp.float32) + bias_ref[...]), 0.0)


def _pre_bwd_kernel(dq_ref, dk_ref, dv_ref, dg_ref, xq_ref, xk_ref, xv_ref,
                    f_ref, hq_ref, hk_ref, hv_ref, wq_ref, wk_ref, wv_ref,
                    a_ref, bias_ref, dxq_ref, dxk_ref, dxv_ref, df_ref,
                    small_ref, carry, *, seq, d, tiles):
    """The tiles of a lane block LAST to first: what a tile's filter
    gradient owes the K - 1 rows before it waits in ``carry`` for the next
    step, and the gradients of what has no row axis (the filters, the
    decay's two vectors) add up in ``small_ref``'s resident block."""
    f32 = jnp.float32
    c = pl.program_id(2)
    rows = dq_ref.shape[0]
    live = _live_rows(dq_ref, tiles - 1 - c, seq)
    first = c == tiles - 1

    @pl.when(c == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)
        small_ref[...] = jnp.zeros_like(small_ref)

    def through_filter(i, x_ref, halo_ref, w_ref, dx_ref, ds_of):
        """dx of one filtered input from ``ds_of(s)``, the gradient of
        s = silu(y); returns the filter's gradient [K, lanes]."""
        y, gate, taps = _filtered(x_ref, halo_ref, w_ref, live, first)
        dy = jnp.where(live, ds_of(y * gate) * gate * (1.0 + y * (1.0 - gate)),
                       0.0)
        w = w_ref[...].astype(f32)
        K = w.shape[0]
        ahead = jnp.concatenate([dy, carry[i]])
        dx_ref[...] = sum(
            w[j:j + 1] * ahead[K - 1 - j:K - 1 - j + rows]
            for j in range(K)).astype(dx_ref.dtype)
        carry[i] = dy[:8]
        return [jnp.sum(dy * tap, axis=0, keepdims=True) for tap in taps]

    def l2_of(dout_ref, scale):
        def ds_of(s):
            r, n = _l2(s, d)
            dn = dout_ref[...] * scale
            return r * (dn - n * _head_sums(dn * n, d))
        return ds_of

    small = through_filter(0, xq_ref, hq_ref, wq_ref, dxq_ref,
                           l2_of(dq_ref, d ** -0.5))
    small += through_filter(1, xk_ref, hk_ref, wk_ref, dxk_ref,
                            l2_of(dk_ref, 1.0))
    small += through_filter(2, xv_ref, hv_ref, wv_ref, dxv_ref,
                            lambda s: dv_ref[...].astype(f32))
    z = jnp.where(live, f_ref[...].astype(f32) + bias_ref[...], 0.0)
    dg = jnp.where(live, dg_ref[...], 0.0)
    dz = dg * a_ref[...] * _sigmoid(z)
    df_ref[...] = dz.astype(df_ref.dtype)
    small += [jnp.sum(dz, axis=0, keepdims=True),
              jnp.sum(dg * jax.nn.softplus(z), axis=0, keepdims=True)]
    small.append(jnp.zeros((small_ref.shape[0] - len(small),
                            small_ref.shape[1]), f32))
    small_ref[...] += jnp.concatenate(small)


def _pass_specs(B, seq, HD, d, rows, tile_of=lambda t: t):
    """(grid, a tile, the 16 rows before it, a [n, lanes] block that has no
    row axis, a [B, n, HD] one that adds up over the tiles) of a pass over
    [B, seq, HD] in tiles of ``rows`` rows and as many whole heads as fill
    ``HEAD_LANES``; ``tile_of(t)`` is the tile step t works on."""
    lanes = _heads_per_step(HD // d, d, d) * d
    tile = pl.BlockSpec((None, rows, lanes),
                        lambda b, j, t: (b, tile_of(t), j))
    halo = pl.BlockSpec(
        (None, _HALO, lanes), lambda b, j, t: (
            b, jnp.maximum(tile_of(t) * (rows // _HALO) - 1, 0), j))
    return ((B, HD // lanes, pl.cdiv(seq, rows)), tile, halo,
            lambda n: pl.BlockSpec((n, lanes), lambda b, j, t: (0, j)),
            lambda n: pl.BlockSpec((None, n, lanes),
                                   lambda b, j, t: (b, 0, j)))


def _tile_rows(rows, padded):
    """Rows a step of a pass over ``padded`` (whole chunks) works on: whole
    chunks, so no tile lies wholly past the sequence's end."""
    return min(rows - rows % CHUNK or CHUNK, padded)


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12))
def _pre_forward(xq, xk, xv, f, wq, wk, wv, neg_a, bias, d, dt, rows,
                 interpret):
    B, S, HD = xq.shape
    padded = S + -S % CHUNK
    rows = _tile_rows(rows, padded)
    grid, tile, halo, flat, _ = _pass_specs(B, padded, HD, d, rows)
    wide = jax.ShapeDtypeStruct((B, padded, HD), jnp.float32)
    K = wq.shape[0]
    return tuple(pl.pallas_call(
        functools.partial(_pre_fwd_kernel, seq=S, d=d),
        grid=grid,
        in_specs=[tile] * 4 + [halo] * 3 + [flat(K)] * 3 + [flat(1)] * 2,
        out_specs=[tile] * 4,
        out_shape=[wide, wide, jax.ShapeDtypeStruct(wide.shape, dt), wide],
        compiler_params=_PASS,
        interpret=interpret,
        name="kda_pre_fwd",
    )(xq, xk, xv, f, xq, xk, xv, wq, wk, wv, neg_a[None], bias[None]))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _pre_backward(d, dt, rows, interpret, res, cot):
    xq, xk, xv, f, wq, wk, wv, neg_a, bias = res
    dq, dk, dv, dg = cot
    B, S, HD = xq.shape
    padded = dq.shape[1]
    rows = _tile_rows(rows, padded)
    tiles = pl.cdiv(padded, rows)
    grid, tile, halo, flat, summed = _pass_specs(
        B, padded, HD, d, rows, lambda t: tiles - 1 - t)
    K = wq.shape[0]
    n_small = -(-(3 * K + 2) // 8) * 8
    lanes = tile.block_shape[-1]
    dxq, dxk, dxv, df, small = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, seq=S, d=d, tiles=tiles),
        grid=grid,
        in_specs=[tile] * 8 + [halo] * 3 + [flat(K)] * 3 + [flat(1)] * 2,
        out_specs=[tile] * 4 + [summed(n_small)],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (xq, xk, xv, f)]
        + [jax.ShapeDtypeStruct((B, n_small, HD), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((3, 8, lanes), jnp.float32)],
        compiler_params=_PASS,
        interpret=interpret,
        name="kda_pre_bwd",
    )(dq, dk, dv, dg, xq, xk, xv, f, xq, xk, xv, wq, wk, wv, neg_a[None],
      bias[None])
    small = jnp.sum(small, axis=0)
    dw = [small[i * K:(i + 1) * K].astype(w.dtype)
          for i, w in enumerate((wq, wk, wv))]
    return (dxq, dxk, dxv, df, *dw, small[3 * K + 1].astype(neg_a.dtype),
            small[3 * K].astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12))
def _kda_pre(xq, xk, xv, f, wq, wk, wv, neg_a, bias, d, dt, rows, interpret):
    return _pre_forward(xq, xk, xv, f, wq, wk, wv, neg_a, bias, d, dt, rows,
                        interpret)


def _kda_pre_fwd(*args):
    return _pre_forward(*args), args[:9]


_kda_pre.defvjp(_kda_pre_fwd, _pre_backward)


def kda_pre(xq, xk, xv, f, wq, wk, wv, a_log, dt_bias, dtype):
    """Everything element-wise between the mixer's projections and the
    delta rule, ONE pass over HBM a direction in the kernels' own layout:
    from the outputs [B, S, H * d] of ``q_proj``, ``k_proj``, ``v_proj``
    and ``f_b_proj``, the three [K, H * d] filters, ``A_log`` [H] and
    ``dt_bias`` [H * d] to ``kda_whole_chunks``' q, k, g (float32) and v
    (``dtype``), [B, N * CHUNK, H * d] with zeros past the sequence's end:
    the K-tap causal filter (zeros before the start), SiLU, q and k
    L2-normalised over their head with 1e-6 and q times d^-0.5, g =
    -exp(A_log) softplus(f + dt_bias). Float32 arithmetic in the tile; the
    backward pass makes the intermediates again from the projections'
    outputs, its only residuals."""
    if wq.shape[0] > 9:
        raise ValueError("a filter of %d taps reaches past the 8 rows a "
                         "tile reads of the tile before it" % wq.shape[0])
    d = xq.shape[-1] // a_log.shape[0]
    neg_a = -jnp.repeat(jnp.exp(a_log.astype(jnp.float32)), d)
    return _kda_pre(xq, xk, xv, f, wq, wk, wv, neg_a,
                    dt_bias.astype(jnp.float32), d, dtype, ROW_TILE,
                    pallas_mode.interpret())


# ---- after the core: the per-head RMSNorm and its sigmoid gate


def _post_fwd_kernel(o_ref, gate_ref, w_ref, out_ref, *, d, eps):
    f32 = jnp.float32
    o = o_ref[...].astype(f32)
    r = jax.lax.rsqrt(_head_sums(o * o, d) * (1.0 / d) + eps)
    out_ref[...] = (o * r * w_ref[...] * _sigmoid(
        gate_ref[...].astype(f32))).astype(out_ref.dtype)


def _post_bwd_kernel(dout_ref, o_ref, gate_ref, w_ref, do_ref, dgate_ref,
                     dw_ref, *, seq, d, eps):
    f32 = jnp.float32
    live = _live_rows(do_ref, pl.program_id(2), seq)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    o = jnp.where(live, o_ref[...].astype(f32), 0.0)
    r = jax.lax.rsqrt(_head_sums(o * o, d) * (1.0 / d) + eps)
    n = o * r
    gate = _sigmoid(jnp.where(live, gate_ref[...].astype(f32), 0.0))
    dout = jnp.where(live, dout_ref[...].astype(f32), 0.0)
    w = w_ref[...]
    through = dout * n * gate          # what reaches the scale, lane by lane
    dgate_ref[...] = (through * w * (1.0 - gate)).astype(dgate_ref.dtype)
    dn = dout * w * gate
    do_ref[...] = (r * (dn - n * _head_sums(dn * n, d) * (1.0 / d))
                   ).astype(do_ref.dtype)
    dw_ref[...] += jnp.concatenate([
        jnp.sum(through, axis=0, keepdims=True),
        jnp.zeros((dw_ref.shape[0] - 1, dw_ref.shape[1]), f32)])


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _post_forward(o, gate, w, d, eps, seq, dt, rows, interpret):
    B, padded, HD = o.shape
    rows = _tile_rows(rows, padded)
    grid, tile, _, flat, _ = _pass_specs(B, seq, HD, d, rows)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, d=d, eps=eps),
        grid=grid,
        in_specs=[tile, tile, flat(1)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, seq, HD), dt),
        compiler_params=_PASS,
        interpret=interpret,
        name="kda_post_fwd",
    )(o, gate, w[None])


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _post_backward(d, eps, seq, dt, rows, interpret, res, dout):
    o, gate, w = res
    B, padded, HD = o.shape
    rows = _tile_rows(rows, padded)
    grid, tile, _, flat, summed = _pass_specs(B, padded, HD, d, rows)
    do, dgate, dw = pl.pallas_call(
        functools.partial(_post_bwd_kernel, seq=seq, d=d, eps=eps),
        grid=grid,
        in_specs=[tile, tile, tile, flat(1)],
        out_specs=[tile, tile, summed(8)],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                   jax.ShapeDtypeStruct((B, 8, HD), jnp.float32)],
        compiler_params=_PASS,
        interpret=interpret,
        name="kda_post_bwd",
    )(dout, o, gate, w[None])
    return do, dgate, jnp.sum(dw[:, 0], axis=0).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _kda_post(o, gate, w, d, eps, seq, dt, rows, interpret):
    return _post_forward(o, gate, w, d, eps, seq, dt, rows, interpret)


def _kda_post_fwd(*args):
    return _post_forward(*args), args[:3]


_kda_post.defvjp(_kda_post_fwd, _post_backward)


def kda_post(o, gate, o_norm, eps, dtype):
    """Everything element-wise between the delta rule and ``o_proj``, one
    pass a direction: the core's o [B, N * CHUNK, H * d] (``dtype``), the
    gate projection's output [B, seq, H * d] and ``o_norm`` [d] ->
    ``rms_normalize(o) * o_norm * sigmoid(gate)`` per head, [B, seq, H * d]
    in ``dtype``; the mean square stays in the tile."""
    d = o_norm.shape[0]
    w = jnp.tile(o_norm.astype(jnp.float32), o.shape[-1] // d)
    return _kda_post(o, gate, w, d, eps, gate.shape[1], dtype, ROW_TILE,
                     pallas_mode.interpret())


def kda_chunked(q, k, v, g, beta, dtype=None):
    """q, k [B, S, H, d_k] (normalised and scaled by the caller), v
    [B, S, H, d_v], g [B, S, H, d_k] float32 log-decay (<= 0), beta
    [B, S, H]: (o [B, S, H, d_v] in ``dtype``, final state
    [B, H, d_k, d_v] float32). Any S: the tail is padded with tokens that
    neither decay nor write. One algorithm, two renderings chosen by the
    head widths (:func:`runs_as_kernels`)."""
    form = _kda_pallas if runs_as_kernels(q.shape[-1], v.shape[-1]) \
        else _kda_lax
    return form(q, k, v, g, beta, dtype)
