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
chunk. What the chunks do not share (M, P, the solve) is computed for all
chunks at once; autodiff's residuals of the scan are one state per chunk
(67 MB for 8 heads of 128 x 128 and 128 chunks), so no ``custom_vjp`` is
needed.

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

CHUNK = 64   # tokens per chunk: one state update per chunk
SUB = 16     # tokens per sub-block of the decay differences
HEAD_GROUP = 8   # heads worked on together


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


def kda_chunked(q, k, v, g, beta, dtype=None):
    """q, k [B, S, H, d_k] (normalised and scaled by the caller), v
    [B, S, H, d_v], g [B, S, H, d_k] float32 log-decay (<= 0), beta
    [B, S, H]: (o [B, S, H, d_v] in ``dtype``, final state
    [B, H, d_k, d_v] float32). Any S: the tail is padded with tokens that
    neither decay nor write. Heads are worked ``HEAD_GROUP`` at a time
    (``lax.map``), each group recomputed in the backward pass, so the
    float32 transients of all heads never live together (3 GB a layer at
    32 heads of 128 and 8,192 tokens)."""
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
