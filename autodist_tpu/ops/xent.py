"""Memory-lean softmax cross-entropy for big-vocab LM heads.

The standard head materializes ``logits [N, V]`` in fp32 (lm1b: 64x256
tokens x 99k vocab = 6.5 GB) plus softmax residuals for the backward —
the tensor that decides the biggest batch a chip fits. Here neither the
forward nor the backward ever holds more than one ``[N, C]`` vocab chunk:

- forward: one ``lax.scan`` over vocab chunks maintains the online
  logsumexp (running max + normalizer, same trick as flash attention's
  softmax) and picks out each token's target logit as its chunk passes.
- backward (custom_vjp): recomputes each chunk's logits from the saved
  activations (linear — one matmul), forms ``softmax - onehot`` for that
  chunk only, accumulates dx and writes the chunk's dW/db slice in place.

The weight matrix is never copied or padded: each scan step reads its
chunk with ``lax.dynamic_slice`` directly from ``w`` (a ragged final
chunk re-reads the tail at a clamped offset with the overlap masked
dead). ``chunk`` is an upper limit, not the width: ``_layout`` takes the
fewest chunks the limit allows and makes each the vocabulary's even
share of them, rounded up to 128 lanes, because every matmul and every
elementwise pass runs on dead columns too (lm1b: 13 chunks of 7680, not
of 8192, for 99,183 words). Peak extra memory is the one ``[N, C]``
logits chunk — 503 MB for the 16,384 tokens a chip holds of lm1b's
step, vs the 6.5 GB full logits.

The target logit is picked by comparison, not by a gather: the row sum
of the chunk's logits where the column is the token's target (one
non-zero term, so exact) reduces the same operand over the same axis as
the normalizer's ``exp`` sum, and XLA emits one reduce fusion with two
outputs — the pick rides on a pass over the chunk the forward makes
anyway, where a ``take_along_axis`` was a scalar gather per chunk (20 ns
an element on a v5e). The backward finds the target the same way.

Exact same math as ``log_softmax`` + gather to float tolerance
(tests/test_xent.py), including out-of-vocab targets (clamped, like
``take_along_axis``).
"""
import functools

import jax
import jax.numpy as jnp

from autodist_tpu.telemetry import scopes
from autodist_tpu.telemetry import spans as tel

NEG_INF = -1e30


def _layout(v: int, chunk: int):
    """(chunk width, number of chunks). ``chunk`` is the UPPER limit (the
    memory property); the count is the fewest chunks that limit allows,
    and the width is the vocabulary's even share of them rounded up to
    the TPU's 128 lanes, so the clamped final chunk — read at offset
    ``v - width``, its overlap with the previous chunk masked dead, no
    padded weight copy — wastes under 128 columns a chunk instead of up
    to a whole one (lm1b: 13 x 7680 for 99,183 words, 657 dead columns
    where 13 x 8192 had 7,313)."""
    n = -(-v // chunk)
    per = -(-v // n)
    return min(chunk, v, -(-per // 128) * 128), n


def _chunk_view(w, b, ci, chunk, v):
    """This iteration's weight/bias slice read IN PLACE from w/b, plus
    the dead-column mask for the clamped final chunk.

    Returns (wc [D, C] fp32, bc [C] fp32, start, dead [C] bool) where
    ``dead`` marks columns already covered by the previous chunk."""
    off = ci * chunk
    start = jnp.minimum(off, v - chunk)
    wc = jax.lax.dynamic_slice_in_dim(w, start, chunk, axis=1)
    bc = jax.lax.dynamic_slice_in_dim(b, start, chunk, axis=0)
    cols = start + jnp.arange(chunk)
    dead = cols < off
    return (wc.astype(jnp.float32), bc.astype(jnp.float32), start, dead)


def _is_target(targets, start, dead):
    """[N, C] bool: the column is the row's (clamped, so >= 0) target and
    LIVE in this chunk. Every vocabulary column is live in exactly one
    chunk, so over the scan each row is hit exactly once."""
    cols = jnp.where(dead, -1, start + jnp.arange(dead.shape[0]))
    return targets[:, None] == cols[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunked_softmax_xent(x, w, b, targets, chunk=8192):
    """Per-token negative log-likelihood of ``targets`` under the linear
    head ``x @ w + b``, never materializing the full [N, V] logits.

    x: [N, D] activations; w: [D, V]; b: [V]; targets: [N] int32.
    Returns nll [N] float32. Differentiable in x, w, b.

    Precision: the chunk matmuls run at the backend's DEFAULT matmul
    precision — the same as the standard full-logits head, so the two
    heads are comparable — which on TPU means bf16 passes (~1e-2
    absolute nll deviation from a float32 softmax reference; exact to
    ~1e-6 on float32 backends). Wrap the call in
    ``jax.default_matmul_precision('highest')`` when bit-level parity
    with an fp32 reference matters more than head throughput.
    """
    nll, _ = _xent_fwd_impl(x, w, b, targets, chunk)
    return nll


@scopes.scoped(scopes.LEAN_HEAD)
def _xent_fwd_impl(x, w, b, targets, chunk):
    n, _d = x.shape
    v = w.shape[1]
    # clamp like take_along_axis in the standard path: an out-of-vocab
    # id must not silently yield nll = lse (tgt stuck at its 0.0 init)
    targets = jnp.clip(targets, 0, v - 1)
    chunk, nchunks = _layout(v, chunk)
    # the plan is static: what it decided, once per trace, host side
    tel.gauge_set("lean_head.chunks", nchunks)
    tel.gauge_set("lean_head.chunk_width", chunk)
    tel.gauge_set("lean_head.dead_cols", nchunks * chunk - v)
    xf = x.astype(jnp.float32)

    def body(carry, ci):
        m, l, tgt = carry
        wc, bc, start, dead = _chunk_view(w, b, ci, chunk, v)
        logits = jax.lax.dot(xf, wc) + bc[None, :]           # [N, C]
        logits = jnp.where(dead[None, :], NEG_INF, logits)
        m_cur = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m, m_cur)
        # the target's logit, if its column is LIVE in this chunk: a
        # masked row sum of the SAME `logits` over the SAME axis as the
        # exp-sum, so XLA makes the two one multi-output reduce
        hit = _is_target(targets, start, dead)
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1)
        tgt = tgt + jnp.sum(jnp.where(hit, logits, 0.0), axis=1)
        return (m_new, l, tgt), None

    m0 = jnp.full((n,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n,), jnp.float32)
    t0 = jnp.zeros((n,), jnp.float32)
    (m, l, tgt), _ = jax.lax.scan(body, (m0, l0, t0), jnp.arange(nchunks))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    nll = lse - tgt
    return nll, (x, w, b, targets, lse)


def _xent_fwd(x, w, b, targets, chunk):
    return _xent_fwd_impl(x, w, b, targets, chunk)


# a custom_vjp rule is traced at transpose time, outside the forward's
# scopes (JAX's own wrapper leaves it a bare transpose(jvp())): name it
@scopes.scoped(scopes.LEAN_HEAD_BWD)
def _xent_bwd(chunk, res, g):
    """g: cotangent [N]. d_nll/d_logit = softmax - onehot(target); each
    chunk's logits are recomputed from the saved activations, and each
    chunk's dW/db slice is WRITTEN in place, not read-add-written: every
    column is live in one chunk only, so there is nothing to add to (the
    read cost a slice and two layout copies of [D, C] a chunk on the
    TPU) as long as a column's live write is its last — hence the
    scan's order below."""
    x, w, b, targets, lse = res
    n, d = x.shape
    v = w.shape[1]
    targets = jnp.clip(targets, 0, v - 1)  # mirror the forward's clamp
    chunk, nchunks = _layout(v, chunk)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)

    def body(carry, ci):
        dx, dw, db = carry
        wc, bc, start, dead = _chunk_view(w, b, ci, chunk, v)
        logits = jax.lax.dot(xf, wc) + bc[None, :]
        logits = jnp.where(dead[None, :], NEG_INF, logits)
        p = jnp.exp(logits - lse[:, None])                  # softmax chunk
        onehot = _is_target(targets, start, dead)
        dlog = (p - onehot.astype(p.dtype)) * gf[:, None]   # [N, C]
        dx = dx + jax.lax.dot(dlog, wc.T)
        dwc = jax.lax.dot(xf.T, dlog).astype(dw.dtype)      # [D, C]
        dbc = jnp.sum(dlog, axis=0).astype(db.dtype)
        dw = jax.lax.dynamic_update_slice_in_dim(dw, dwc, start, axis=1)
        db = jax.lax.dynamic_update_slice_in_dim(db, dbc, start, axis=0)
        return (dx, dw, db), None

    dx0 = jnp.zeros((n, d), jnp.float32)
    dw0 = jnp.zeros((d, v), w.dtype)
    db0 = jnp.zeros((v,), b.dtype)
    # the clamped final chunk FIRST: what it writes into its dead overlap
    # (zeros) the chunk that holds those columns live then overwrites
    (dx, dw, db), _ = jax.lax.scan(body, (dx0, dw0, db0),
                                   jnp.roll(jnp.arange(nchunks), 1))
    return (dx.astype(x.dtype), dw, db, None)


chunked_softmax_xent.defvjp(_xent_fwd, _xent_bwd)
