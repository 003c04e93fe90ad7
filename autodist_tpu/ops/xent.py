"""Memory-lean softmax cross-entropy for big-vocab LM heads.

The standard head materializes ``logits [N, V]`` in fp32 (lm1b: 32x256
tokens x 99k vocab = 3.25 GB) plus softmax residuals for the backward —
the tensor that decides the biggest batch a chip fits. Here neither the
forward nor the backward ever holds more than one ``[N, C]`` vocab chunk:

- forward: one ``lax.scan`` over vocab chunks maintains the online
  logsumexp (running max + normalizer, same trick as flash attention's
  softmax) and picks out each token's target logit as its chunk passes.
- backward (custom_vjp): recomputes each chunk's logits from the saved
  activations (linear — one matmul), forms ``softmax - onehot`` for that
  chunk only, and accumulates dx and in-place dW/db slices.

The weight matrix is never copied or padded: each scan step reads its
chunk with ``lax.dynamic_slice`` directly from ``w`` (a ragged final
chunk re-reads the tail at a clamped offset with the overlap masked
dead). Peak extra memory is the one ``[N, C]`` logits chunk — 268 MB at
the default C=8192 for lm1b's 8192 tokens, vs the 3.25 GB full logits.
Exact same math as ``log_softmax`` + gather to float tolerance
(tests/test_xent.py), including out-of-vocab targets (clamped, like
``take_along_axis``).
"""
import functools

import jax
import jax.numpy as jnp

from autodist_tpu.telemetry import scopes

NEG_INF = -1e30


def _layout(v: int, chunk: int):
    """(effective chunk, number of chunks). The final chunk of a ragged
    vocab is read at the clamped offset ``v - chunk`` and its overlap
    with the previous chunk is masked dead — no padded weight copy."""
    chunk = min(chunk, v)
    return chunk, (v + chunk - 1) // chunk


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
    xf = x.astype(jnp.float32)

    def body(carry, ci):
        m, l, tgt = carry
        wc, bc, start, dead = _chunk_view(w, b, ci, chunk, v)
        logits = jax.lax.dot(xf, wc) + bc[None, :]           # [N, C]
        logits = jnp.where(dead[None, :], NEG_INF, logits)
        m_cur = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m, m_cur)
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1)
        # target logit if the target falls inside this chunk's LIVE range
        local = targets - start
        inside = (targets >= ci * chunk) & (local < chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=1)[:, 0]
        tgt = jnp.where(inside, picked, tgt)
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
    chunk's logits are recomputed from the saved activations, and dW/db
    accumulate into their slices in place (read-add-write inside the
    scan — dead overlap columns contribute exactly zero)."""
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
        local = targets - start
        inside = (targets >= ci * chunk) & (local < chunk)
        onehot = (jnp.clip(local, 0, chunk - 1)[:, None]
                  == jnp.arange(chunk)[None, :]) & inside[:, None]
        dlog = (p - onehot.astype(p.dtype)) * gf[:, None]   # [N, C]
        dx = dx + jax.lax.dot(dlog, wc.T)
        dwc = jax.lax.dot(xf.T, dlog).astype(dw.dtype)      # [D, C]
        dbc = jnp.sum(dlog, axis=0).astype(db.dtype)
        dw = jax.lax.dynamic_update_slice_in_dim(
            dw, jax.lax.dynamic_slice_in_dim(dw, start, chunk, 1) + dwc,
            start, axis=1)
        db = jax.lax.dynamic_update_slice_in_dim(
            db, jax.lax.dynamic_slice_in_dim(db, start, chunk, 0) + dbc,
            start, axis=0)
        return (dx, dw, db), None

    dx0 = jnp.zeros((n, d), jnp.float32)
    dw0 = jnp.zeros((d, v), w.dtype)
    db0 = jnp.zeros((v,), b.dtype)
    (dx, dw, db), _ = jax.lax.scan(body, (dx0, dw0, db0),
                                   jnp.arange(nchunks))
    return (dx.astype(x.dtype), dw, db, None)


chunked_softmax_xent.defvjp(_xent_fwd, _xent_bwd)
