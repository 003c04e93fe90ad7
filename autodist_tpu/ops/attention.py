"""Attention ops, including sequence-parallel (ring / Ulysses) variants.

Long-context scaling is first-class here (the reference is data-parallel
only — SURVEY §5 "long-context: absent"): these ops let attention run with
the *sequence* dimension sharded across the mesh's ``seq`` axis.

- ``ring_attention``: blockwise attention with online (flash-style) softmax
  accumulation; K/V blocks rotate around the ring via ``ppermute`` so each
  device only ever holds one remote block — memory O(seq/N), comms ride
  nearest-neighbor ICI links (Liu et al., Ring Attention, arXiv 2310.01889).
- ``ulysses_attention``: all-to-all reshard seq-sharded -> head-sharded,
  run ordinary attention per head group, all-to-all back (DeepSpeed Ulysses,
  arXiv 2309.14509). Cheaper than ring when heads >= mesh axis and the
  all-to-all fits ICI.

Both are numerically exact (not approximations) and verified against the
reference attention in ``tests/test_sequence_parallel.py``.

All functions expect to run INSIDE shard_map with the given axis bound;
tensors are local chunks shaped [batch, seq_chunk, heads, head_dim].
"""
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def reference_attention(q, k, v, mask=None):
    """Plain softmax attention. [B, S, H, D] -> [B, S, H, D].
    mask: broadcastable to [B, H, Sq, Sk], True = attend."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    weights = jax.nn.softmax(logits.astype(jnp.float32)).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def causal_band(seq_q: int, seq_k: int, window=None):
    """[seq_q, seq_k] bool, True = attend: key j at or before query i and,
    with a ``window``, among the ``window`` latest of those, the query's own
    position counted (``j <= i`` and ``i - j < window``)."""
    rows = jnp.arange(seq_q)[:, None]
    cols = jnp.arange(seq_k)[None, :]
    seen = rows >= cols
    return seen if window is None else seen & (rows - cols < window)


def _block_update(q, k_blk, v_blk, acc, m, l, blk_mask, scale):
    """One online-softmax accumulation step (the flash-attention recurrence)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
    if blk_mask is not None:
        logits = jnp.where(blk_mask, logits, -jnp.inf)
    m_blk = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # rows with no allowed keys yet keep m=-inf; guard the exp
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
    return acc_new, m_new, l_new


@partial(jax.named_call, name="ring_attention")
def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False):
    """Exact attention over a sequence sharded along ``axis_name``.

    q, k, v: local chunks [B, C, H, D] (C = global_seq / axis_size), chunk r
    holding global positions [r*C, (r+1)*C). Returns the local output chunk.
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_rank = jax.lax.axis_index(axis_name)
    B, C, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    q_pos = my_rank * C + jnp.arange(C)                      # global q positions

    acc0 = jnp.zeros((B, H, C, D), jnp.float32)
    m0 = jnp.full((B, H, C), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, C), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def block_step(t, acc, m, l, k_cur, v_cur):
        # after t forward rotations, we hold the block originally at rank - t
        src = (my_rank - t) % axis_size
        blk_mask = None
        if causal:
            k_pos = src * C + jnp.arange(C)
            blk_mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        return _block_update(q, k_cur, v_cur, acc, m, l, blk_mask, scale)

    def body(t, carry):
        acc, m, l, k_cur, v_cur = carry
        acc, m, l = block_step(t, acc, m, l, k_cur, v_cur)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt

    # N-1 rotations suffice: the last block updates WITHOUT the trailing
    # ppermute pair whose rotated result nothing reads (1/N of the op's
    # communication on an N-way ring)
    acc, m, l, k_last, v_last = jax.lax.fori_loop(
        0, axis_size - 1, body, (acc0, m0, l0, k, v))
    acc, m, l = block_step(axis_size - 1, acc, m, l, k_last, v_last)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


@partial(jax.named_call, name="ulysses_attention")
def ulysses_attention(q, k, v, axis_name: str = "seq", causal: bool = False,
                      mask: Optional[jax.Array] = None):
    """Ulysses sequence parallelism: all-to-all from seq-sharded to
    head-sharded, full-sequence attention on H/N heads, all-to-all back.

    Requires H % axis_size == 0. Local inputs [B, C, H, D] with C = S/N.
    """
    axis_size = jax.lax.psum(1, axis_name)
    B, C, H, D = q.shape
    if H % axis_size != 0:
        raise ValueError("ulysses needs heads %% axis_size == 0 (H=%d)" % H)

    def seq_to_heads(x):
        # [B, C, H, D] -> all_to_all over head dim -> [B, S, H/N, D]
        x = jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                               tiled=True)
        return x

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    S = qg.shape[1]
    attn_mask = mask
    if causal:
        cm = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
        attn_mask = cm if attn_mask is None else (attn_mask & cm)
    out = reference_attention(qg, kg, vg, attn_mask)
    return heads_to_seq(out)


def cached_attention(q, k_cache, v_cache, cursor):
    """Decode-shape attention against a KV cache (continuous batching).

    One query per slot against the slot's cached keys/values:
    ``q`` is [B, H, D] (the current token's projected query), ``k_cache``
    and ``v_cache`` are [B, T, H, D] slot caches, ``cursor`` is [B] int32
    — the row the current token was just written to. Rows ``<= cursor``
    are live; later rows hold garbage from evicted sequences and are
    masked out, which is what makes slot reuse safe without zeroing the
    cache. Numerics match :func:`reference_attention` on the live prefix
    (same fp32 softmax), so decode is exact-parity with full-sequence
    recompute (tests/test_decode.py)."""
    T = k_cache.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bhd,bthd->bht", q, k_cache) * scale
    mask = jnp.arange(T)[None, None, :] <= cursor[:, None, None]
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights = weights.astype(q.dtype)
    return jnp.einsum("bht,bthd->bhd", weights, v_cache)


def flash_cached_attention(q, k_cache, v_cache, cursor):
    """Decode-shape attention through the pallas flash kernel
    (``ops/flash_attention.py``) — the optional decode inner loop.

    The kernel tiles query blocks of at least 8 rows, so the single
    decode query is broadcast to an 8-row block and the cursor mask is
    expressed as segment ids (q row 0 gets segment 1; cache rows
    ``<= cursor`` get segment 1, dead rows 0): attention is allowed iff
    the segments match, which is exactly the live-prefix mask. Rows 1-7
    of the query block attend only dead rows and are discarded. Off-TPU
    the kernel runs under ``interpret=True``; when the cache length
    cannot be tiled the kernel itself falls back to the XLA reference
    path, so this is always safe to call.

    Parity with :func:`cached_attention` is allclose, not bitwise: the
    kernel accumulates blockwise in fp32 with a finite ``NEG_INF`` mask
    stand-in (tolerances documented in tests/test_decode.py)."""
    from autodist_tpu.ops.flash_attention import flash_attention
    B, T = k_cache.shape[0], k_cache.shape[1]
    q_blk = jnp.broadcast_to(q[:, None], (B, 8) + q.shape[1:])
    q_seg = jnp.zeros((B, 8), jnp.int32).at[:, 0].set(1)
    kv_seg = (jnp.arange(T)[None, :] <= cursor[:, None]).astype(jnp.int32)
    out = flash_attention(q_blk, k_cache, v_cache, causal=False,
                          segment_ids=(q_seg, kv_seg))
    return out[:, 0]


def make_attn_fn(kind: str = "ring", axis_name: str = "seq",
                 causal: bool = False):
    """Attention implementation injectable into model layers
    (``models/layers.py`` MultiHeadAttention.attn_fn)."""
    if kind == "ring":
        def ring_fn(q, k, v, mask=None):
            if mask is not None:
                # silently dropping the model's padding mask would let
                # every token attend PAD positions with no error
                raise ValueError(
                    "ring attention cannot apply a dense mask (the K/V "
                    "blocks rotate); use kind='ulysses' (full-sequence "
                    "attention per head group honors masks) or pack "
                    "sequences without padding")
            return ring_attention(q, k, v, axis_name, causal=causal)
        return ring_fn
    if kind == "ulysses":
        return lambda q, k, v, mask=None: ulysses_attention(
            q, k, v, axis_name, causal=causal, mask=mask)
    if kind == "flash":
        # single-device fused pallas kernel (no mesh axis involved)
        from autodist_tpu.ops.flash_attention import make_flash_attn_fn
        return make_flash_attn_fn(causal=causal)
    if kind == "reference":
        return lambda q, k, v, mask=None: reference_attention(q, k, v, mask)
    raise ValueError("unknown attention kind %r" % kind)
