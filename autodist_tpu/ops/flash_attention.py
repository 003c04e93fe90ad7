"""Pallas TPU flash attention: fused, tiled, memory-linear exact attention.

The reference has no kernel like this (its attention lives inside stock TF
ops); on TPU the fused softmax-attention kernel is the single hottest op in
every transformer benchmark (BERT / lm1b families, SURVEY §2.2), so it gets
a hand-written pallas kernel: O(seq) memory instead of the O(seq^2) logits
tensor XLA materializes, online-softmax accumulation in VMEM, matmuls on
the MXU in fp32 accumulation.

Design (standard FlashAttention-2 tiling, arXiv 2307.08691):
- forward: grid (batch, heads, q_blocks, kv_blocks) with the kv dimension
  innermost/"arbitrary"; running (m, l, acc) live in VMEM scratch across kv
  steps; the log-sum-exp per row is written out for the backward pass.
- backward: delta = rowsum(dO * O) precomputed in XLA (cheap elementwise),
  then two kernels — dQ over (q_blocks, kv_blocks) and dK/dV over
  (kv_blocks, q_blocks) — recompute P = exp(S - lse) tile by tile instead
  of storing it.
- causal: fully-masked tiles are skipped at trace time via ``pl.when``
  (upper-triangular tiles cost nothing), partial tiles are masked with
  broadcasted iotas.
- segment ids (BERT padding masks, packed sequences): attention is allowed
  iff ``q_seg[i] == kv_seg[j]``. Tiles whose q-segment range cannot
  intersect the kv-segment range are skipped dynamically (``pl.when`` on a
  range-overlap test — exact skips for the sorted/contiguous layouts BERT
  and sequence packing produce, safe over-approximation otherwise);
  partial tiles are masked elementwise. A query whose segment matches NO
  key anywhere (possible only with a distinct ``(q_seg, kv_seg)`` pair —
  self-attention position i always sees position i) outputs zeros with
  zero gradients, guarded in both passes; the XLA fallback's softmax
  instead yields a uniform average for such rows, so don't rely on
  empty-row values across paths.

On the CPU backend the same kernels run under ``interpret=True``
(``ops/pallas_mode.py`` decides, for every kernel of the tree) so unit
tests exercise the identical code path (tests/test_flash_attention.py
checks fwd+grad against ``ops.attention.reference_attention``).

Layout matches the rest of the model zoo: [batch, seq, heads, head_dim];
segment ids are [batch, seq] int32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_mode

NEG_INF = -1e30  # finite stand-in for -inf: keeps exp() NaN-free on masked rows
_LANES = 128     # last-dim tile width; m/l scratch are lane-replicated


def _pick_block(seq: int, want: int) -> int:
    """Largest power-of-two block <= want that divides seq (0 if none >= 8)."""
    b = min(want, seq)
    while b & (b - 1):
        b &= b - 1  # round down to a power of two (seq == b could be odd)
    while b >= 8 and seq % b:
        b //= 2
    return b if b >= 8 else 0


def _mask_val(s, qi, ki, bq, bk, causal, qs, ks):
    """Apply causal and/or segment masking to a score tile [bq, bk]."""
    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    if qs is not None:
        s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
    return s


def _tile_live(qi, ki, bq, bk, causal, qs, ks):
    """Skip condition: False only when the tile provably has no visible
    entry. Causal skips are static (upper-triangular tiles); segment skips
    compare the blocks' id ranges (exact for sorted segments, safe
    over-approximation otherwise)."""
    live = (qi * bq + bq - 1 >= ki * bk) if causal else True
    if qs is not None:
        overlap = ((jnp.max(qs) >= jnp.min(ks))
                   & (jnp.min(qs) <= jnp.max(ks)))
        live = jnp.logical_and(live, overlap)
    return live


# ---------------------------------------------------------------- forward

def _fwd_kernel(*refs, scale, causal, has_seg, bq, bk, n_kv):
    if has_seg:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qs_ref = ks_ref = None
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    qs = qs_ref[0, :, 0] if has_seg else None
    ks = ks_ref[0, :, 0] if has_seg else None
    live = _tile_live(qi, ki, bq, bk, causal, qs, ks)

    @pl.when(live)
    def _():
        q, k, v = q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :]
        # native-dtype (bf16) MXU operands, fp32 accumulation
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_val(s, qi, ki, bq, bk, causal, qs, ks)
        m_prev = m_ref[:, :1]                            # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # [bq, bk]
        # a row with NO visible key so far has m_new == NEG_INF and every
        # score masked: exp(NEG_INF - NEG_INF) = 1 would average garbage
        # values into the row — zero its contribution (empty rows emit 0)
        p = jnp.where(m_new > NEG_INF * 0.5, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv - 1)
    def _():
        l = l_ref[:, :1]
        o_ref[0, 0, :, :] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # empty rows (l == 0) record lse = 0, NOT NEG_INF + log(1e-30):
        # the backward pass computes p = exp(s - lse), and a huge-negative
        # lse would blow exp() up to garbage gradients for those rows;
        # with lse = 0, exp(NEG_INF - 0) = 0 and the row's grads vanish
        lse_ref[0, 0, :, :] = jnp.where(
            l > 0, m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-30)), 0.0)


def _seg_specs(bq, bk, q_major=True):
    """BlockSpecs for segment-id arrays, carried as [B, S, 1] so the block
    trailing dims (rows, 1) satisfy the TPU (8, 128)-divisibility rule
    (same trick as the lse row vectors)."""
    if q_major:
        qs = pl.BlockSpec((1, bq, 1), lambda b, h, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
        ks = pl.BlockSpec((1, bk, 1), lambda b, h, i, j: (b, j, 0),
                          memory_space=pltpu.VMEM)
    else:  # kv-major grid (dk/dv kernel): i indexes kv, j indexes q
        qs = pl.BlockSpec((1, bq, 1), lambda b, h, i, j: (b, j, 0),
                          memory_space=pltpu.VMEM)
        ks = pl.BlockSpec((1, bk, 1), lambda b, h, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    return qs, ks


def _fwd(q, k, v, segs, causal, block_q, block_k):
    """q, k, v in [B, H, S, D] (kernel-internal layout); segs is None or
    (q_seg [B, Sq], kv_seg [B, Sk]) int32."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = _pick_block(Sq, block_q), _pick_block(Sk, block_k)
    scale = float(1.0 / np.sqrt(D))
    n_q, n_kv = Sq // bq, Sk // bk
    grid = (B, H, n_q, n_kv)
    has_seg = segs is not None

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                           memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k, v]
    if has_seg:
        qs_spec, ks_spec = _seg_specs(bq, bk)
        in_specs += [qs_spec, ks_spec]
        operands += [segs[0].astype(jnp.int32)[..., None],
                     segs[1].astype(jnp.int32)[..., None]]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, bq=bq, bk=bk, n_kv=n_kv),
        grid=grid,
        in_specs=in_specs,
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=pallas_mode.interpret(),
    )(*operands)
    return out, lse


# ---------------------------------------------------------------- backward

def _dq_kernel(*refs, scale, causal, has_seg, bq, bk, n_kv):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
        qs_ref = ks_ref = None
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qs = qs_ref[0, :, 0] if has_seg else None
    ks = ks_ref[0, :, 0] if has_seg else None
    live = _tile_live(qi, ki, bq, bk, causal, qs, ks)

    @pl.when(live)
    def _():
        q, k, v = q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]                        # [bq, 1]
        delta = delta_ref[0, 0, :, :]                    # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_val(s, qi, ki, bq, bk, causal, qs, ks)
        p = jnp.exp(s - lse)                             # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[0, 0, :, :] = acc_ref[:].astype(dq_ref.dtype)


def _dkdv_kernel(*refs, scale, causal, has_seg, bq, bk, n_q):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qs = qs_ref[0, :, 0] if has_seg else None
    ks = ks_ref[0, :, 0] if has_seg else None
    live = _tile_live(qi, ki, bq, bk, causal, qs, ks)

    @pl.when(live)
    def _():
        q, k, v = q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]
        delta = delta_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_val(s, qi, ki, bq, bk, causal, qs, ks)
        p = jnp.exp(s - lse).astype(do.dtype)            # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (jnp.exp(s - lse) * (dp - delta) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, block_q, block_k, res, do):
    """res tensors in [B, H, S, D]; do arrives/leaves in [B, S, H, D]."""
    q, k, v, out, lse, q_seg, kv_seg = res
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = _pick_block(Sq, block_q), _pick_block(Sk, block_k)
    scale = float(1.0 / np.sqrt(D))
    n_q, n_kv = Sq // bq, Sk // bk
    has_seg = q_seg is not None
    do = do.transpose(0, 2, 1, 3)

    # delta_i = rowsum(dO_i * O_i): tiny elementwise reduce, XLA fuses it
    delta = jnp.einsum("bhsd,bhsd->bhs", do.astype(jnp.float32),
                       out.astype(jnp.float32))[..., None]

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    q_spec_i = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    kv_spec_j = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                             memory_space=pltpu.VMEM)
    row_spec_i = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
                              memory_space=pltpu.VMEM)

    in_specs = [q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, row_spec_i,
                row_spec_i]
    operands = [q, k, v, do, lse, delta]
    if has_seg:
        qs_spec, ks_spec = _seg_specs(bq, bk)
        in_specs += [qs_spec, ks_spec]
        operands += [q_seg[..., None], kv_seg[..., None]]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, bq=bq, bk=bk, n_kv=n_kv),
        grid=(B, H, n_q, n_kv),
        in_specs=in_specs,
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=params,
        interpret=pallas_mode.interpret(),
    )(*operands)

    # kv-major grid: q is the reduction (innermost) dim
    q_spec_j = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, j, 0),
                            memory_space=pltpu.VMEM)
    kv_spec_i = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, i, 0),
                             memory_space=pltpu.VMEM)
    row_spec_j = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, j, 0),
                              memory_space=pltpu.VMEM)

    in_specs = [q_spec_j, kv_spec_i, kv_spec_i, q_spec_j, row_spec_j,
                row_spec_j]
    operands = [q, k, v, do, lse, delta]
    if has_seg:
        qs_spec, ks_spec = _seg_specs(bq, bk, q_major=False)
        in_specs += [qs_spec, ks_spec]
        operands += [q_seg[..., None], kv_seg[..., None]]

    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, bq=bq, bk=bk, n_q=n_q),
        grid=(B, H, n_kv, n_q),
        in_specs=in_specs,
        out_specs=[kv_spec_i, kv_spec_i],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=params,
        interpret=pallas_mode.interpret(),
    )(*operands)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


# ---------------------------------------------------------------- public op

def _seg_zero_cot(seg):
    from autodist_tpu.kernel.common.variable_utils import zero_cotangent
    return zero_cotangent(seg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, q_seg, kv_seg, causal, block_q, block_k):
    segs = None if q_seg is None else (q_seg, kv_seg)
    out, _ = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                  v.transpose(0, 2, 1, 3), segs, causal, block_q, block_k)
    return out.transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, q_seg, kv_seg, causal, block_q, block_k):
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    segs = None if q_seg is None else (q_seg, kv_seg)
    out, lse = _fwd(qt, kt, vt, segs, causal, block_q, block_k)
    return out.transpose(0, 2, 1, 3), (qt, kt, vt, out, lse, q_seg, kv_seg)


def _flash_bwd(causal, block_q, block_k, res, do):
    dq, dk, dv = _bwd(causal, block_q, block_k, res, do)
    return dq, dk, dv, _seg_zero_cot(res[5]), _seg_zero_cot(res[6])


_flash.defvjp(_flash_fwd, _flash_bwd)


def _tileable(q, k, block_q, block_k):
    return bool(_pick_block(q.shape[1], block_q)) and \
        bool(_pick_block(k.shape[1], block_k))


def flash_attention(q, k, v, causal: bool = False, segment_ids=None,
                    block_q: int = 128, block_k: int = 128):
    """Exact fused attention. q,k,v: [B, S, H, D] -> [B, S, H, D].

    ``segment_ids``: [B, S] int32 (shared q/kv for self-attention) or a
    ``(q_seg, kv_seg)`` pair — attention is allowed iff the ids are equal.
    For a BERT-style key-padding mask, pass validity as segment ids (1 for
    real tokens, 0 for padding): valid tokens then attend exactly the
    valid tokens; padding rows attend padding (their outputs are excluded
    from any loss that masks padding, which BERT's MLM objective does).
    Composes with ``causal``.

    Falls back to the XLA reference path (differentiable as usual) when the
    sequence can't be tiled (remainder below the 8-row minimum block)."""
    if segment_ids is None:
        q_seg = kv_seg = None
    elif isinstance(segment_ids, (tuple, list)):
        q_seg = jnp.asarray(segment_ids[0], jnp.int32)
        kv_seg = jnp.asarray(segment_ids[1], jnp.int32)
    else:
        q_seg = kv_seg = jnp.asarray(segment_ids, jnp.int32)
    if not _tileable(q, k, block_q, block_k):
        from autodist_tpu.ops.attention import reference_attention
        from autodist_tpu.utils import logging
        logging.warning(
            "flash_attention: q %s / k %s cannot be tiled (blocks %d/%d, "
            "8-row minimum) — running the XLA reference path instead of "
            "the kernel", tuple(q.shape), tuple(k.shape), block_q, block_k)
        mask = None
        if causal:
            rows = jnp.arange(q.shape[1])[:, None]
            cols = jnp.arange(k.shape[1])[None, :]
            mask = (rows >= cols)[None, None]
        if q_seg is not None:
            seg_mask = (q_seg[:, :, None] == kv_seg[:, None, :])[:, None]
            mask = seg_mask if mask is None else jnp.logical_and(mask,
                                                                 seg_mask)
        return reference_attention(q, k, v, mask)
    return _flash(q, k, v, q_seg, kv_seg, causal, block_q, block_k)


def make_flash_attn_fn(causal: bool = True, block_q: int = 128,
                       block_k: int = 128):
    """(q, k, v, mask) -> out adapter for model layers' ``attn_fn`` slot.

    A key-padding mask (boolean, broadcastable [B, 1, 1, S] / [B, S])
    becomes segment ids (valid=1, pad=0) — the masked-tile block path.
    Arbitrary dense masks are not expressible as segments and raise."""
    def attn(q, k, v, mask=None):
        if mask is None:
            return flash_attention(q, k, v, causal, None, block_q, block_k)
        m = jnp.asarray(mask)
        # accept [B, S] or the layers' [B, 1, 1, S] broadcast form
        if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1:
            m = m[:, 0, 0, :]
        elif m.ndim != 2:
            raise ValueError(
                "flash attention supports key-padding masks ([B, S] or "
                "[B, 1, 1, S]) via segment ids; got mask shape %s"
                % (mask.shape,))
        seg = m.astype(jnp.int32)
        return flash_attention(q, k, v, causal, seg, block_q, block_k)
    return attn
