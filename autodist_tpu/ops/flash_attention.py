"""Pallas TPU flash attention: fused, tiled, memory-linear exact attention.

The reference has no kernel like this (its attention lives inside stock TF
ops). XLA's softmax attention writes the [B, H, S, S] scores to HBM and
reads them back several times in each direction; this kernel keeps a
[block_q, block_k] tile of them in VMEM: O(seq) memory, online-softmax
accumulation, matmuls on the MXU with fp32 accumulation.

Design (standard FlashAttention-2 tiling, arXiv 2307.08691):
- grid: every kernel takes ONE grid form, (batch, heads, T), whose last
  axis ("arbitrary") walks a TABLE of tiles made in numpy from the shapes
  as the call is traced and handed over through scalar prefetch
  (``_tile_table``: for each step its q block, its kv block, its bits and
  the q block whose dq leaves next). The block index maps read the table;
  so do the kernels' "first / last tile of this q block / kv block". A
  call that is not causal walks the whole rectangle; there is no second
  grid form.
- causal: the table lists the lower triangle only (136 of 256 tiles at
  seq 8192 x 512 rows), so no grid step is spent on a tile above the
  diagonal, and only the tiles the diagonal crosses (16 of the 136, bit
  ``CROSSED``) build the positional mask: under it every row passes every
  column, and that tile runs the same body without the two ``iota``s, the
  compare and the select. Counters ``attention.flash_tiles`` /
  ``attention.flash_tiles_masked`` say, per traced launch and head, how
  many steps the table has and how many carry the mask (PERF.md section
  6, PR 39). The sums are the rectangle's, tile for tile in the same
  order; on the CPU the values are the masked-everywhere kernel's to the
  last bit wherever segment ids or a selection mask the tile, and within
  a float32 rounding (1e-7) on a plain causal call's interior tiles, whose
  ``s * scale - m`` XLA:CPU contracts once no select stands between.
- window (``window=w``, with ``causal``): query i sees key j iff
  ``j <= i`` and ``i - j < w``: the w latest keys, THE QUERY'S OWN
  POSITION COUNTED (the Hugging Face sliding-window convention: w = 1 is
  a query that sees itself alone). The table then has two edges: a tile
  is listed iff the diagonal reaches it AND the window's far edge has not
  passed it (252 of the 528 causal tiles at seq 16,384 x 512 rows and
  w = 4,096: 43.75 % of the causal pairs in 47.7 % of the tiles), and
  ``CROSSED`` is on the tiles EITHER edge crosses, which alone pay the
  two compares. Nothing else changes: the walks, ``_Q_OUT`` (a q block's
  dq still leaves at its last tile, which is its diagonal tile, kv-major
  the first step of its own kv pass; its first tile is no longer kv block
  0, and ``Q_FIRST`` zeroes its slab where the table says) and the sums'
  order are the table's. A row of a tile the far edge crosses may see no
  key of that tile: its running maximum stays ``NEG_INF`` and what it
  summed is wiped by ``exp(NEG_INF - m)`` = 0 at the first tile that
  shows it a key, which its diagonal tile always does. Counters
  ``attention.window_tiles`` / ``attention.window_tiles_causal``: the
  steps a windowed launch's table has, and what the same launch would
  walk without the window.
- forward: q-major walk (a q block's kv blocks in a run); running
  (m, l, acc) live in VMEM scratch across the run; the log-sum-exp per row
  is written out for the backward pass.
- backward: delta = rowsum(dO * O) precomputed in XLA (cheap elementwise),
  then ONE kernel, ``flash_bwd``, over the kv-major walk (a kv block's q
  blocks in a run): each tile recomputes P = exp(S - lse) and
  dS = P * (dP - delta) once, instead of storing them, and adds its three
  products to dK / dV (float32 scratch of the kv block, as k and v stay put)
  and to dQ, whose float32 accumulator for the WHOLE head ([Sq, D]) stays
  in VMEM across the head's grid steps; a q block's rows are scaled, cast
  and written at its LAST tile of the walk (causal: the first step of kv
  pass j completes q block j; not causal: all leave in the last kv pass),
  its output block named from the table over the run of steps that ends
  there; ``vmem_limit_bytes``
  is counted from the shapes (``_fused_vmem``; 21.5 MB at seq 8192 x 192,
  Mosaic's default scope is 16 MiB of the v5e's 128). Where that count
  passes half of VMEM (seq 65,536 x 192: dq alone is 67 MB) the two
  kernels of before run instead, ``flash_dq`` over the q-major walk
  and ``flash_dkdv``, each recomputing the tile: same products, same
  order, same gradients to the last bit (``_bwd`` decides from the
  shapes alone; counters ``attention.flash_bwd_fused`` / ``_split`` say
  which form each traced backward pass took; PERF.md section 6, PR 34).
- tiles: ``_tiles`` chooses (block_q, block_k) from the shapes; no caller
  passes a tile size (PERF.md section 6, PR 28, has the chip's readings).
- layout: the model zoo's [batch, seq, heads, head_dim], transposed to
  [batch, heads, seq, head_dim] around the kernels; ``heads_first`` operands
  come in the kernels' layout already (``ops/attn_pre.py`` writes a layer's
  q, k and v so, in the one pass that norms and rotates them) and only the
  result and its cotangent are transposed.
- under a recomputing checkpoint: three of the backward kernels'
  residuals carry the name ``KEPT``: the forward kernel's results ``out``
  and ``lse``, and its operand ``q`` in the kernels' layout. A
  ``jax.checkpoint`` / ``nn.remat`` whose policy saves that name
  (``models/lm.py:TransformerLM._block``) keeps them, and its recomputed
  forward then holds no ``flash_fwd`` (nothing reads its results, so it
  is dead code) and nothing that only made q (its projection, rotation,
  transpose). At [1, 16, 8192, .] bfloat16 that is 33.5 MB of ``out``,
  0.5 MB of ``lse`` and 50 MB of ``q`` a layer against a second forward
  kernel of 3.55 ms and 1.5 ms of q's making. ``k`` and ``v`` are NOT
  kept: with them DeepSeek-V2-Lite's step asked for 3.75 GB of scratch
  where q alone asks for 3.15 (PERF.md section 6, PR 32, has every
  reading). With no policy, or one that does not list the name, the name
  is an identity that lowers to nothing and the block recomputes as
  before.
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

Segment ids are [batch, seq] int32.
"""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_mode
from autodist_tpu.telemetry import spans as tel

NEG_INF = -1e30  # finite stand-in for -inf: keeps exp() NaN-free on masked rows
_LANES = 128     # last-dim tile width; m/l scratch are lane-replicated
_ROWS = 512      # rows of a q or kv tile
_VMEM = 128 << 20  # a v5e core's vector memory (Mosaic scopes 16 MiB of it
#                    to a kernel that asks for no more)
# what a recomputed block keeps of the core: the forward kernel's output and
# log-sum-exp, the two residuals of the backward kernels that only the
# forward kernel can give, and q as the kernels read it
# (``ops/kda.py:KEPT`` is the delta rule's)
KEPT = "flash_core_kept"


def _pick_block(seq: int, want: int) -> int:
    """Largest power-of-two block <= want that divides seq (0 if none >= 8)."""
    b = min(want, seq)
    while b & (b - 1):
        b &= b - 1  # round down to a power of two (seq == b could be odd)
    while b >= 8 and seq % b:
        b //= 2
    return b if b >= 8 else 0


def _tiles(sq: int, sk: int):
    """(block_q, block_k) for ``sq`` queries against ``sk`` keys: as many
    rows as divide the sequence, up to ``_ROWS``, whatever the head width,
    the dtype and the segment ids. On the v5e larger tiles won up to 512
    (PERF.md section 6, PR 28; [4, 2048, 16, 128] bfloat16 causal,
    forward + backward alone: 20.5 ms at 128 rows, 9.0 at 256, 4.3 at
    512). 1,024 rows read 4.2 alone and nothing in the step (123.81
    against 123.79 ms), and whether the chip's VMEM held them moved with
    dtype, segment ids, batch and heads; at 512 every case of
    ``tests/test_olmoe.py::test_the_kernels_tiles_fit_the_v5es_vmem``
    compiles for the v5e."""
    return _pick_block(sq, _ROWS), _pick_block(sk, _ROWS)


def full_tiles(seq: int) -> bool:
    """Does ``seq`` split into whole ``_ROWS``-row tiles, the size the
    kernel was read at in a training step? (1000 gives tiles of 8.)"""
    return seq % _ROWS == 0


# ------------------------------------------------------------------ tiles

def _tile_live(qi, ki, bq, bk, causal, window=None):
    """Does tile (q block ``qi``, kv block ``ki``) hold a visible entry by
    position? Under a causal mask: does its last row reach its first
    column; under a window besides: is its first row's distance to its last
    column still inside it. Known from the shapes alone (ints or numpy
    arrays)."""
    if not causal:
        return True
    live = qi * bq + bq - 1 >= ki * bk
    if window is not None:
        live = live & (qi * bq - (ki * bk + bk - 1) < window)
    return live


def _tile_crossed(qi, ki, bq, bk, window=None):
    """Does an edge of the visible band pass THROUGH the live tile, so that
    it holds a masked entry: the diagonal (its last column lies past its
    first row) or the window's far edge (its last row's distance to its
    first column is outside the window)."""
    crossed = ki * bk + bk - 1 > qi * bq
    if window is not None:
        crossed = crossed | (qi * bq + bq - 1 - ki * bk >= window)
    return crossed


# the table's columns ...
_Q, _KV, _FLAGS, _Q_OUT = range(4)
# ... and the bits of its flags: the first / last tile of the walk that
# holds this q block, the same of this kv block, and a tile the diagonal
# or a window's far edge crosses (the only ones the positional compares
# change)
Q_FIRST, Q_LAST, KV_FIRST, KV_LAST, CROSSED = 1, 2, 4, 8, 16


def _tile_table(n_q, n_kv, bq, bk, causal, kv_major, window=None):
    """The tiles a kernel walks, int32 [4, T], made from the shapes as the
    call is traced: column ``_Q`` / ``_KV`` of step t is the tile's q / kv
    block, ``_FLAGS`` its bits, ``_Q_OUT`` the q block whose rows leave
    next (below). Every tile ``_tile_live`` finds a visible entry in, once:
    the whole rectangle, the causal lower triangle, or of it the band a
    ``window`` leaves; q-major (a q block's
    kv blocks in a run, rising: the forward kernel's and ``flash_dq``'s
    order of summing) or kv-major (``flash_bwd`` / ``flash_dkdv``).

    ``_Q_OUT``: a q block's float32 dq rows are complete at its LAST tile
    of the walk, so the output block of step t names the q block whose
    last tile comes soonest at or after t: each q block is named over one
    run of steps that ends where it is written. q-major that is the tile's
    own q block; kv-major and causal, q block j is done at the first step
    of kv pass j, with a window or without; with no mask all are done in
    the last kv pass. (Every q block has a tile, kv block 0 or under a
    window its own diagonal's, so none is left out; a kv block past the
    last query's position has no tile: ``_bwd`` zeroes its dk / dv.)"""
    grid = np.indices((n_kv, n_q) if kv_major else (n_q, n_kv))
    q, kv = (x.ravel() for x in (grid[::-1] if kv_major else grid))
    if causal:
        live = _tile_live(q, kv, bq, bk, causal, window)
        q, kv = q[live], kv[live]
    steps = np.arange(q.size)

    def ends(block, n):
        first, last = np.full(n, q.size), np.full(n, -1)
        np.minimum.at(first, block, steps)
        np.maximum.at(last, block, steps)
        return first, last

    q_first, q_last = ends(q, n_q)
    kv_first, kv_last = ends(kv, n_kv)
    flags = (Q_FIRST * (q_first[q] == steps) + Q_LAST * (q_last[q] == steps)
             + KV_FIRST * (kv_first[kv] == steps)
             + KV_LAST * (kv_last[kv] == steps))
    if causal:
        flags = flags + CROSSED * _tile_crossed(q, kv, bq, bk, window)
    leaving = np.argsort(q_last)
    q_out = leaving[np.searchsorted(q_last[leaving], steps)]
    return np.stack([q, kv, flags, q_out]).astype(np.int32)


def _table(Sq, Sk, bq, bk, causal, kv_major, window=None):
    """(``_tile_table`` flat, as the kernels' scalar-prefetch operand; its
    steps), counted as the launch is traced."""
    n_q, n_kv = Sq // bq, Sk // bk
    table = _tile_table(n_q, n_kv, bq, bk, causal, kv_major, window)
    tel.counter_add("attention.flash_tiles", table.shape[1])
    tel.counter_add("attention.flash_tiles_masked",
                    int(np.count_nonzero(table[_FLAGS] & CROSSED)))
    if window is not None:
        q, kv = np.indices((n_q, n_kv))
        tel.counter_add("attention.window_tiles", table.shape[1])
        tel.counter_add("attention.window_tiles_causal", int(
            np.count_nonzero(_tile_live(q, kv, bq, bk, causal))))
    return jnp.asarray(table.reshape(-1)), table.shape[1]


def _at(tab, col, steps, t):
    """Column ``col`` of the flat table at step t."""
    return tab[col * steps + t]


def _step(tab_ref, steps):
    """(q block, kv block, flags) of this grid step."""
    t = pl.program_id(2)
    return tuple(_at(tab_ref, col, steps, t) for col in (_Q, _KV, _FLAGS))


_Specs = collections.namedtuple(
    "_Specs", "q k v out row q_seg kv_seg sel dq")


def _specs(D, Dv, bq, bk, steps, group=1):
    """BlockSpecs of one grid (b, h, t) over [B, H, S, .] operands, t a
    step of the tile table (the scalar-prefetch operand, handed to every
    index map): a [rows, D] tile of q and of k, a [rows, Dv] tile of v and
    of the output (the values may be narrower or wider than the scores'
    features: latent attention scores over 192 and carries 128), a
    [rows, 1] tile of a per-row q-side vector ([B, H, S, 1]), the
    [rows, 1] q / kv segment ids ([B, S, 1]: the trailing 1 satisfies the
    TPU's (8, 128) rule as for the row vectors), the [bq, bk] tile of a
    selection ([B, Sq, Sk], every head's alike), and the [bq, D] tile of
    dq where its rows leave (``_Q_OUT``). Query head h reads K/V head
    ``h // group`` of [B, H / group, S, .] (nothing is repeated in HBM).
    The kernels see the tiles without leading dims."""
    def tile(rows, col, width, head=lambda h: h):
        return pl.BlockSpec(
            (None, None, rows, width),
            lambda b, h, t, tab: (b, head(h), _at(tab, col, steps, t), 0))

    def seg(rows, col):
        return pl.BlockSpec(
            (None, rows, 1),
            lambda b, h, t, tab: (b, _at(tab, col, steps, t), 0))

    kv_head = (lambda h: h) if group == 1 else (lambda h: h // group)
    sel = pl.BlockSpec(
        (None, bq, bk),
        lambda b, h, t, tab: (b, _at(tab, _Q, steps, t),
                              _at(tab, _KV, steps, t)))
    return _Specs(tile(bq, _Q, D), tile(bk, _KV, D, kv_head),
                  tile(bk, _KV, Dv, kv_head), tile(bq, _Q, Dv),
                  tile(bq, _Q, 1), seg(bq, _Q), seg(bk, _KV), sel,
                  tile(bq, _Q_OUT, D))


def _launch(kernel, name, table, grid, operands, in_specs, outs, scratch,
            **params):
    """One kernel over the tile table ``table`` (its scalar-prefetch
    operand), grid (batch, heads, steps); ``outs``: (block, result like) of
    each result."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[spec for spec, _ in outs],
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in scratch]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for _, x in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **params),
        interpret=pallas_mode.interpret(),
        name=name,
    )(table, *operands)


def _mask_val(s, qi, ki, bq, bk, causal, qs, ks, window=None):
    """Apply causal (and a window's) and/or segment masking to a score
    tile [bq, bk]."""
    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = rows >= cols
        if window is not None:
            seen = seen & (rows - cols < window)
        s = jnp.where(seen, s, NEG_INF)
    if qs is not None:
        s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
    return s


def _on_live_tile(qi, ki, flags, bq, bk, causal, qs_ref, ks_ref, sel_ref,
                  body, window=None):
    """Run ``body(mask)`` on a tile of the table unless its segment ids
    say no entry is visible; ``mask(s)`` masks a score tile [bq, bk]. The
    table lists no tile above the diagonal or behind a window, and only
    one an edge crosses (``CROSSED``) pays the positional compares: in the
    others every row passes every column. Segment ids skip a tile whose
    blocks' id ranges
    do not meet (exact for sorted segments, a safe over-approximation
    otherwise) and mask the others elementwise. A selection masks
    elementwise within the tile (its [bq, bk] int8 block, non-zero =
    attend) and skips none: whether a tile holds a chosen pair is not
    known without reading it."""
    qs = None if qs_ref is None else qs_ref[:, 0]
    ks = None if ks_ref is None else ks_ref[:, 0]

    def run(diagonal):
        def mask(s):
            s = _mask_val(s, qi, ki, bq, bk, diagonal, qs, ks, window)
            if sel_ref is None:
                return s
            return jnp.where(sel_ref[...].astype(jnp.int32) != 0, s, NEG_INF)
        return lambda: body(mask)

    if qs is None:
        when = pl.when
    else:
        meet = (jnp.max(qs) >= jnp.min(ks)) & (jnp.min(qs) <= jnp.max(ks))

        def when(cond):
            return pl.when(jnp.logical_and(meet, cond))
    if not causal:
        return run(False)() if qs is None else pl.when(meet)(run(False))
    crossed = (flags & CROSSED) != 0
    when(crossed)(run(True))
    when(jnp.logical_not(crossed))(run(False))


def _scores(q, k, scale, mask):
    # native-dtype (bf16) MXU operands, fp32 accumulation
    return mask(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale)


# ---------------------------------------------------------------- forward

def _split_refs(refs, n_fixed, has_seg, has_sel):
    """(the fixed operands, q / kv segment ids or None, the selection or
    None, results and scratch) of a kernel's refs."""
    n_in = n_fixed + 2 * has_seg + has_sel
    qs_ref, ks_ref = refs[n_fixed:n_fixed + 2] if has_seg else (None, None)
    sel_ref = refs[n_in - 1] if has_sel else None
    return refs[:n_fixed], qs_ref, ks_ref, sel_ref, refs[n_in:]


def _fwd_kernel(tab_ref, *refs, scale, causal, has_seg, has_sel, bq, bk,
                steps, window):
    (q_ref, k_ref, v_ref), qs_ref, ks_ref, sel_ref, (
        o_ref, lse_ref, acc_ref, m_ref, l_ref) = _split_refs(
            refs, 3, has_seg, has_sel)
    qi, ki, flags = _step(tab_ref, steps)

    @pl.when((flags & Q_FIRST) != 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def lanes(x, n):
        """[rows, 128] lane-replicated -> [rows, n]."""
        if n % _LANES == 0:
            return jnp.tile(x, (1, n // _LANES))
        return x[:, :n] if n < _LANES else jnp.broadcast_to(
            x[:, :1], (x.shape[0], n))

    def tile(mask):
        # m, l and what is derived from them stay [bq, 128], every lane the
        # row's value: a [bq, 1] column costs a vector op as much and a
        # lane broadcast on top (forward 1.97 -> 1.22 ms at 512 x 512
        # tiles, PERF.md section 6, PR 28)
        v = v_ref[...]
        s = _scores(q_ref[...], k_ref[...], scale, mask)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - lanes(m_new, bk))                # [bq, bk]
        if has_seg or has_sel:
            # a row with NO visible key so far has m_new == NEG_INF and
            # every score masked: exp(NEG_INF - NEG_INF) = 1 would average
            # garbage values into the row — zero its contribution (empty
            # rows emit 0). Causal rows all see column 0 in their first
            # tile, so only segments or a selection can leave a row empty
            p = jnp.where(lanes(m_new, bk) > NEG_INF * 0.5, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)[:, None]
        m_ref[...] = m_new
        acc_ref[:] = acc_ref[:] * lanes(corr, acc_ref.shape[1]) \
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _on_live_tile(qi, ki, flags, bq, bk, causal, qs_ref, ks_ref, sel_ref,
                  tile, window)

    @pl.when((flags & Q_LAST) != 0)
    def _():
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # empty rows (l == 0) record lse = 0, NOT NEG_INF + log(1e-30):
        # the backward pass computes p = exp(s - lse), and a huge-negative
        # lse would blow exp() up to garbage gradients for those rows;
        # with lse = 0, exp(NEG_INF - 0) = 0 and the row's grads vanish
        lse_ref[...] = jnp.where(
            l > 0, m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-30)), 0.0)


def _fwd(q, k, v, segs, sel, causal, window=None):
    """q [B, H, S, D], k [B, H / group, S, D], v [B, H / group, S, Dv]
    (kernel-internal layout); segs is None or (q_seg [B, Sq], kv_seg
    [B, Sk]) int32; sel None or [B, Sq, Sk] int8; window None or the keys
    a query sees, itself counted. Returns (out
    [B, H, Sq, Dv], lse [B, H, Sq, 1])."""
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    has_seg, has_sel = segs is not None, sel is not None
    bq, bk = _tiles(Sq, Sk)
    table, steps = _table(Sq, Sk, bq, bk, causal, False, window)

    specs = _specs(D, Dv, bq, bk, steps, H // k.shape[1])
    in_specs = [specs.q, specs.k, specs.v]
    operands = [q, k, v]
    if has_seg:
        in_specs += [specs.q_seg, specs.kv_seg]
        operands += [segs[0][..., None], segs[1][..., None]]
    if has_sel:
        in_specs.append(specs.sel)
        operands.append(sel)

    return _launch(
        functools.partial(_fwd_kernel, scale=float(1.0 / np.sqrt(D)),
                          causal=causal, has_seg=has_seg, has_sel=has_sel,
                          bq=bq, bk=bk, steps=steps, window=window),
        "flash_fwd", table, (B, H, steps), operands, in_specs,
        [(specs.out, jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype)),
         (specs.row, jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32))],
        [(bq, Dv), (bq, _LANES), (bq, _LANES)])


# ---------------------------------------------------------------- backward

def _p_and_ds(q, k, v, do, lse, delta, scale, mask):
    """(P, dS / scale) of one tile, both [bq, bk] float32: the softmax
    weights recomputed from the saved log-sum-exp and the scores' gradient
    before the scale, which the callers apply once to their [rows, D]
    accumulators instead of to every tile."""
    p = jnp.exp(_scores(q, k, scale, mask) - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _dq_kernel(tab_ref, *refs, scale, causal, has_seg, has_sel, bq, bk,
               steps, window):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qs_ref, ks_ref, \
        sel_ref, (dq_ref, acc_ref) = _split_refs(refs, 6, has_seg, has_sel)
    qi, ki, flags = _step(tab_ref, steps)

    @pl.when((flags & Q_FIRST) != 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile(mask):
        k = k_ref[...]
        _, ds = _p_and_ds(q_ref[...], k, v_ref[...], do_ref[...],
                          lse_ref[...], delta_ref[...], scale, mask)
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_live_tile(qi, ki, flags, bq, bk, causal, qs_ref, ks_ref, sel_ref,
                  tile, window)

    @pl.when((flags & Q_LAST) != 0)
    def _():
        dq_ref[...] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_kernel(tab_ref, *refs, scale, causal, has_seg, has_sel, bq, bk,
                steps, window, fused):
    """dk and dv of one kv block, summed over its q blocks in float32
    scratch. ``fused``: dq too, from the SAME ``_p_and_ds`` of each tile:
    it sums over the kv blocks in ``dq_acc``, one float32 [bq, D] slab a q
    block, which stays in VMEM for the whole head; a q block's rows leave
    at its last tile of the walk (``_tile_table``)."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qs_ref, ks_ref, \
        sel_ref, outs = _split_refs(refs, 6, has_seg, has_sel)
    if fused:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = outs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = outs
    qi, ki, flags = _step(tab_ref, steps)

    @pl.when((flags & KV_FIRST) != 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if fused:
        @pl.when((flags & Q_FIRST) != 0)
        def _():
            dq_acc[qi] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    def tile(mask):
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        p, ds = _p_and_ds(q, k, v_ref[...], do, lse_ref[...],
                          delta_ref[...], scale, mask)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = ds.astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if fused:
            dq_acc[qi] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _on_live_tile(qi, ki, flags, bq, bk, causal, qs_ref, ks_ref, sel_ref,
                  tile, window)

    @pl.when((flags & KV_LAST) != 0)
    def _():
        dk_ref[...] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)

    if fused:
        @pl.when((flags & Q_LAST) != 0)
        def _():
            dq_ref[...] = (dq_acc[qi] * scale).astype(dq_ref.dtype)


def _vmem_bytes(rows, cols, dtype):
    """Bytes of a [rows, cols] buffer in VMEM: whole (sublane, 128-lane)
    tiles, 8 sublanes of 32 bits."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    return -(-rows // sub) * sub * -(-cols // _LANES) * _LANES * item


def _fused_vmem(Sq, D, Dv, bq, bk, dtype, has_seg, has_sel=False):
    """Bytes of VMEM the fused backward kernel asks for, from the shapes:
    the head's float32 dq accumulator, the two dk / dv accumulators, every
    block the pipeline holds twice (operands, row vectors, segment ids, a
    selection's tile, the three results) and a tile's float32 [bq, bk]
    temporaries (scores, P, dP, dS, the two operands cast for the MXU and
    their transposes; a selection's tile widened to 32 bits)."""
    f32 = jnp.float32
    rows = 2 * _vmem_bytes(bq, 1, f32)               # lse, delta
    if has_seg:
        rows += _vmem_bytes(bq, 1, jnp.int32) + _vmem_bytes(bk, 1, jnp.int32)
    if has_sel:
        rows += _vmem_bytes(bq, bk, jnp.int8)
    blocks = (2 * _vmem_bytes(bq, D, dtype) + _vmem_bytes(bq, Dv, dtype)
              + 2 * _vmem_bytes(bk, D, dtype) + 2 * _vmem_bytes(bk, Dv, dtype)
              + rows)
    return (Sq // bq * _vmem_bytes(bq, D, f32)
            + _vmem_bytes(bk, D, f32) + _vmem_bytes(bk, Dv, f32)
            + 2 * blocks + (9 if has_sel else 8) * _vmem_bytes(bq, bk, f32))


def _bwd(causal, res, do, window=None):
    """res tensors, do and the returned (dq, dk, dv) in [B, H, S, .] (dk
    and dv [B, H / group, S, .] as k and v are)."""
    q, k, v, out, lse, q_seg, kv_seg, sel = res
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    has_seg, has_sel = q_seg is not None, sel is not None
    group = H // k.shape[1]
    bq, bk = _tiles(Sq, Sk)
    static = dict(scale=float(1.0 / np.sqrt(D)), causal=causal,
                  has_seg=has_seg, has_sel=has_sel, bq=bq, bk=bk,
                  window=window)

    # delta_i = rowsum(dO_i * O_i): tiny elementwise reduce, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)              # [B, H, Sq, 1]

    operands = [q, k, v, do, lse, delta]
    if has_seg:
        operands += [q_seg[..., None], kv_seg[..., None]]
    if has_sel:
        operands.append(sel)

    def call(kernel, name, table, steps, specs, outs, **params):
        """``outs``: (block, result like, float32 accumulator) of each
        result."""
        in_specs = [specs.q, specs.k, specs.v, specs.out, specs.row,
                    specs.row]
        if has_seg:
            in_specs += [specs.q_seg, specs.kv_seg]
        if has_sel:
            in_specs.append(specs.sel)
        return _launch(functools.partial(kernel, steps=steps, **static),
                       name, table, (B, H, steps), operands, in_specs,
                       [out[:2] for out in outs], [out[2] for out in outs],
                       **params)

    # kv-major walk: a kv block's q blocks in a run, dk and dv summed over
    # them. One kernel or two? One, whenever what ``_fused_vmem`` counts
    # fits half the core's vector memory; a longer head's dq accumulator
    # (seq 131,072 x 192 float32 is 134 MB) stays on the two kernels, whose
    # dq lives a q block at a time. Counted by form as the backward pass is
    # traced
    vmem = _fused_vmem(Sq, D, Dv, bq, bk, q.dtype, has_seg, has_sel)
    fused = vmem <= _VMEM // 2
    tel.counter_add("attention.flash_bwd_fused" if fused
                    else "attention.flash_bwd_split")
    table, steps = _table(Sq, Sk, bq, bk, causal, True, window)
    specs = _specs(D, Dv, bq, bk, steps, group)
    # K/V heads that ``group`` query heads share: each query head writes
    # the dk / dv of ITS scores ([B, H, Sk, .], a q-shaped tile spec with
    # kv rows), summed over the group below
    if group == 1:
        outs = [(specs.k, k, (bk, D)), (specs.v, v, (bk, Dv))]
    else:
        per_head = _specs(D, Dv, bq, bk, steps)
        outs = [(per_head.k, jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
                 (bk, D)),
                (per_head.v, jax.ShapeDtypeStruct((B, H, Sk, Dv), v.dtype),
                 (bk, Dv))]
    if fused:
        outs.insert(0, (specs.dq, q, (Sq // bq, bq, D)))
    grads = list(call(
        functools.partial(_bwd_kernel, fused=fused),
        "flash_bwd" if fused else "flash_dkdv", table, steps, specs, outs,
        **({"vmem_limit_bytes": vmem} if fused else {})))
    seen = (Sq - 1) // bk + 1           # kv blocks with a tile in the table
    for n, like in ((-2, k), (-1, v)):
        g = grads[n]
        if group > 1:
            g = g.astype(jnp.float32)
            g = jnp.sum(g.reshape((B, H // group, group) + g.shape[2:]),
                        axis=2).astype(like.dtype)
        if causal and seen * bk < Sk:   # ... the others were never written
            g = jnp.where(jnp.arange(Sk)[:, None] < seen * bk, g, 0)
        grads[n] = g
    if fused:
        return tuple(grads)

    table, steps = _table(Sq, Sk, bq, bk, causal, False, window)
    specs = _specs(D, Dv, bq, bk, steps, group)
    dq, = call(_dq_kernel, "flash_dq", table, steps, specs,
               [(specs.q, q, (bq, D))])
    return (dq, *grads)


# ---------------------------------------------------------------- public op

def _seg_zero_cot(seg):
    from autodist_tpu.kernel.common.variable_utils import zero_cotangent
    return zero_cotangent(seg)


def _heads_first(x):
    """[B, S, H, D] <-> [B, H, S, D]. The layout the kernels index: a
    [rows, D] block of one head is contiguous. (A [B, S, H*D] view with a
    lane block per head needs no transpose at heads of 128 and was 1.4 ms
    a step SLOWER in ``olmoe_train_1chip``: PERF.md section 6, PR 28.)"""
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash(q, k, v, q_seg, kv_seg, sel, causal, window, heads_first):
    return _flash_fwd(q, k, v, q_seg, kv_seg, sel, causal, window,
                      heads_first)[0]


def _flash_fwd(q, k, v, q_seg, kv_seg, sel, causal, window, heads_first):
    qt, kt, vt = (q, k, v) if heads_first else (
        _heads_first(q), _heads_first(k), _heads_first(v))
    segs = None if q_seg is None else (q_seg, kv_seg)
    qt = checkpoint_name(qt, KEPT)
    out, lse = _fwd(qt, kt, vt, segs, sel, causal, window)
    # (the log-sum-exp is named as [B, H, S]: as the kernels' [B, H, S, 1]
    # a row is padded to 128 lanes in HBM, 67 MB a layer at 16 heads and
    # seq 8192 where the values are 0.5; with the name on that form
    # Kimi-Linear's step asked for 0.23 GB more scratch)
    out = checkpoint_name(out, KEPT)
    lse = checkpoint_name(lse[..., 0], KEPT)[..., None]
    return _heads_first(out), (qt, kt, vt, out, lse, q_seg, kv_seg, sel)


def _flash_bwd(causal, window, heads_first, res, do):
    grads = _bwd(causal, res, _heads_first(do), window)
    if not heads_first:
        grads = tuple(_heads_first(g) for g in grads)
    return tuple(grads) + tuple(_seg_zero_cot(ids) for ids in res[5:])


_flash.defvjp(_flash_fwd, _flash_bwd)


def tileable(*seqs) -> bool:
    """Do sequences of these lengths split into tiles the kernels take (a
    power of two of 8 rows at least)?"""
    return all(_pick_block(seq, _ROWS) for seq in seqs)


def _tileable(q, k):
    return tileable(q.shape[1], k.shape[1])


def flash_attention(q, k, v, causal: bool = False, segment_ids=None,
                    select=None, window=None, heads_first: bool = False):
    """Exact fused attention. q, k: [B, S, H, D], v: [B, S, H, Dv] ->
    [B, S, H, Dv]; scores are scaled by 1 / sqrt(D). With ``heads_first``
    q, k and v come [B, H, S, .] (the kernels' own layout, which
    ``ops/attn_pre.py`` writes) and take their gradients so; the result is
    [B, S, H, Dv] either way. k and v may have fewer heads than q, a
    divisor of H: query head h reads K/V head ``h // (H / Hkv)``
    (grouped-query attention; the kernels index the shared head, nothing
    is repeated in HBM, and each query head's dk and dv are summed over
    its group after the backward kernel).

    ``select``: [B, Sq, Sk], non-zero = this query attends this key, every
    head alike (a learned sparse attention's choice of keys; composes with
    ``causal`` and the segment ids). It is read a [rows, rows] int8 tile
    at a time and masks within the tile; no gradient flows to it. Every
    query must keep at least one key.

    ``window``: with ``causal``, the latest keys a query attends, ITS OWN
    POSITION COUNTED: query i sees key j iff ``j <= i`` and ``i - j <
    window`` (a sliding window of 4,096 is the query and the 4,095 before
    it). The kernels walk only the tiles the band touches. None = every
    earlier key.

    ``segment_ids``: [B, S] int32 (shared q/kv for self-attention) or a
    ``(q_seg, kv_seg)`` pair — attention is allowed iff the ids are equal.
    For a BERT-style key-padding mask, pass validity as segment ids (1 for
    real tokens, 0 for padding): valid tokens then attend exactly the
    valid tokens; padding rows attend padding (their outputs are excluded
    from any loss that masks padding, which BERT's MLM objective does).
    Composes with ``causal``.

    Falls back to the XLA reference path (differentiable as usual) when the
    sequence can't be tiled (remainder below the 8-row minimum block)."""
    seq_axis = 2 if heads_first else 1
    if segment_ids is None:
        q_seg = kv_seg = None
    elif isinstance(segment_ids, (tuple, list)):
        q_seg = jnp.asarray(segment_ids[0], jnp.int32)
        kv_seg = jnp.asarray(segment_ids[1], jnp.int32)
    else:
        q_seg = kv_seg = jnp.asarray(segment_ids, jnp.int32)
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError(
                "a window counts the latest keys a causal query sees, itself "
                "included (>= 1): got window %d with causal %s"
                % (window, causal))
        if window >= k.shape[seq_axis]:
            window = None       # every earlier key: the plain causal table
    if not tileable(q.shape[seq_axis], k.shape[seq_axis]):
        if heads_first:
            q, k, v = (_heads_first(x) for x in (q, k, v))
        from autodist_tpu.ops.attention import (causal_band,
                                                reference_attention)
        from autodist_tpu.utils import logging
        logging.warning(
            "flash_attention: q %s / k %s cannot be tiled (8-row minimum) "
            "— running the XLA reference path instead of the kernel",
            tuple(q.shape), tuple(k.shape))
        mask = None
        if causal:
            mask = causal_band(q.shape[1], k.shape[1], window)[None, None]
        if q_seg is not None:
            seg_mask = (q_seg[:, :, None] == kv_seg[:, None, :])[:, None]
            mask = seg_mask if mask is None else jnp.logical_and(mask,
                                                                 seg_mask)
        if select is not None:
            chosen = (jnp.asarray(select) != 0)[:, None]
            mask = chosen if mask is None else jnp.logical_and(mask, chosen)
        group = q.shape[2] // k.shape[2]
        if group > 1:
            k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        return reference_attention(q, k, v, mask)
    if select is not None:
        select = jnp.asarray(select).astype(jnp.int8)
    return _flash(q, k, v, q_seg, kv_seg, select, causal, window, heads_first)


def make_flash_attn_fn(causal: bool = True):
    """(q, k, v, mask) -> out adapter for model layers' ``attn_fn`` slot.

    A key-padding mask (boolean, broadcastable [B, 1, 1, S] / [B, S])
    becomes segment ids (valid=1, pad=0) — the masked-tile block path.
    Arbitrary dense masks are not expressible as segments and raise;
    ``select`` ([B, Sq, Sk], a sparse attention's chosen keys),
    ``window`` (a layer's sliding window) and ``heads_first`` (operands
    [B, H, S, D]) go to the kernels as they are. The adapter says of
    itself that it takes operands heads-first (``attn.heads_first``): what
    ``ops/attn_pre.py:runs_fused`` asks of a layer's ``attn_fn``."""
    def attn(q, k, v, mask=None, select=None, window=None,
             heads_first=False):
        if mask is None:
            return flash_attention(q, k, v, causal, select=select,
                                   window=window, heads_first=heads_first)
        m = jnp.asarray(mask)
        # accept [B, S] or the layers' [B, 1, 1, S] broadcast form
        if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1:
            m = m[:, 0, 0, :]
        elif m.ndim != 2:
            raise ValueError(
                "flash attention supports key-padding masks ([B, S] or "
                "[B, 1, 1, S]) via segment ids; got mask shape %s"
                % (mask.shape,))
        return flash_attention(q, k, v, causal, m.astype(jnp.int32), select,
                               window, heads_first)
    attn.heads_first = True
    return attn
