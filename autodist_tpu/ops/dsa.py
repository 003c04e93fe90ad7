"""A learned sparse attention's choice of keys (DeepSeek Sparse Attention's
"lightning indexer" of DeepSeek-V3.2-Exp's report): which keys each query
attends, as a [B, S, S] int8 selection for ``ops/flash_attention.py``.

The index score of query t and key s <= t is

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])

over the indexer's heads j, all of which read ONE key head; query t keeps
the ``topk`` keys of largest I[t, .] among s <= t (all of them while
t < topk), ties to the lower s. A positive scale on I changes no choice, so
none is applied.

How it is computed:

- in float32 with true float32 products (``Precision.HIGHEST``), as the
  routers are: a choice is discrete, and a bfloat16 product moves scores
  by 2^-9 of their size, enough to swap keys near the threshold;
- a block of ``block`` queries at a time under ``lax.map``: a block's
  [B, block, S] scores are live, never [S, S] a head; the blocks go in
  up to ``SEGMENTS`` runs, each against the keys up to its own last
  query only (a block's later keys are unseen: with four runs 10 of 16
  quarter-squares are computed, not 16);
- the choice needs the k-th largest score of a row and nothing of the
  order above it, so nothing is sorted: scores map to unsigned integers
  of the same order (:func:`_ordered_bits`) and the k-th largest is built
  bit by bit, 32 counts of a row against a candidate (:func:`kth_largest`).
  Keys above it are in, and of the keys equal to it the first that are
  still needed (a cumulative count, run only where some row has more ties
  than it needs: -0.0 is made +0.0 first, so that the two tie as numbers
  do).

No gradient flows through a choice. What it yields carries the name
:data:`KEPT`: a block recomputed in the backward pass keeps it by that name
(``models/lm.py:TransformerLM._block``, as ``flash_attention.KEPT`` and
``kda.KEPT`` are kept) and its recomputed forward then holds neither the
scores nor the choice.
"""
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu.telemetry import scopes

# the selection, by name, for a recomputing checkpoint's policy
KEPT = "dsa_choice_kept"
# runs of query blocks, each scored against the keys it can see
SEGMENTS = 4


def index_scores(q_idx, k_idx, w):
    """I [B, Q, S] float32 of q_idx [B, Q, J, D], k_idx [B, S, D] and
    w [B, Q, J]: ``sum_j w_j relu(q_j . k)``, -0.0 made +0.0."""
    dots = jnp.einsum("bqjd,bsd->bqjs", q_idx, k_idx,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)
    return jnp.where(scores == 0, 0.0, scores)


def _ordered_bits(x):
    """float32 -> uint32 with the same order: a < b iff bits(a) < bits(b)
    (negative numbers have every bit flipped, the others the sign bit)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(bits, k):
    """The k-th largest of each row of ``bits`` [..., S] uint32: the
    largest v with ``#{bits >= v} >= k``, one bit a pass from the top."""
    def one_bit(i, found):
        candidate = found | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(bits >= candidate[..., None], axis=-1) >= k
        return jnp.where(enough, candidate, found)
    return jax.lax.fori_loop(0, 32, one_bit,
                             jnp.zeros(bits.shape[:-1], jnp.uint32))


def choose(scores, rows, k):
    """[B, Q, S] bool: the keys each of the block's queries keeps. Query
    ``rows[i]`` sees keys 0..rows[i]; of those the k of largest score, ties
    to the lower key; all of them where there are no more than k."""
    seen = rows[:, None] >= jnp.arange(scores.shape[-1])[None, :]
    # (an unseen key sorts under every score, -inf included)
    bits = jnp.where(seen, _ordered_bits(scores), jnp.uint32(0))
    kth = kth_largest(bits, k)[..., None]
    above = bits > kth
    tied = (bits == kth) & seen
    needed = k - jnp.sum(above, axis=-1, keepdims=True)

    def first_needed(tied):
        return tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= needed)
    more_than_needed = jnp.any(
        jnp.sum(tied, axis=-1, keepdims=True) > needed)
    return above | jax.lax.cond(more_than_needed, first_needed,
                                lambda tied: tied, tied)


def chosen_keys(q_idx, k_idx, w, topk, block):
    """The selection [B, S, S] int8 (non-zero = query attends key) of an
    indexer's q_idx [B, S, J, D], k_idx [B, S, D] and w [B, S, J], all
    float32: ``block`` queries at a time where that divides S, else all S
    at once. Carries the name :data:`KEPT`."""
    B, S = k_idx.shape[:2]
    step = block if S % block == 0 else S
    n = S // step
    runs = SEGMENTS if n % SEGMENTS == 0 else 1

    def run(lo, hi):
        """Queries lo..hi against keys 0..hi: [B, hi - lo, S], zeros past
        hi."""
        keys = k_idx[:, :hi]

        def one_block(xs):
            q_rows, w_rows, rows = xs
            with scopes.scope(scopes.DSA_INDEX):
                scores = index_scores(q_rows, keys, w_rows)
            with scopes.scope(scopes.DSA_TOPK):
                return choose(scores, rows, topk).astype(jnp.int8)

        def blocks(x):  # [B, S, ...] -> [(hi - lo) / step, B, step, ...]
            x = x[:, lo:hi]
            return jnp.moveaxis(x.reshape((B, -1, step) + x.shape[2:]), 1, 0)
        chosen = jax.lax.map(one_block, (
            blocks(q_idx), blocks(w), jnp.arange(lo, hi).reshape(-1, step)))
        chosen = jnp.moveaxis(chosen, 0, 1).reshape(B, hi - lo, hi)
        return jnp.pad(chosen, [(0, 0), (0, 0), (0, S - hi)])

    edges = [S // runs * i for i in range(runs + 1)]
    chosen = jnp.concatenate([run(lo, hi)
                              for lo, hi in zip(edges, edges[1:])], axis=1)
    return checkpoint_name(chosen, KEPT)
