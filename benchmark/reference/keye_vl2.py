"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language model
(keye_vl2 family), text only: the layer equations of the model's public
``config.json`` (Qwen3-MoE's keys plus ``sa_config``, a DeepSeek Sparse
Attention indexer), written from the equations and not from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no kernel, no bit-wise selection, no chunked head. Attention
materialises its scores, one checkpointed block of 512 queries after
another under ``lax.map``; a block's choice of keys is a STABLE SORT of its
index scores (the program sorts nothing). EVERY held expert is applied to
EVERY token and masked by the routing's weights. Each layer runs under
``jax.checkpoint`` for memory; that changes no value. Every matmul runs
under ``default_matmul_precision("highest")``
(``reference/lm.py:train_check``).

The equations (x the residual stream, ``h = RMSNorm(x)``, eps 1e-6, no
bias on a projection; S positions, causal):

- attention: ``q = W_q h`` in H heads of D, ``k = W_k h``, ``v = W_v h``
  in H / G heads; q and k pass an RMSNorm over their D features with a
  learned weight (one for q, one for k, shared by the heads), then RoPE
  over all D features (``rotate_half`` pairing, positions 0..S-1); query
  head i reads K/V head ``i // G``.
- indexer, on ``h`` with the gradient stopped: ``q' = W_q' h`` in J heads
  of D', ``k' = LayerNorm(W_k' h)`` ONE head, ``w = W_w h`` [J]; the first
  ``INDEX_ROPE_DIM`` features of q' and k' rotated the same way;
  ``I[t, s] = sum_j w[t, j] relu(q'[t, j] . k'[s])`` for s <= t; query t
  keeps the ``INDEX_TOPK`` keys of largest I[t, .] (all while t <
  INDEX_TOPK; ties to the lower s; -0.0 ties with +0.0).
- ``o[t, i] = softmax over the kept s of (q[t, i] . k[s, i // G] / sqrt D)``
  times v; ``W_o`` on the heads side by side.
- feed-forward: ``p = softmax(W_r h)`` over ALL the router's outputs, the
  top k chosen, gates ``p_e / sum of the chosen p``, ``y = sum over chosen
  and held e of g_e E_e(h)``, E a SwiGLU. No shared expert.
- loss: mean NLL. The choice is discrete: no gradient reaches the
  indexer's weights, and Adam's first step leaves them where they were.

The share. The program holds some of each layer's experts (``held``: by
default the first E of the router's outputs, E the size of the weight
stacks) and so does this reference: the router scores and chooses over all
its outputs and renormalises over the chosen, and only held experts add to
the result. :func:`routed_ffn` with every expert held is the uncut layer.

Assumed, the catalog's row being silent (listed in the configuration's
file): the per-head norm of q and k (Qwen3-MoE's modelling code has it
without a key), the LayerNorm on k' and the rotation of the first half of
the indexer's features (DeepSeek-V3.2's released indexer, at its sizes).
Left out, here and in the program: DeepSeek-V3.2's alignment loss for the
indexer, which neither the row nor ``sa_config`` carries; its Hadamard
rotation of q' and k' (orthogonal: it changes no score) and their float8
rounding; the vision tower and M-RoPE's image positions.

The precision control is ``reference/olmoe.py``'s: under
:func:`computed_in` every matmul takes its operands rounded to a coarser
dtype.
"""
import math

import jax
import jax.numpy as jnp

from benchmark.reference import lm
from benchmark.reference.olmoe import (computed_in, einsum, expert,  # noqa: F401
                                       mm, rotate_half)

RMS_EPS = 1e-6
LN_EPS = 1e-6
TOP_K = 8                # num_experts_per_tok
ROPE_THETA = 1e7
INDEX_TOPK = 2048        # sa_config.topk
INDEX_ROPE_DIM = 32      # of indexer_head_dim 64 (assumed)
QUERY_BLOCK = 512        # queries per block of materialised scores


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def rope(x):
    """x [B, S, H, D] turned by its position (the index in the sequence),
    feature i paired with i + D/2."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / ROPE_THETA ** (jnp.arange(0, dim, 2) / dim)
    ang = jnp.arange(seq)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


# --------------------------------------------------------------- indexer

def indexer(h, ix, rope_dim):
    """(q' [B, S, J, D'], k' [B, S, D'], w [B, S, J]) of the normed input."""
    h = jax.lax.stop_gradient(h)
    B, S, _ = h.shape
    w = mm(h, ix["weights_proj"]["kernel"])
    q = mm(h, ix["wq"]["kernel"]).reshape(B, S, w.shape[-1], -1)
    k = layer_norm(mm(h, ix["wk"]["kernel"]), ix["k_norm"])[:, :, None, :]

    def turn(t):
        return jnp.concatenate([rope(t[..., :rope_dim]), t[..., rope_dim:]],
                               axis=-1) if rope_dim else t
    return turn(q), turn(k)[:, :, 0, :], w


def index_scores(q_rows, k, w_rows):
    """I [B, rows, S] of a block of queries against every key."""
    dots = einsum("bqjd,bsd->bqjs", q_rows, k)
    scores = jnp.sum(jax.nn.relu(dots) * w_rows[..., None], axis=2)
    return jnp.where(scores == 0, 0.0, scores)       # -0.0 ties with 0.0


def kept(scores, rows, topk):
    """[B, rows, S] bool: key s is among the ``topk`` best of the keys
    query rows[i] sees, by a stable sort from the largest score down."""
    seen = rows[:, None] >= jnp.arange(scores.shape[-1])[None, :]
    order = jnp.argsort(-jnp.where(seen, scores, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return seen & (rank < topk)


# ------------------------------------------------------------- attention

def attention(h, a, topk=INDEX_TOPK, rope_dim=INDEX_ROPE_DIM, choose=True,
              head_norm=True, kv_head_of=lambda i, group: i // group,
              return_kept=False):
    """The attention sub-layer on the normed input. ``choose`` False (dense
    causal attention), ``head_norm`` False and another ``kv_head_of`` plant
    faults of ``tools/loss_limit_keye_vl2.py``; ``return_kept`` gives the
    [B, S, S] choice instead."""
    B, S, _ = h.shape
    q = einsum("bsd,dhk->bshk", h, a["query"]["kernel"])
    k = einsum("bsd,dhk->bshk", h, a["key"]["kernel"])
    v = einsum("bsd,dhk->bshk", h, a["value"]["kernel"])
    H, D = q.shape[2:]
    group = H // k.shape[2]
    if head_norm:
        q, k = rms(q, a["q_norm"]["scale"]), rms(k, a["k_norm"]["scale"])
    q, k = rope(q), rope(k)
    # every query head's own K/V rows, by index (a reference may repeat)
    heads = jnp.asarray([kv_head_of(i, group) for i in range(H)])
    k, v = k[:, :, heads], v[:, :, heads]
    qi, ki, wi = indexer(h, a["indexer"], rope_dim)

    @jax.checkpoint
    def attend(block):
        q_rows, qi_rows, wi_rows, rows = block
        seen = rows[:, None] >= jnp.arange(S)[None, :]
        keep = kept(index_scores(qi_rows, ki, wi_rows), rows, topk) \
            if choose else jnp.broadcast_to(seen, (B,) + seen.shape)
        if return_kept:
            return keep
        logits = einsum("bqhd,bthd->bhqt", q_rows, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(keep[:, None], logits, -jnp.inf), -1)
        return einsum("bhqt,bthd->bqhd", p, v)

    step = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def blocks(x):
        return jnp.moveaxis(x.reshape((B, S // step, step) + x.shape[2:]),
                            1, 0)
    o = jax.lax.map(attend, (blocks(q), blocks(qi), blocks(wi),
                             jnp.arange(S).reshape(S // step, step)))
    o = jnp.moveaxis(o, 0, 1)
    if return_kept:
        return o.reshape(B, S, S)
    return einsum("bqhk,hkd->bqd", o.reshape(B, S, H, D), a["out"]["kernel"])


# -------------------------------------------------------------------- MoE

def routing(p, top_k):
    """weight [T, E_all] of router probabilities p: ``p_e / sum of the
    chosen p`` where e is among t's top k, else 0."""
    _, chosen = jax.lax.top_k(p, top_k)
    picked = p * jnp.sum(jax.nn.one_hot(chosen, p.shape[-1]), axis=1)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed_ffn(h, m, top_k, held=None):
    """One routed layer for h [T, d]: the held experts' part of the routed
    sum."""
    weight = routing(jax.nn.softmax(mm(h, m["router"]), axis=-1), top_k)
    held = tuple(range(m["gate_proj"].shape[0])) if held is None else held

    def add_expert(out, e):  # one expert after another: compiled once
        w_gate, w_up, w_down, its_weight = e
        return out + its_weight[:, None] * expert(h, w_gate, w_up, w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["gate_proj"], m["up_proj"], m["down_proj"],
        weight.T[jnp.asarray(held)]))
    return out


# ------------------------------------------------------------------ model

def layer(x, lp, top_k, held, topk, rope_dim):
    x = x + attention(rms(x, lp["RMSNorm_0"]["scale"]),
                      lp["MultiHeadAttention_0"], topk, rope_dim)
    h = rms(x, lp["RMSNorm_1"]["scale"])
    y = routed_ffn(h.reshape(-1, h.shape[-1]), lp["moe"], top_k, held)
    return x + y.reshape(x.shape)


def logits_fn(params, ids, top_k=TOP_K, held=None, topk=INDEX_TOPK,
              rope_dim=INDEX_ROPE_DIM):
    """[B, S] token ids -> [B, S, vocab] float32 logits."""
    p = params["params"]
    x = p["embed"]["embedding"][ids]          # no scale, no position table
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        # the module-level attention / routing / routed_ffn are looked up
        # at trace time, so a planted fault reaches them
        x = jax.checkpoint(
            lambda x, lp: layer(x, lp, top_k, held, topk, rope_dim))(
            x, p["layer_%d" % i])
    return mm(rms(x, p["final_ln"]["scale"]), p["lm_head"]["kernel"])


def nll_sum(params, batch, top_k=TOP_K, held=None, topk=INDEX_TOPK,
            rope_dim=INDEX_ROPE_DIM):
    """Sum of next-token negative log-likelihoods: sum / weight is the
    training loss."""
    tokens = batch["tokens"]
    logits = logits_fn(params, tokens[:, :-1], top_k, held, topk, rope_dim)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(picked)


def kept_in_layer_0(params, ids, topk=INDEX_TOPK, rope_dim=INDEX_ROPE_DIM):
    """[B, S, S] bool: the first layer's choice of keys, whose input (the
    embedding's rows) the program and this reference share."""
    p = params["params"]
    lp = p["layer_0"]
    return attention(rms(p["embed"]["embedding"][ids],
                         lp["RMSNorm_0"]["scale"]),
                     lp["MultiHeadAttention_0"], topk, rope_dim,
                     return_kept=True)


batch_weight = lm.batch_weight


def train_check(nll_sum_fn, weight_fn, params, batch0, batch1, devices):
    """``reference/lm.py:train_check`` one sequence at a time on the first
    device: the NLL is a sum over rows, so the blocks add up whatever the
    replicas."""
    return lm.train_check(nll_sum_fn, weight_fn, params, batch0, batch1,
                          devices[:1], block_rows=1)
