"""Plain float32 reference of LFM2-24B-A2B (lfm2_moe family): the layer
equations of the model's public ``config.json`` (``model_type: lfm2_moe``),
written from the equations and not from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no kernel, no chunked head. The convolution is three shifted
products; attention materialises its scores, one checkpointed block of 512
queries after another under ``lax.map``; EVERY held expert is applied to
EVERY token and masked by the routing's weights. Each layer runs under
``jax.checkpoint`` for memory; that changes no value. Every matmul runs
under ``default_matmul_precision("highest")``
(``reference/lm.py:train_check``).

The equations (x the residual stream, ``h = RMSNorm(x)``, eps 1e-5, no
bias anywhere, no scale on the embedding; S positions, causal):

- block: ``x <- x + Mix_l(RMSNorm(x))``, ``x <- x + FFN_l(RMSNorm(x))``;
  after the last block one RMSNorm, then ``logits = h E^T`` with E the
  embedding table (tied).
- conv mixer (3 taps): ``[B_t, C_t, u_t] = W_in h_t`` (d -> 3 d, thirds in
  that order); ``z_t = B_t * u_t``; ``c_t = sum_{j=0..2} w_j * z_{t-2+j}``
  (one filter a channel, zeros before the sequence's start, no activation);
  ``y_t = W_out (C_t * c_t)``.
- attention: ``q = W_q h`` in H heads of D, ``k = W_k h``, ``v = W_v h`` in
  H / G heads; q and k pass an RMSNorm over their D features with a
  learned weight (one for q, one for k, shared by the heads), then RoPE
  (theta 1e6) over all D features (``rotate_half`` pairing, feature i with
  i + D / 2, positions 0..S-1); query head i reads K/V head ``i // G``;
  ``softmax(q k^T / sqrt D) v`` over the keys a query sees; ``W_o`` on the
  heads side by side.
- dense feed-forward (the leading layers): ``W_2(silu(W_1 h) * W_3 h)``.
- routed feed-forward: ``s = sigmoid(W_r h)`` over ALL the router's
  outputs; chosen = top k of ``s + b`` (b only chooses); gates
  ``s_e / sum of the chosen s`` (x ``routed_scaling_factor`` 1);
  ``y = sum over chosen and held e of g_e E_e(h)``, E a SwiGLU. No shared
  expert.
- loss: mean NLL.

The share. The program holds some of each layer's experts (``held``: by
default the first E of the router's outputs, E the size of the weight
stacks) and so does this reference: the router scores and chooses over all
its outputs and renormalises over the chosen, and only held experts add to
the result. :func:`routed_ffn` with every expert held is the uncut layer.

Assumed, the catalog's row being silent (listed in the configuration's
file): the tied head and the per-head norm of q and k before the rotation
(the LFM2 family's convention); ``b`` stays at its initial zero (no update
rule is published). Departure, shared with the program: the released code
divides the gates by ``sum + 1e-6``, here and in the program by ``sum``.

The precision control is ``reference/olmoe.py``'s: under
:func:`computed_in` every matmul takes its operands rounded to a coarser
dtype.
"""
import math

import jax
import jax.numpy as jnp

from benchmark.reference import lm
# (Kimi-Linear's reference wrote the depthwise causal convolution, ``c_t =
# sum_j w_j z_{t-(K-1)+j}`` with zeros before the start, as K shifted
# products, and the dense SwiGLU; neither knows a model's constant)
from benchmark.reference.kimi_linear import causal_conv as short_conv
from benchmark.reference.kimi_linear import swiglu
from benchmark.reference.olmoe import (computed_in, einsum, expert,  # noqa: F401
                                       mm, rotate_half)

RMS_EPS = 1e-5           # norm_eps
TOP_K = 4                # num_experts_per_tok
ROPE_THETA = 1e6         # rope_parameters.rope_theta
QUERY_BLOCK = 512        # queries per block of materialised scores


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


# ------------------------------------------------------------- conv mixer

def conv_mixer(h, c, b_gate=True, c_gate=True):
    """The gated short convolution on the normed input. ``b_gate`` /
    ``c_gate`` False plant faults of ``tools/loss_limit_lfm2_moe.py``."""
    b, cc, u = jnp.split(mm(h, c["in_proj"]["kernel"]), 3, axis=-1)
    y = short_conv(b * u if b_gate else u, c["conv"])
    return mm(cc * y if c_gate else y, c["out_proj"]["kernel"])


# -------------------------------------------------------------- attention

def rope(x):
    """x [B, S, H, D] turned by its position (the index in the sequence),
    feature i paired with i + D/2."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / ROPE_THETA ** (jnp.arange(0, dim, 2) / dim)
    ang = jnp.arange(seq)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def attention(h, a, head_norm=True, kv_head_of=lambda i, group: i // group):
    """The attention sub-layer on the normed input. ``head_norm`` False and
    another ``kv_head_of`` plant faults."""
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

    @jax.checkpoint
    def attend(block):
        q_rows, rows = block
        seen = rows[:, None] >= jnp.arange(S)[None, :]
        logits = einsum("bqhd,bthd->bhqt", q_rows, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), -1)
        return einsum("bhqt,bthd->bqhd", p, v)

    step = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    o = jax.lax.map(attend, (
        jnp.moveaxis(q.reshape(B, S // step, step, H, D), 1, 0),
        jnp.arange(S).reshape(S // step, step)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, D)
    return einsum("bqhk,hkd->bqd", o, a["out"]["kernel"])


# -------------------------------------------------------------------- MoE

def routing(scores, bias, top_k):
    """weight [T, E_all] of sigmoid scores [T, E_all]: the top k of
    ``scores + bias`` get ``score / (sum of the chosen scores)``, the
    others 0."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = scores * jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), axis=1)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed_ffn(h, m, top_k, held=None):
    """One routed layer for h [T, d]: the held experts' part of the routed
    sum."""
    scores = jax.nn.sigmoid(mm(h, m["router"]))
    weight = routing(scores, m["e_score_correction_bias"], top_k)
    held = tuple(range(m["gate_proj"].shape[0])) if held is None else held

    def add_expert(out, e):  # one expert after another: compiled once
        w_gate, w_up, w_down, its_weight = e
        return out + its_weight[:, None] * expert(h, w_gate, w_up, w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["gate_proj"], m["up_proj"], m["down_proj"],
        weight.T[jnp.asarray(held)]))
    return out


# ------------------------------------------------------------------ model

def layer(x, lp, top_k, held):
    h = rms(x, lp["RMSNorm_0"]["scale"])
    x = x + (conv_mixer(h, lp["conv"]) if "conv" in lp
             else attention(h, lp["MultiHeadAttention_0"]))
    h = rms(x, lp["RMSNorm_1"]["scale"])
    if "mlp" in lp:                            # a leading dense layer
        return x + swiglu(h, lp["mlp"])
    y = routed_ffn(h.reshape(-1, h.shape[-1]), lp["moe"], top_k, held)
    return x + y.reshape(x.shape)


def head(h, p):
    """logits = h E^T, E the embedding table (``head`` planted untied
    reads another matrix)."""
    return mm(h, p["embed"]["embedding"].T)


def logits_fn(params, ids, top_k=TOP_K, held=None):
    """[B, S] token ids -> [B, S, vocab] float32 logits."""
    p = params["params"]
    x = p["embed"]["embedding"][ids]          # no scale, no position table
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        # the module-level conv_mixer / attention / routing / routed_ffn
        # are looked up at trace time, so a planted fault reaches them
        x = jax.checkpoint(
            lambda x, lp: layer(x, lp, top_k, held))(x, p["layer_%d" % i])
    return head(rms(x, p["final_ln"]["scale"]), p)


def nll_sum(params, batch, top_k=TOP_K, held=None):
    """Sum of next-token negative log-likelihoods: sum / weight is the
    training loss."""
    tokens = batch["tokens"]
    logits = logits_fn(params, tokens[:, :-1], top_k, held)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(picked)


batch_weight = lm.batch_weight


def train_check(nll_sum_fn, weight_fn, params, batch0, batch1, devices):
    """``reference/lm.py:train_check`` one sequence at a time on the first
    device: the NLL is a sum over rows, so the blocks add up whatever the
    replicas."""
    return lm.train_check(nll_sum_fn, weight_fn, params, batch0, batch1,
                          devices[:1], block_rows=1)
