"""Plain float32 reference of DeepSeek-V2-Lite (deepseek_v2 family): the
layer equations of arXiv 2405.04434 and of the model's public
``config.json``, written from the equations and not from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no kernel, no sort, no grouped matmul, no chunked head.
Latent attention materialises its scores, one checkpointed block of 512
queries after another under ``lax.map`` (a Python loop is scheduled side
by side: 12.6 GB at 1 x 8,192, PR 29). EVERY held expert is applied to
EVERY token and masked by the routing's weights, so a pair the program
dropped or sent to the wrong expert shows as a wrong loss. Each layer
runs under ``jax.checkpoint`` for memory; that changes no value. Every
matmul runs under ``default_matmul_precision("highest")``
(``reference/lm.py:train_check``).

The equations (x the residual stream, ``h = RMSNorm(x)``, eps 1e-6, no
bias anywhere):

- latent attention, every layer: ``q = W_q h`` in heads of (nope | pe);
  ``[c | k_pe] = W_kva h``; ``[k_nope | v] = W_kvb RMSNorm(c)`` in heads;
  ``k_pe`` is ONE key a token, shared by the heads; ``q_pe`` and ``k_pe``
  are rotated by position with YaRN's frequencies (:func:`yarn_inv_freq`),
  cos and sin times ``ms(mscale) / ms(mscale_all_dim)``; scores
  ``(q_nope k_nope + q_pe k_pe) (nope + pe)^-0.5 ms(mscale_all_dim)^2``
  with ``ms(a) = 0.1 a ln(factor) + 1``, causal softmax, values, ``W_o``.
- feed-forward: layer 0 a SwiGLU; after it ``s = softmax(W_r h)`` over ALL
  the router's outputs, the top k chosen, the gate ``s_e`` itself (no
  renormalisation, x 1), ``y = sum_chosen s_e E_e(h) + Shared(h)``.
- loss: ``mean NLL + alpha sum_l L_l``, ``L_l = mean_b sum_e f_be P_be``
  per SEQUENCE b of S tokens, ``f_be = E / (k S) #{tokens of b that chose
  e}``, ``P_be = mean_t s_te`` (``seq_aux``); an even router gives 1.

The share. The program holds some of each layer's experts (``held``: by
default the first E of the router's outputs, E the size of the weight
stacks) and so does this reference: the router scores all its outputs,
chooses over all of them and takes its balance loss over all of them, and
only held experts add to the result. :func:`routed_ffn` with every expert
held is the uncut layer.

Departures from the released code, shared with the program: the rotated
features pair as ``rotate_half`` pairs them (i with i + d/2); the released
code first un-interleaves (2i, 2i + 1), which with seeded weights is a
permutation of ``W_q``'s and ``W_kva``'s columns. The balance loss's VALUE
is part of the loss compared here (the released code injects its gradient
and reports the NLL alone). The paper's device-level and communication
balance losses are in neither ``config.json`` nor the released modelling
code and are left out. ``aux_loss_alpha`` 0.001 is the released config's
(the catalog's row keeps shape keys only).

The precision control is ``reference/olmoe.py``'s: under
:func:`computed_in` every matmul takes its operands rounded to a coarser
dtype.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import lm
from benchmark.reference.olmoe import (computed_in, einsum, expert,  # noqa: F401
                                       mm)

RMS_EPS = 1e-6
TOP_K = 6                # num_experts_per_tok
ALPHA = 0.001            # aux_loss_alpha
ROPE_THETA = 10000.0
YARN = {"factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
QUERY_BLOCK = 512        # queries per block of materialised scores


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


def swiglu(h, m):
    return mm(jax.nn.silu(mm(h, m["gate_proj"]["kernel"]))
              * mm(h, m["up_proj"]["kernel"]), m["down_proj"]["kernel"])


# ------------------------------------------------------------------ YaRN

def ms(yarn, a):
    """YaRN's temperature term for a context extended ``factor`` times."""
    return 0.1 * a * math.log(yarn["factor"]) + 1.0 \
        if yarn["factor"] > 1 else 1.0


def yarn_inv_freq(dim, yarn):
    """The angle a position adds to rotary pair i of ``dim / 2`` [float32]:
    ``theta^(-2i/dim)`` where the pair turns often over the original window
    (extrapolated as trained), that over ``factor`` where it turns rarely
    (interpolated), a linear ramp between pair ``low`` and pair ``high``."""
    i = np.arange(dim // 2)
    f_extra = ROPE_THETA ** (-2.0 * i / dim)
    f_inter = f_extra / yarn["factor"]

    def cd(r):   # the pair that makes r whole turns over the window
        return dim * math.log(yarn["original_max_position_embeddings"]
                              / (2 * math.pi * r)) / (2 * math.log(ROPE_THETA))
    low = max(math.floor(cd(yarn["beta_fast"])), 0)
    high = min(math.ceil(cd(yarn["beta_slow"])), dim - 1)
    m = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f_inter * (1.0 - m) + f_extra * m).astype(np.float32)


def softmax_scale(width, yarn):
    return width ** -0.5 * ms(yarn, yarn["mscale_all_dim"]) ** 2


def rotate(x, yarn):
    """x [B, S, H, D] turned by its position (the index in the sequence),
    feature i paired with i + D/2."""
    seq, dim = x.shape[1], x.shape[-1]
    ang = jnp.arange(seq)[:, None] * yarn_inv_freq(dim, yarn)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    amp = ms(yarn, yarn["mscale"]) / ms(yarn, yarn["mscale_all_dim"])
    x1, x2 = jnp.split(x, 2, axis=-1)
    return (x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)) \
        * amp


# ------------------------------------------------------------------- MLA

def mla(x, a, yarn, norm_latent=True):
    """Latent attention, training form. ``norm_latent`` False plants a
    fault of ``tools/loss_limit_deepseek_v2.py``."""
    B, S, _ = x.shape
    rank = a["kv_a_norm"]["scale"].shape[0]
    v_dim = a["o_proj"]["kernel"].shape[0]
    kv_a = mm(x, a["kv_a_proj"]["kernel"])
    latent, k_pe = kv_a[..., :rank], kv_a[..., rank:]
    if norm_latent:
        latent = rms(latent, a["kv_a_norm"]["scale"])
    kv = mm(latent, a["kv_b_proj"]["kernel"])
    # the widths are q = H (nope + pe), kv = H nope + v_dim, v_dim = H v
    pe = k_pe.shape[-1]
    H = (a["q_proj"]["kernel"].shape[1] - (kv.shape[-1] - v_dim)) // pe
    q = mm(x, a["q_proj"]["kernel"]).reshape(B, S, H, -1)
    kv = kv.reshape(B, S, H, -1)
    nope = q.shape[-1] - pe
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], yarn)], -1)
    k_pe = rotate(k_pe[:, :, None, :], yarn)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, pe))], -1)
    scale = softmax_scale(q.shape[-1], yarn)

    @jax.checkpoint
    def attend(block):
        q_rows, rows = block
        logits = einsum("bqhd,bthd->bhqt", q_rows, k) * scale
        seen = rows[:, None] >= jnp.arange(S)[None, :]
        w = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), -1)
        return einsum("bhqt,bthd->bqhd", w, v)

    # one block of queries after another (``lax.map``: the compiler may
    # not run them side by side), [H, rows, S] scores live at a time
    step = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    o = jax.lax.map(attend, (
        jnp.moveaxis(q.reshape(B, S // step, step, H, -1), 1, 0),
        jnp.arange(S).reshape(S // step, step)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, -1)
    return mm(o.reshape(B, S, -1), a["o_proj"]["kernel"])


# -------------------------------------------------------------------- MoE

def routing(s, top_k):
    """(weight [T, E_all], chosen [T, k]) of router probabilities s:
    weight[t, e] = s[t, e] where e is among t's top k (no renormalising,
    x 1), else 0."""
    _, chosen = jax.lax.top_k(s, top_k)
    picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
    return s * picked, chosen


def balance_loss(s, chosen, sequences):
    """``mean_b sum_e f_be P_be`` of probabilities s [T, E_all] and choices
    [T, k], the T rows ``sequences`` sequences one after another."""
    E, k = s.shape[-1], chosen.shape[-1]
    S = s.shape[0] // sequences
    times = jnp.sum(jax.nn.one_hot(chosen, E), axis=1).reshape(sequences, S, E)
    f = jnp.sum(times, axis=1) * E / (k * S)
    P = jnp.mean(s.reshape(sequences, S, E), axis=1)
    return jnp.mean(jnp.sum(jax.lax.stop_gradient(f) * P, axis=-1))


def shared_ffn(h, m):
    """The two shared experts: one SwiGLU of twice an expert's width."""
    return swiglu(h, m["shared"])


def routed_ffn(h, m, top_k, sequences, held=None, shared=True):
    """(output, L) of one routed layer for h [T, d]: the held experts' part
    of the routed sum plus the shared experts (``shared`` False leaves
    them out: the share test counts them once, a planted fault not at
    all), and the layer's balance loss over ALL the router's outputs."""
    s = jax.nn.softmax(mm(h, m["router"]), axis=-1)
    weight, chosen = routing(s, top_k)
    held = tuple(range(m["gate_proj"].shape[0])) if held is None else held

    def add_expert(out, e):  # one expert after another: compiled once
        w_gate, w_up, w_down, its_weight = e
        return out + its_weight[:, None] * expert(h, w_gate, w_up, w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["gate_proj"], m["up_proj"], m["down_proj"],
        weight.T[jnp.asarray(held)]))
    if shared:
        out = out + shared_ffn(h, m)
    return out, balance_loss(s, chosen, sequences)


# ------------------------------------------------------------------ model

def layer(x, lp, top_k, held, yarn):
    """(x after the layer, the layer's balance loss: 0 for a dense one)."""
    x = x + mla(rms(x, lp["RMSNorm_0"]["scale"]), lp["mla"], yarn)
    h = rms(x, lp["RMSNorm_1"]["scale"])
    if "mlp" in lp:                            # the leading dense layer
        return x + swiglu(h, lp["mlp"]), 0.0
    y, aux = routed_ffn(h.reshape(-1, h.shape[-1]), lp["moe"], top_k,
                        x.shape[0], held)
    return x + y.reshape(x.shape), aux


def forward(params, ids, top_k=TOP_K, held=None, yarn=None):
    """[B, S] token ids -> ([B, S, vocab] float32 logits, the routed
    layers' balance losses SUMMED)."""
    yarn = YARN if yarn is None else yarn
    p = params["params"]
    x = p["embed"]["embedding"][ids]
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    aux = 0.0
    for i in range(n_layers):
        # the module-level mla / routed_ffn / rotate are looked up at
        # trace time, so a planted fault reaches them
        x, l_aux = jax.checkpoint(
            lambda x, lp: layer(x, lp, top_k, held, yarn))(
            x, p["layer_%d" % i])
        aux = aux + l_aux
    return mm(rms(x, p["final_ln"]["scale"]), p["lm_head"]["kernel"]), aux


def logits_fn(params, ids, top_k=TOP_K, held=None, yarn=None):
    return forward(params, ids, top_k, held, yarn)[0]


def nll_sum(params, batch, top_k=TOP_K, held=None, yarn=None):
    """Sum of next-token negative log-likelihoods plus the batch's weight
    times ``ALPHA sum_l L_l``, so that sum / weight is the training loss
    with the balance loss taken per sequence of THIS batch."""
    tokens = batch["tokens"]
    logits, aux = forward(params, tokens[:, :-1], top_k, held, yarn)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    weight = tokens.shape[0] * (tokens.shape[1] - 1)
    return -jnp.sum(picked) + weight * ALPHA * aux


batch_weight = lm.batch_weight


def train_check(nll_sum_fn, weight_fn, params, batch0, batch1, devices):
    """``reference/lm.py:train_check`` one sequence at a time on the first
    device: the NLL is a sum over rows and the balance loss a mean over
    sequences, so the blocks add up whatever the replicas."""
    return lm.train_check(nll_sum_fn, weight_fn, params, batch0, batch1,
                          devices[:1], block_rows=1)
