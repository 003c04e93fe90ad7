"""Plain float32 reference of SmallThinker-21BA3B-Instruct (smallthinker
family): the layer equations of the model's public ``config.json``
(``model_name: smallthinker_21b_instruct``), written from the equations and
not from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no kernel, no tile table, no chunked head. Attention
materialises its scores under a boolean mask ``[queries, S]`` written out
from the two inequalities below, one checkpointed block of ``QUERY_BLOCK``
queries after another under ``lax.map``; EVERY held expert is applied to
EVERY token, one after another, and weighed by the routing. Each layer
runs under ``jax.checkpoint`` for memory; that changes no value. Every
matmul runs under ``default_matmul_precision("highest")``
(``reference/lm.py:train_check``).

The equations (x the residual stream [B, S, d], no bias anywhere, no scale
on the embedding, RMSNorm eps 1e-6; layer l of the stack, numbered from 0):

- ``h = RMSNorm(x)``; **``r = h W_r``**: the router's logits are taken
  HERE, from the attention's normed input ("router placed before
  attention"), over ALL the router's outputs;
- ``q = h W_q`` in H heads of D, ``k = h W_k``, ``v = h W_v`` in H / G
  heads; where ``rope_layout[l]`` is 1, q and k are rotated over all D
  features (``rotate_half`` pairing, feature i with i + D / 2, positions
  0..S-1, theta 1.5e6); where it is 0 nothing is rotated and the layer has
  no position signal;
- key j is visible to query i iff ``j <= i`` and
  (``sliding_window_layout[l]`` is 0 or ``i - j < W``), W = 4,096: a window
  layer's query sees itself and the W - 1 keys before it; query head n
  reads K/V head ``n // G``; ``softmax(q k^T / sqrt D)`` over the visible
  keys, times v; ``x' = x + a W_o``;
- ``u = RMSNorm(x')``; chosen = the k largest of ``softmax(r)`` (the same k
  as of r), gates = those k probabilities renormalised to sum to one
  (= the softmax over the k chosen logits); ``y = sum over chosen and held
  e of g_e W_down^e (relu(W_gate^e u) * W_up^e u)``: the experts read the
  SECOND norm's output, the router read the first's; out = ``x' + y``;
- after the last block one RMSNorm and the untied head; loss: mean NLL (no
  router loss).

Both published layouts are ``[0, 1, 1, 1] x 13``: layer l is global and
unrotated iff ``l % 4 == 0`` (:data:`PERIOD`).

The share. The program holds some of each layer's experts (``held``: by
default the first E of the router's outputs, E the size of the weight
stacks) and so does this reference: the router scores and chooses over all
its outputs and renormalises over the chosen, and only held experts add to
the result. :func:`routed_ffn` with every expert held is the uncut layer.

Assumed, the catalog's row being silent (listed in the configuration's
file): a window that counts the query itself (``i - j < W``); the
rotate-half pairing over all 128 features; no QK-norm, no bias, no
embedding scale; no router loss.

The precision control is ``reference/olmoe.py``'s: under
:func:`computed_in` every matmul takes its operands rounded to a coarser
dtype.
"""
import math

import jax
import jax.numpy as jnp

from benchmark.reference import lm
from benchmark.reference.olmoe import (computed_in, einsum, mm,  # noqa: F401
                                       rotate_half)

RMS_EPS = 1e-6           # rms_norm_eps
TOP_K = 6                # moe_num_active_primary_experts
ROPE_THETA = 1.5e6       # rope_theta
WINDOW = 4096            # sliding_window_size
PERIOD = (0, 1, 1, 1)    # sliding_window_layout and rope_layout, x 13
QUERY_BLOCK = 512        # queries per block of materialised scores


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


# -------------------------------------------------------------- attention

def rope(x):
    """x [B, S, H, D] turned by its position (the index in the sequence),
    feature i paired with i + D/2."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / ROPE_THETA ** (jnp.arange(0, dim, 2) / dim)
    ang = jnp.arange(seq)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def visible(rows, seq, window):
    """[len(rows), seq] bool: may query ``rows[i]`` attend key j? ``window``
    None: every key up to its own position; else of those the ``window``
    latest, its own counted."""
    keys = jnp.arange(seq)[None, :]
    seen = keys <= rows[:, None]
    if window is not None:
        seen = seen & (rows[:, None] - keys < window)
    return seen


def attention(h, a, rotated, window, kv_head_of=lambda i, group: i // group):
    """The attention sub-layer on the normed input: ``rotated`` is the
    layer's ``rope_layout`` flag, ``window`` None or its window. Another
    ``kv_head_of`` plants a fault."""
    B, S, _ = h.shape
    q = einsum("bsd,dhk->bshk", h, a["query"]["kernel"])
    k = einsum("bsd,dhk->bshk", h, a["key"]["kernel"])
    v = einsum("bsd,dhk->bshk", h, a["value"]["kernel"])
    H, D = q.shape[2:]
    group = H // k.shape[2]
    if rotated:
        q, k = rope(q), rope(k)
    # every query head's own K/V rows, by index (a reference may repeat)
    heads = jnp.asarray([kv_head_of(i, group) for i in range(H)])
    k, v = k[:, :, heads], v[:, :, heads]

    @jax.checkpoint
    def attend(block):
        q_rows, rows = block
        seen = visible(rows, S, window)
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

def expert(u, w_gate, w_up, w_down, act=jax.nn.relu):
    """One ReGLU expert on u [T, d] (its [T, f] activations recomputed in
    the backward pass). Another ``act`` plants a fault."""
    @jax.checkpoint
    def reglu(u, w_gate, w_up, w_down):
        return mm(act(mm(u, w_gate)) * mm(u, w_up), w_down)
    return reglu(u, w_gate, w_up, w_down)


def routing(logits, top_k, renormalize=True):
    """weight [T, E_all] of the router's logits [T, E_all]: the k largest
    probabilities of their softmax, renormalised to sum to one, the others
    0."""
    p = jax.nn.softmax(logits, axis=-1)
    gate, chosen = jax.lax.top_k(p, top_k)
    if renormalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, p.shape[-1]) * gate[..., None],
                   axis=1)


def routed_ffn(u, logits, m, top_k, held=None):
    """One routed layer for u [T, d] under router logits [T, E_all]: the
    held experts' part of the routed sum."""
    weight = routing(logits, top_k)
    held = tuple(range(m["gate_proj"].shape[0])) if held is None else held

    def add_expert(out, e):  # one expert after another: compiled once
        w_gate, w_up, w_down, its_weight = e
        return out + its_weight[:, None] * expert(u, w_gate, w_up, w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        m["gate_proj"], m["up_proj"], m["down_proj"],
        weight.T[jnp.asarray(held)]))
    return out


# ------------------------------------------------------------------ model

def layout(index):
    """(rotated, windowed) of layer ``index``: both published layouts are
    the one period."""
    flag = bool(PERIOD[index % len(PERIOD)])
    return flag, flag


def layer(x, lp, index, top_k, held, window, router_reads="first"):
    """``router_reads`` "second" plants a fault: the router fed what the
    experts read."""
    rotated, windowed = layout(index)
    h = rms(x, lp["RMSNorm_0"]["scale"])
    x = x + attention(h, lp["MultiHeadAttention_0"], rotated,
                      window if windowed else None)
    u = rms(x, lp["RMSNorm_1"]["scale"])
    d = x.shape[-1]
    routed_from = h if router_reads == "first" else u
    y = routed_ffn(u.reshape(-1, d),
                   mm(routed_from.reshape(-1, d), lp["moe"]["router"]),
                   lp["moe"], top_k, held)
    return x + y.reshape(x.shape)


def logits_fn(params, ids, top_k=TOP_K, held=None, window=WINDOW):
    """[B, S] token ids -> [B, S, vocab] float32 logits."""
    p = params["params"]
    x = p["embed"]["embedding"][ids]          # no scale, no position table
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        # the module-level layout / attention / routing / routed_ffn / expert
        # are looked up at trace time, so a planted fault reaches them
        x = jax.checkpoint(
            lambda x, lp, i=i: layer(x, lp, i, top_k, held, window))(
            x, p["layer_%d" % i])
    return mm(rms(x, p["final_ln"]["scale"]), p["lm_head"]["kernel"])


def nll_sum(params, batch, top_k=TOP_K, held=None, window=WINDOW):
    """Sum of next-token negative log-likelihoods: sum / weight is the
    training loss."""
    tokens = batch["tokens"]
    logits = logits_fn(params, tokens[:, :-1], top_k, held, window)
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
