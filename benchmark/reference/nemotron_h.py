"""Plain float32 reference of the Nemotron-H tower of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 (``model_type: nemotron_h``): the
layer equations of the model's public ``config.json``, written from the
equations and not from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no kernel, no chunked form of anything. The state-space
recurrence runs TOKEN BY TOKEN (a checkpointed scan over blocks of
``SCAN_BLOCK`` tokens: 8,192 states of 64 x 64 x 128 float32 would be 17 GB
for the backward pass to read); attention materialises its scores, one
checkpointed block of 512 queries after another; EVERY held expert is
applied to EVERY token and masked by the routing's weights; logits and
loss are taken ``ROW_BLOCK`` rows at a time. Each layer runs under
``jax.checkpoint`` for memory; that changes no value. Every matmul runs
under ``default_matmul_precision("highest")``
(``reference/lm.py:train_check``).

The equations (x the residual stream, N an RMSNorm with a learned weight,
eps 1e-5; no bias but the filter's; no scale on the embedding; S positions,
causal). EVERY layer is ONE sub-layer, ``x <- x + f_l(N_l(x))``, f by the
letter of ``hybrid_override_pattern``; after the last layer one RMSNorm,
then ``logits = W_head h`` (untied); loss = mean NLL.

- M, Mamba-2 (H heads of P, G groups of B and C of N; inner = H P):
  ``[z | xBC | dt] = W_in u`` (inner | inner + 2 G N | H);
  ``xBC <- silu(conv(xBC) + b)``, a depthwise causal filter of 4 taps a
  channel, zeros before the start; xBC split into x [H, P], B [G, N],
  C [G, N], head h reading group ``h // (H / G)``;
  ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``S_0 = 0``;
  ``y_t = S_t C_t + D x_t``; ``y <- N_g(y * silu(z))``: the gate FIRST, the
  RMS statistic over each group's inner / G features, one learned weight
  of inner; ``W_out y``.
- ``*``, attention: ``q = W_q h`` in 32 heads of 128, ``k``, ``v`` in 2;
  NO rotation, no position signal, no QK-norm; query head i reads K/V head
  ``i // 16``; ``softmax(q k^T / sqrt 128) v`` over the keys a query sees;
  ``W_o`` on the heads side by side.
- E, routed feed-forward: ``s = sigmoid(W_r h)`` over ALL the router's
  outputs; chosen = top 6 of ``s + b`` (b only chooses); gates ``s_e /
  sum of the chosen s x 2.5``; ``y = sum over chosen and held e of g_e
  W_d,e relu(W_u,e h)^2 + W_d,s relu(W_u,s h)^2``: two matrices an expert,
  no gate, the shared expert at a width of its own and added in full.

The share. The program holds some of each layer's experts (``held``: by
default the first E of the router's outputs, E the size of the weight
stacks) and so does this reference: the router scores and chooses over all
its outputs and renormalises over the chosen, and only held experts add to
the routed sum. :func:`routed_ffn` with every expert held is the uncut
layer.

Assumed, the catalog's row being silent (listed in the configuration's
file): the inner width from the heads (``mamba_num_heads x
mamba_head_dim``, not ``expand`` x hidden), the gate before the grouped
norm, no rotation in attention; ``b`` stays at its initial zero.
``rescale_prenorm_residual`` is an initialisation rule and touches no
equation.

The precision control is ``reference/olmoe.py``'s: under
:func:`computed_in` every matmul takes its operands rounded to a coarser
dtype, and so do the recurrence's ``dt x``, B and C (its state and decays
stay float32, as the program's do).
"""
import math

import jax
import jax.numpy as jnp

from benchmark.reference import lm
# (Kimi-Linear's reference wrote the depthwise causal convolution with zeros
# before the start as K shifted products; it knows no model's constant)
from benchmark.reference.kimi_linear import causal_conv as short_conv
from benchmark.reference.olmoe import (computed_in, einsum, mm,  # noqa: F401
                                       operand)

RMS_EPS = 1e-5           # layer_norm_epsilon
TOP_K = 6                # num_experts_per_tok
SCALING = 2.5            # routed_scaling_factor; norm_topk_prob is true
N_GROUPS = 8             # n_groups
SCAN_BLOCK = 128         # tokens per checkpointed block of the recurrence
QUERY_BLOCK = 512        # queries per block of materialised scores
ROW_BLOCK = 1024         # rows per block of materialised logits


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


# ---------------------------------------------------------------- Mamba-2

def recurrence(x, dt, a, b, c, d, group_of=lambda h, per_group: h // per_group):
    """The recurrence, token by token. x [B, S, H, P], dt [B, S, H]
    (positive), a [H] (negative), b / c [B, S, G, N], d [H] or None -> y
    [B, S, H, P]. ``group_of`` and ``d`` None plant faults."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    groups = jnp.asarray([group_of(h, H // G) for h in range(H)])
    xdt = operand(x * dt[..., None])
    b, c = operand(b)[:, :, groups], operand(c)[:, :, groups]   # [B,S,H,N]

    def token(state, t):
        xdt_t, dt_t, b_t, c_t = t
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + xdt_t[..., None] * b_t[..., None, :]
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(token, state, ts)

    pad = -S % SCAN_BLOCK   # padding neither decays nor writes (dt = 0)
    ts = tuple(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
               for t in (xdt, dt, b, c))
    ts = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (-1, SCAN_BLOCK) + t.shape[:1] + t.shape[2:]) for t in ts)
    _, y = jax.lax.scan(block, jnp.zeros((B, H, P, N)), ts)
    y = jnp.moveaxis(y.reshape((-1,) + y.shape[2:])[:S], 0, 1)
    return y if d is None else y + d[:, None] * x


def mamba(h, m, n_groups=N_GROUPS, dt_bias=True, skip=True, decay_sign=-1.0,
          group_of=None, gate_first=True, conv_bias=True):
    """One Mamba-2 mixer on the normed input. Every keyword but
    ``n_groups`` plants a fault of ``tools/loss_limit_nemotron_h.py``."""
    B, S, _ = h.shape
    inner, H = m["norm"].shape[0], m["A_log"].shape[0]
    gn = (m["conv"].shape[1] - inner) // 2
    z, xbc, dt = jnp.split(mm(h, m["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * gn], axis=-1)
    xbc = short_conv(xbc, m["conv"])
    xbc = jax.nn.silu(xbc + m["conv_bias"] if conv_bias else xbc)
    x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"] if dt_bias else dt)
    y = recurrence(
        x.reshape(B, S, H, -1), dt, decay_sign * jnp.exp(m["A_log"]),
        b.reshape(B, S, n_groups, -1), c.reshape(B, S, n_groups, -1),
        m["D"] if skip else None,
        **({} if group_of is None else {"group_of": group_of}))
    y = y.reshape(B, S, inner)

    def group_norm(t):
        t = t.reshape(B, S, n_groups, -1)
        t = t / jnp.sqrt(jnp.mean(jnp.square(t), axis=-1, keepdims=True)
                         + RMS_EPS)
        return t.reshape(B, S, inner) * m["norm"]
    y = group_norm(y * jax.nn.silu(z)) if gate_first \
        else group_norm(y) * jax.nn.silu(z)
    return mm(y, m["out_proj"]["kernel"])


# -------------------------------------------------------------- attention

def attention(h, a, kv_head_of=lambda i, group: i // group):
    """The attention sub-layer on the normed input: no rotation, no norm
    of q or k. Another ``kv_head_of`` plants a fault."""
    B, S, _ = h.shape
    q = einsum("bsd,dhk->bshk", h, a["query"]["kernel"])
    k = einsum("bsd,dhk->bshk", h, a["key"]["kernel"])
    v = einsum("bsd,dhk->bshk", h, a["value"]["kernel"])
    H, D = q.shape[2:]
    # every query head's own K/V rows, by index (a reference may repeat)
    heads = jnp.asarray([kv_head_of(i, H // k.shape[2]) for i in range(H)])
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

def relu2_mlp(h, w_up, w_down, act=lambda u: jnp.square(jax.nn.relu(u))):
    """``W_d relu(W_u h)^2``; another ``act`` plants a fault."""
    return mm(act(mm(h, w_up)), w_down)


def routing(scores, bias, top_k, renormalize=True, scaling=SCALING):
    """weight [T, E_all] of sigmoid scores [T, E_all]: the top k of
    ``scores + bias`` get ``score / (sum of the chosen scores) x 2.5``, the
    others 0. ``renormalize`` False and another ``scaling`` plant faults."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = scores * jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), axis=1)
    if renormalize:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked * scaling


def routed_ffn(h, m, top_k, held=None, shared=True):
    """One routed layer's output for h [T, d]: the held experts' part of
    the routed sum, plus the shared expert (``shared`` False leaves it out:
    the share test counts it once)."""
    scores = jax.nn.sigmoid(mm(h, m["router"]))
    weight = routing(scores, m["e_score_correction_bias"], top_k)
    held = tuple(range(m["up_proj"].shape[0])) if held is None else held

    def add_expert(out, e):  # one expert after another: compiled once
        w_up, w_down, its_weight = e
        return out + its_weight[:, None] * jax.checkpoint(relu2_mlp)(
            h, w_up, w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["up_proj"], m["down_proj"], weight.T[jnp.asarray(held)]))
    if not shared:
        return out
    return out + relu2_mlp(h, m["shared"]["up_proj"]["kernel"],
                           m["shared"]["down_proj"]["kernel"])


# ------------------------------------------------------------------ model

def layer(x, lp, top_k, held, n_groups):
    """ONE sub-layer behind ONE norm, by what the layer holds."""
    h = rms(x, lp["RMSNorm_0"]["scale"])
    if "mamba" in lp:
        return x + mamba(h, lp["mamba"], n_groups)
    if "moe" in lp:
        y = routed_ffn(h.reshape(-1, h.shape[-1]), lp["moe"], top_k, held)
        return x + y.reshape(x.shape)
    return x + attention(h, lp["MultiHeadAttention_0"])


def hidden(params, ids, top_k=TOP_K, held=None, n_groups=N_GROUPS):
    """[B, S] token ids -> the final norm's output [B, S, d]."""
    p = params["params"]
    x = p["embed"]["embedding"][ids]          # no scale, no position table
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        # the module-level mamba / attention / routing / routed_ffn are
        # looked up at trace time, so a planted fault reaches them
        x = jax.checkpoint(lambda x, lp: layer(x, lp, top_k, held, n_groups))(
            x, p["layer_%d" % i])
    return rms(x, p["final_ln"]["scale"])


def logits_fn(params, ids, top_k=TOP_K, held=None, n_groups=N_GROUPS):
    """[B, S] token ids -> [B, S, vocab] float32 logits (untied head)."""
    return mm(hidden(params, ids, top_k, held, n_groups),
              params["params"]["lm_head"]["kernel"])


def nll_sum(params, batch, top_k=TOP_K, held=None, n_groups=N_GROUPS):
    """Sum of next-token negative log-likelihoods, the logits made
    ``ROW_BLOCK`` rows at a time: sum / weight is the training loss."""
    tokens = batch["tokens"]
    h = hidden(params, tokens[:, :-1], top_k, held, n_groups)
    h, targets = h.reshape(-1, h.shape[-1]), tokens[:, 1:].reshape(-1)
    w_head = params["params"]["lm_head"]["kernel"]

    @jax.checkpoint
    def rows_nll(block):
        rows, picked = block
        logp = jax.nn.log_softmax(mm(rows, w_head), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, picked[:, None], axis=-1))

    step = ROW_BLOCK if h.shape[0] % ROW_BLOCK == 0 else h.shape[0]
    return jnp.sum(jax.lax.map(rows_nll, (
        h.reshape(-1, step, h.shape[-1]), targets.reshape(-1, step))))


batch_weight = lm.batch_weight


def train_check(nll_sum_fn, weight_fn, params, batch0, batch1, devices):
    """``reference/lm.py:train_check`` one sequence at a time on the first
    device: the NLL is a sum over rows, so the blocks add up whatever the
    replicas."""
    return lm.train_check(nll_sum_fn, weight_fn, params, batch0, batch1,
                          devices[:1], block_rows=1)
