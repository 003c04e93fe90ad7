"""Plain float32 reference of Trinity-Mini (arcee-ai, ``model_type: afmoe``,
26B-A3B): the layer equations of the model's public ``config.json`` and of
the family's released implementation, written from the equations and not
from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no kernel, no tile table, no chunked head. Attention
materialises its scores under a boolean mask ``[queries, S]`` written out
from the two inequalities below, one checkpointed block of ``QUERY_BLOCK``
queries after another under ``lax.map``; EVERY held expert is applied to
EVERY token, one after another, and weighed by the routing. Each layer runs
under ``jax.checkpoint`` for memory; that changes no value. Every matmul
runs under ``default_matmul_precision("highest")``
(``reference/lm.py:train_check``).

The equations (h the residual stream [B, S, d], d = 2,048; ``N`` an RMSNorm
with a learned scale, eps 1e-5; no projection has a bias):

- ``h_0 = E[ids] * sqrt(d)`` (``mup_enabled``);
- ``u = N_1(h)``; ``q = N_q(u W_q)``, ``k = N_k(u W_k)`` (an RMSNorm over
  each head's D features, one learned weight of D for q and one for k),
  ``v = u W_v``, ``g = u W_g`` (``W_q``, ``W_g``: d -> H x D; ``W_k``,
  ``W_v``: d -> H / G heads of D);
- a ``sliding_attention`` layer rotates q and k over all D features
  (``rotate_half`` pairing, feature i with i + D / 2, positions 0..S-1,
  theta 10,000) and lets query i see key j iff ``j <= i`` and ``i - j < W``,
  W = 2,048 (the query's own position counted); a ``full_attention`` layer
  rotates NOTHING (no position signal at all) and sees every ``j <= i``;
- ``o = softmax(q k^T / sqrt D) v``, query head n on K/V head ``n // G``;
  **``a = (o * sigmoid(g)) W_o``**: the gate, head by head and feature by
  feature, read from the layer's NORMED input; ``h' = h + N_2(a)``;
- ``m = N_3(h')``. A dense layer: ``f = W_down(silu(W_gate m) * W_up m)``.
  A routed layer: ``s = sigmoid(m W_r)`` over ALL the router's outputs;
  chosen = the k largest of ``s + b`` (b the choice-only bias); ``w_e =
  2.826 s_e / (sum of the chosen s + 1e-20)`` (``route_norm``,
  ``route_scale``); ``f = shared(m) + sum over chosen and held e of w_e
  expert_e(m)``, shared and routed experts SwiGLU; ``h'' = h' + N_4(f)``;
- after the last block one RMSNorm and the untied head; loss: mean NLL (no
  router loss: ``load_balance_coeff`` is the bias's update rate in the
  training recipe, not a loss term).

Which layers. ``layer_types`` is three ``sliding_attention`` to one
``full_attention``: published layer l is global iff ``l % 4 == 3``
(:func:`layout`). The tree's ``layer_i`` is published layer ``first_layer +
i`` (:data:`FIRST_LAYER`: the cell builds published layers 1-5, the second
dense layer and the first four routed ones); a layer is dense where its
tree holds ``mlp``, routed where it holds ``moe``.

The share. The program holds some of each routed layer's experts (``held``:
by default the first E of the router's outputs, E the size of the weight
stacks) and so does this reference: the router scores and chooses over all
its outputs and renormalises over the chosen, and only held experts add to
the result; the shared expert is added in full. :func:`routed_ffn` with
every expert held is the uncut layer.

Assumed, the catalog's row being silent (listed in the configuration's
file): the gate, the per-head QK-norm, the four norms a block and the
global layers' missing rotation (the released implementation's, no key of
config.json); a window that counts the query itself; the rotate-half
pairing over all 128 features; the choice bias held at its initial zero.

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

RMS_EPS = 1e-5           # rms_norm_eps
TOP_K = 8                # num_experts_per_tok
ROPE_THETA = 10000.0     # rope_theta
WINDOW = 2048            # sliding_window
ROUTE_SCALE = 2.826      # route_scale
GLOBAL_EVERY = 4         # global_attn_every_n_layers: layer l iff l % 4 == 3
FIRST_LAYER = 1          # the tree's layer_0 is published layer 1
QUERY_BLOCK = 512        # queries per block of materialised scores


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


def swiglu(x, p):
    return mm(jax.nn.silu(mm(x, p["gate_proj"]["kernel"]))
              * mm(x, p["up_proj"]["kernel"]), p["down_proj"]["kernel"])


def embed(table, ids, scaled=True):
    """``E[ids] sqrt(d)``; ``scaled`` False plants a fault."""
    x = table[ids]
    return x * math.sqrt(table.shape[1]) if scaled else x


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


def attention(u, a, rotated, window, gate_input=None, gated=True,
              qk_norm=True):
    """The attention sub-layer on the normed input u: ``rotated`` the
    layer's kind (a window layer rotates), ``window`` None or its window.
    ``gate_input`` (another operand for the gate), ``gated`` False and
    ``qk_norm`` False plant faults."""
    B, S, _ = u.shape
    q = einsum("bsd,dhk->bshk", u, a["query"]["kernel"])
    k = einsum("bsd,dhk->bshk", u, a["key"]["kernel"])
    v = einsum("bsd,dhk->bshk", u, a["value"]["kernel"])
    if qk_norm:     # per head: over the last axis, one weight of D
        q, k = rms(q, a["q_norm"]["scale"]), rms(k, a["k_norm"]["scale"])
    H, D = q.shape[2:]
    group = H // k.shape[2]
    if rotated:
        q, k = rope(q), rope(k)
    # every query head's own K/V rows, by index (a reference may repeat)
    heads = jnp.arange(H) // group
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
    if gated:
        g = einsum("bsd,dhk->bshk", u if gate_input is None else gate_input,
                   a["gate"]["kernel"])
        o = o * jax.nn.sigmoid(g)
    return einsum("bqhk,hkd->bqd", o, a["out"]["kernel"])


# -------------------------------------------------------------------- MoE

def expert(m, w_gate, w_up, w_down):
    """One SwiGLU expert on m [T, d] (its [T, f] activations recomputed in
    the backward pass)."""
    @jax.checkpoint
    def swiglu_of(m, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)
    return swiglu_of(m, w_gate, w_up, w_down)


def routing(scores, bias, top_k, renormalize=True, scale=ROUTE_SCALE):
    """weight [T, E_all] of sigmoid scores [T, E_all]: the top k of
    ``scores + bias`` get ``scale x score / (sum of the chosen scores +
    1e-20)``, the others 0. ``renormalize`` False and another ``scale``
    plant faults."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    weight = scores * jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), axis=1)
    if renormalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return weight * scale


def routed_ffn(m_in, m, top_k, held=None, shared=True):
    """One routed layer's output for m_in [T, d]: the held experts' part of
    the routed sum, plus the shared expert (``shared`` False leaves it out:
    the share test counts it once, a planted fault not at all)."""
    scores = jax.nn.sigmoid(mm(m_in, m["router"]))
    weight = routing(scores, m["e_score_correction_bias"], top_k)
    held = tuple(range(m["gate_proj"].shape[0])) if held is None else held

    def add_expert(out, e):  # one expert after another: compiled once
        w_gate, w_up, w_down, its_weight = e
        return out + its_weight[:, None] * expert(m_in, w_gate, w_up,
                                                  w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m_in), (
        m["gate_proj"], m["up_proj"], m["down_proj"],
        weight.T[jnp.asarray(held)]))
    return out + swiglu(m_in, m["shared"]) if shared else out


# ------------------------------------------------------------------ model

def layout(index):
    """(rotated, windowed) of PUBLISHED layer ``index``: a
    ``sliding_attention`` layer is both, a ``full_attention`` layer
    (``index % 4 == 3``) neither."""
    local = index % GLOBAL_EVERY != GLOBAL_EVERY - 1
    return local, local


def layer(x, lp, index, top_k, held, window, gate_reads="normed",
          out_norms=("attn", "mlp")):
    """``gate_reads`` "residual" (the gate fed the un-normed stream) and an
    ``out_norms`` without one of the two output norms plant faults."""
    rotated, windowed = layout(index)
    u = rms(x, lp["RMSNorm_0"]["scale"])
    a = attention(u, lp["MultiHeadAttention_0"], rotated,
                  window if windowed else None,
                  gate_input=None if gate_reads == "normed" else x)
    x = x + (rms(a, lp["attn_out_norm"]["scale"]) if "attn" in out_norms
             else a)
    m_in = rms(x, lp["RMSNorm_1"]["scale"])
    if "mlp" in lp:                            # a leading dense layer
        f = swiglu(m_in, lp["mlp"])
    else:
        d = x.shape[-1]
        f = routed_ffn(m_in.reshape(-1, d), lp["moe"], top_k,
                       held).reshape(x.shape)
    return x + (rms(f, lp["mlp_out_norm"]["scale"]) if "mlp" in out_norms
                else f)


def logits_fn(params, ids, top_k=TOP_K, held=None, window=WINDOW,
              first_layer=FIRST_LAYER):
    """[B, S] token ids -> [B, S, vocab] float32 logits."""
    p = params["params"]
    x = embed(p["embed"]["embedding"], ids)
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        # the module-level layout / attention / routing / routed_ffn / embed
        # are looked up at trace time, so a planted fault reaches them
        x = jax.checkpoint(
            lambda x, lp, i=i: layer(x, lp, first_layer + i, top_k, held,
                                     window))(x, p["layer_%d" % i])
    return mm(rms(x, p["final_ln"]["scale"]), p["lm_head"]["kernel"])


def nll_sum(params, batch, top_k=TOP_K, held=None, window=WINDOW,
            first_layer=FIRST_LAYER):
    """Sum of next-token negative log-likelihoods: sum / weight is the
    training loss."""
    tokens = batch["tokens"]
    logits = logits_fn(params, tokens[:, :-1], top_k, held, window,
                       first_layer)
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
