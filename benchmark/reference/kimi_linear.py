"""Plain float32 reference of Kimi-Linear (kimi_linear family): the layer
equations of arXiv 2510.26692 and of the model's public ``config.json``,
written from the equations and not from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no kernel, no chunked delta rule, no sort, no grouped matmul,
no chunked head. KDA is the TOKEN-BY-TOKEN recurrence

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T,   o_t = S_t^T q_t

as a two-level ``lax.scan`` whose inner level (``SCAN_BLOCK`` tokens) is
under ``jax.checkpoint``: the backward pass then keeps one state per block
and recomputes inside it (8,192 stored states of 32 x 128 x 128 float32
would be 16 GB); that changes no value. Latent attention materialises its
scores, one checkpointed block of queries at a time. EVERY held expert is applied to
EVERY token and masked by the routing, so a pair the program dropped or
sent to the wrong expert shows as a wrong loss. Each layer runs under
``jax.checkpoint`` for the same reason of memory. Every matmul runs under
``default_matmul_precision("highest")`` (``reference/lm.py:train_check``).

The share. The program holds some of each layer's experts (``held``: by
default the first E of the router's outputs, E the size of the weight
stacks) and so does this reference: the router scores all its outputs,
chooses and normalises over all of them, and only held experts add to
the result. What absent experts would add is left out here as there.
:func:`routed_ffn` with every expert held is the uncut layer.

Departures from the published description, shared with the program:
``e_score_correction_bias`` stays at zero (its update rule is not in
``config.json``) and the loss is the NLL alone; the decay is
``-exp(A_log) softplus(W_f x + dt_bias)`` with the low-rank ``W_f`` and
output gate of rank = KDA's head width, and q, k are normalised as
``x / sqrt(sum x^2 + 1e-6)`` (the released implementation's, not
``config.json``'s).

The precision control is ``reference/olmoe.py``'s: under
:func:`computed_in` every matmul takes its operands rounded to a coarser
dtype.
"""
import math

import jax
import jax.numpy as jnp

from benchmark.reference import lm
from benchmark.reference.olmoe import (computed_in, einsum, expert,  # noqa: F401
                                       mm, rms)

TOP_K = 8                # num_experts_per_token
SCALING = 2.446          # routed_scaling_factor; moe_renormalize is true
L2_EPS = 1e-6
SCAN_BLOCK = 128         # tokens per checkpointed block of the recurrence
QUERY_BLOCK = 512        # queries per block of materialised scores


def swiglu(h, m):
    return mm(jax.nn.silu(mm(h, m["gate_proj"]["kernel"]))
              * mm(h, m["up_proj"]["kernel"]), m["down_proj"]["kernel"])


# ------------------------------------------------------------------- KDA

def causal_conv(x, w):
    """Depthwise, causal, per channel: x [B, S, C], w [K, C]."""
    taps = w.shape[0]
    padded = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    return sum(padded[:, j:j + x.shape[1]] * w[j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token. q, k, g [B, S, H, dk], v
    [B, S, H, dv], beta [B, S, H] -> (o [B, S, H, dv], final S
    [B, H, dk, dv])."""
    B, S, H, dk = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state             # Diag(alpha) S
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)       # S^T k
        state = state + (b_t[..., None] * k_t)[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -S % SCAN_BLOCK   # padding neither decays nor writes (g = b = 0)
    xs = tuple(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
               for t in (q, k, v, g, beta))
    xs = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (-1, SCAN_BLOCK) + t.shape[:1] + t.shape[2:]) for t in xs)
    state, o = jax.lax.scan(block, jnp.zeros((B, H, dk, v.shape[-1])), xs)
    o = o.reshape((-1,) + o.shape[2:])[:S]
    return jnp.moveaxis(o, 0, 1), state


def kda(x, a, eps, decay=True, write_strength=True):
    """One KDA mixer; ``decay`` / ``write_strength`` False plant the
    faults of ``tools/loss_limit_kimi_linear.py`` (alpha = 1, beta = 1).
    Each stage is under ``jax.checkpoint`` (its [S, 4096] float32
    intermediates, twenty of 134 MB at the cell's size, are recomputed in
    the backward pass and never live together); that changes no value."""
    H = a["A_log"].shape[0]
    heads = lambda t: t.reshape(t.shape[:-1] + (H, -1))  # noqa: E731

    @jax.checkpoint
    def branch(x, w_proj, w_conv, scale):
        t = heads(jax.nn.silu(causal_conv(mm(x, w_proj), w_conv)))
        if scale is None:
            return t
        return t / jnp.sqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True)
                            + L2_EPS) * scale

    @jax.checkpoint
    def log_decay(x, f_a, f_b, a_log, dt_bias):
        return -jnp.exp(a_log)[:, None] * heads(
            jax.nn.softplus(mm(mm(x, f_a), f_b) + dt_bias))

    @jax.checkpoint
    def gated_output(x, o, g_a, g_b, scale, w_o):
        gate = jax.nn.sigmoid(heads(mm(mm(x, g_a), g_b)))
        o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                         + eps) * scale * gate
        return mm(o.reshape(o.shape[:2] + (-1,)), w_o)

    d_k = a["q_proj"]["kernel"].shape[1] // H
    q = branch(x, a["q_proj"]["kernel"], a["q_conv"], d_k ** -0.5)
    k = branch(x, a["k_proj"]["kernel"], a["k_conv"], 1.0)
    v = branch(x, a["v_proj"]["kernel"], a["v_conv"], None)
    g = log_decay(x, a["f_a_proj"]["kernel"], a["f_b_proj"]["kernel"],
                  a["A_log"], a["dt_bias"])
    beta = jax.nn.sigmoid(mm(x, a["b_proj"]["kernel"]))
    if not decay:
        g = jnp.zeros_like(g)
    if not write_strength:
        beta = jnp.ones_like(beta)
    o, _ = delta_rule(q, k, v, g, beta)
    return gated_output(x, o, a["g_a_proj"]["kernel"], a["g_b_proj"]["kernel"],
                        a["o_norm"], a["o_proj"]["kernel"])


# ------------------------------------------------------------------- MLA

def mla(x, a):
    """NoPE latent attention, training form: scores over the 128 + 64
    features of a head (the 64 come straight from x and are shared by all
    heads; nothing is rotated), values of 128."""
    B, S, _ = x.shape
    rank = a["kv_a_norm"]["scale"].shape[0]
    v_dim = a["o_proj"]["kernel"].shape[0]
    kv_a = mm(x, a["kv_a_proj"]["kernel"])
    latent, k_pe = kv_a[..., :rank], kv_a[..., rank:]
    kv = mm(rms(latent, a["kv_a_norm"]["scale"]), a["kv_b_proj"]["kernel"])
    # the widths are q = H (nope + pe), kv = H nope + v_dim, v_dim = H v
    pe = k_pe.shape[-1]
    H = (a["q_proj"]["kernel"].shape[1] - (kv.shape[-1] - v_dim)) // pe
    q = mm(x, a["q_proj"]["kernel"]).reshape(B, S, H, -1)
    kv = kv.reshape(B, S, H, -1)
    nope = q.shape[-1] - pe
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, pe))], -1)
    @jax.checkpoint
    def attend(block):
        q_rows, rows = block
        logits = einsum("bqhd,bthd->bhqt", q_rows, k) / math.sqrt(q.shape[-1])
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

def routing(scores, bias, top_k):
    """weight [T, E_all] of sigmoid scores [T, E_all]: the top k of
    ``scores + bias`` get ``score / (sum of the chosen scores) x 2.446``,
    the others 0."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), axis=1)
    weight = scores * picked
    return weight / jnp.sum(weight, axis=-1, keepdims=True) * SCALING


def routed_ffn(h, m, top_k, held=None, shared=True):
    """One routed layer's output for h [T, d]: the held experts' part of
    the routed sum, plus the shared expert (``shared`` False leaves it
    out: the share test counts it once, a planted fault not at all)."""
    scores = jax.nn.sigmoid(mm(h, m["router"]))
    weight = routing(scores, m["e_score_correction_bias"], top_k)
    held = tuple(range(m["gate_proj"].shape[0])) if held is None else held
    def add_expert(out, e):  # one expert after another: compiled once
        w_gate, w_up, w_down, its_weight = e
        return out + its_weight[:, None] * expert(h, w_gate, w_up, w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["gate_proj"], m["up_proj"], m["down_proj"],
        weight.T[jnp.asarray(held)]))
    return out + swiglu(h, m["shared"]) if shared else out


# ------------------------------------------------------------------ model

def layer(x, lp, top_k, held, eps):
    h = rms(x, lp["RMSNorm_0"]["scale"])
    x = x + (kda(h, lp["kda"], eps) if "kda" in lp else mla(h, lp["mla"]))
    h = rms(x, lp["RMSNorm_1"]["scale"])
    if "mlp" in lp:                            # a leading dense layer
        return x + swiglu(h, lp["mlp"])
    y = routed_ffn(h.reshape(-1, h.shape[-1]), lp["moe"], top_k, held)
    return x + y.reshape(x.shape)


def logits_fn(params, ids, top_k=TOP_K, held=None, eps=1e-5):
    """[B, S] token ids -> [B, S, vocab] float32 logits; no position
    signal anywhere."""
    p = params["params"]
    x = p["embed"]["embedding"][ids]
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        # the module-level kda / mla / routed_ffn are looked up at trace
        # time, so a planted fault reaches them
        x = jax.checkpoint(
            lambda x, lp: layer(x, lp, top_k, held, eps))(x, p["layer_%d" % i])
    return mm(rms(x, p["final_ln"]["scale"]), p["lm_head"]["kernel"])


def nll_sum(params, batch, top_k=TOP_K, held=None):
    """Sum of next-token negative log-likelihoods: the loss is the NLL
    alone."""
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
