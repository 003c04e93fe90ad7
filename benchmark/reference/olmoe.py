"""Plain float32 reference of OLMoE (olmoe family): HF ``modeling_olmoe``
as published (arXiv 2409.02060), written from the equations and not from
the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no sort, no grouped matmul, no chunked head. EVERY expert is
applied to EVERY token and the result masked by the routing, so a pair
the program dropped or sent to the wrong expert shows as a wrong loss.
Each expert runs under ``jax.checkpoint`` (its [T, f] activations are
recomputed in the backward pass: at the published widths 64 experts x
three [8192, 1024] float32 arrays would otherwise be 6.4 GB beside the
reference's 7.5 GB of weights, gradients and the stepped weights); that
changes no value. Every matmul runs under
``default_matmul_precision("highest")``, because a float32 matmul on a
TPU is otherwise computed in bf16 passes.

One departure from HF, shared with the program: HF concatenates all
layers' router outputs before taking ``f`` and ``P`` of the load-balance
loss; here each is taken per layer and the layers' losses averaged (the
same thing at depth 1, the benchmark cell's).

The precision control (``tools/loss_limit.py``): under
:func:`computed_in` every matmul takes its two operands rounded to a
coarser dtype and still accumulates in float32, which is what a step
computed in that dtype does. Outside it :func:`operand` is the identity.
"""
import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import lm

RMS_EPS = 1e-5
ROPE_THETA = 10000.0
TOP_K = 8                 # num_experts_per_tok
LB_COEF, Z_COEF = 0.01, 0.001


_ROUND_TO = [None]


def operand(x):
    """A matmul's operand: ``x`` itself, or rounded under
    :func:`computed_in`."""
    dtype = _ROUND_TO[0]
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


@contextlib.contextmanager
def computed_in(dtype):
    """Trace the reference with every matmul operand rounded to ``dtype``
    (float32 accumulation): the control that a limit on the loss has to
    refuse for the next precision under the configuration's."""
    _ROUND_TO[0] = dtype
    try:
        yield
    finally:
        _ROUND_TO[0] = None


def mm(a, b):
    return operand(a) @ operand(b)


def einsum(spec, a, b):
    return jnp.einsum(spec, operand(a), operand(b))


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x):
    """x [B, S, H, D]; position = index in the sequence."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / ROPE_THETA ** (jnp.arange(0, dim, 2) / dim)
    ang = jnp.arange(seq)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def attention(x, a):
    h_q = einsum("bsd,dhk->bshk", x, a["query"]["kernel"])
    h_k = einsum("bsd,dhk->bshk", x, a["key"]["kernel"])
    v = einsum("bsd,dhk->bshk", x, a["value"]["kernel"])
    shape = h_q.shape                      # QK-norm over all H*D features
    q = rms(h_q.reshape(shape[:2] + (-1,)), a["q_norm"]["scale"]).reshape(shape)
    k = rms(h_k.reshape(shape[:2] + (-1,)), a["k_norm"]["scale"]).reshape(shape)
    q, k = rope(q), rope(k)
    logits = einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(shape[-1])
    causal = jnp.tril(jnp.ones((shape[1], shape[1]), bool))[None, None]
    w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = einsum("bhqt,bthk->bqhk", w, v)
    return einsum("bqhk,hkd->bqd", o, a["out"]["kernel"])


@jax.checkpoint
def expert(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def routing(p, top_k):
    """(weight [T, E], chosen [T, k]) of router probabilities p [T, E]:
    weight[t, e] = p[t, e] where e is among t's top k (no renormalising),
    else 0."""
    gate, chosen = jax.lax.top_k(p, top_k)
    weight = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1]) * gate[..., None],
                     axis=1)
    return weight, chosen


def routed_ffn(h, m, top_k):
    """(output, L_lb, L_z) of one layer; h [T, d]."""
    logits = mm(h, m["router"])                            # [T, E]
    p = jax.nn.softmax(logits, axis=-1)
    n_experts = p.shape[-1]
    weight, chosen = routing(p, top_k)
    def add_expert(out, e):  # one expert after another: compiled once
        w_gate, w_up, w_down, its_weight = e
        return out + its_weight[:, None] * expert(h, w_gate, w_up, w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["gate_proj"], m["up_proj"], m["down_proj"], weight.T))
    f = jnp.sum(jax.nn.one_hot(chosen, n_experts), axis=(0, 1)) / h.shape[0]
    l_lb = n_experts * jnp.sum(f * jnp.mean(p, axis=0))
    l_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return out, l_lb, l_z


def forward(params, ids, top_k=TOP_K):
    """[B, S] token ids -> ([B, S, vocab] float32 logits, mean over layers
    of L_lb, of L_z)."""
    p = params["params"]
    x = p["embed"]["embedding"][ids]          # no scale, no position table
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    l_lb = l_z = 0.0
    for i in range(n_layers):
        lp = p["layer_%d" % i]
        h = rms(x, lp["RMSNorm_0"]["scale"])
        x = x + attention(h, lp["MultiHeadAttention_0"])
        h = rms(x, lp["RMSNorm_1"]["scale"])
        y, lb, z = routed_ffn(h.reshape(-1, h.shape[-1]), lp["moe"], top_k)
        x = x + y.reshape(x.shape)
        l_lb, l_z = l_lb + lb / n_layers, l_z + z / n_layers
    logits = mm(rms(x, p["final_ln"]["scale"]), p["lm_head"]["kernel"])
    return logits, l_lb, l_z


def logits_fn(params, ids, top_k=TOP_K):
    return forward(params, ids, top_k)[0]


def nll_sum(params, batch, top_k=TOP_K):
    """Sum of next-token negative log-likelihoods plus the batch's weight
    times the router losses, so that sum / weight is the training loss
    ``mean NLL + 0.01 L_lb + 0.001 L_z`` with both router losses taken
    over the rows of THIS batch."""
    tokens = batch["tokens"]
    logits, l_lb, l_z = forward(params, tokens[:, :-1], top_k)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    weight = tokens.shape[0] * (tokens.shape[1] - 1)
    return -jnp.sum(picked) + weight * (LB_COEF * l_lb + Z_COEF * l_z)


batch_weight = lm.batch_weight


def train_check(nll_sum_fn, weight_fn, params, batch0, batch1, devices):
    """``reference/lm.py:train_check`` with one block per data-parallel
    replica: the router losses are not sums over rows, so a block must be
    exactly the rows one replica's loss sees. On one chip that is the
    whole batch; on n replicas the program's loss is the mean of n such
    losses, and so is this (the blocks run one after another on the first
    device)."""
    rows = len(np.asarray(batch0["tokens"]))
    return lm.train_check(nll_sum_fn, weight_fn, params, batch0, batch1,
                          devices[:1], block_rows=rows // len(devices))
