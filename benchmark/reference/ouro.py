"""Plain float32 reference of Ouro-2.6B (ouro family): the equations of the
model's public ``config.json`` (``model_type: ouro``) and of arXiv
2510.25741 ("Scaling Latent Reasoning via Looped Language Models"), written
from the equations and not from the program.

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module, no scan over the passes, no kernel, no chunked head. The
passes and the layers are an unrolled Python loop; attention materialises
its scores, one checkpointed block of 512 queries after another under
``lax.map``; every pass's head materialises its [S, vocab] logits. Each
block application and each pass's head runs under ``jax.checkpoint`` for
memory (24 float32 applications at 4,096 tokens do not fit otherwise);
that changes no value. Every matmul runs under
``default_matmul_precision("highest")`` (``reference/lm.py:train_check``).

The equations (RMSNorm ``N(x) = x / sqrt(mean x^2 + 1e-6) * w``, no bias
on a projection, S positions, causal; the points marked + are the paper's
or the family's convention without a key in the catalog's row, and are
listed under ``assumed`` in the configuration's file):

- ``x^0 = E[ids]``, no sqrt(d) scale (+).
- pass ``t = 1 .. T`` (``total_ut_steps`` = 4), the SAME parameters in
  every pass: ``y = x^{t-1}``; for each layer l
  ``y <- y + N2_l(Attn_l(N1_l(y)))``, ``y <- y + N4_l(SwiGLU_l(N3_l(y)))``
  (N2, N4 norm the sub-layers' OUTPUTS: sandwich normalisation (+)); then
  ``x^t = N_f(y)``, and the NORMED state is the next pass's input (+).
- ``Attn``: q, k, v in 16 heads of 128 (as many K/V heads), RoPE theta 1e6
  over all 128 features (``rotate_half`` pairing, feature i with i + 64,
  positions 0 .. S-1), ``softmax(q k^T / sqrt 128) v`` over the keys a
  query sees, ``W_o`` on the heads side by side; no QK-norm (+: no bias).
- ``SwiGLU(h) = W_d(silu(W_g h) * W_u h)``.
- after every pass: ``z^t = W_head x^t`` (one untied head without a bias),
  ``l^t_i = NLL(z^t_i, target_i)`` and the exit gate
  ``lambda^t_i = sigmoid(w_g . x^t_i + b_g)`` (+: the bias).
- exit distribution per token: ``S^0 = 1``, ``p^t = lambda^t S^{t-1}`` and
  ``S^t = S^{t-1} (1 - lambda^t)`` for t < T, ``p^T = S^{T-1}``.
- loss (the paper's stage-I objective): ``mean_i [sum_t p^t_i l^t_i -
  beta H_i]``, ``H_i = -sum_t p^t_i log p^t_i``, beta 0.05 (+), the mean
  over every target position. The gate's gradient flows through p.
  ``early_exit_threshold`` 1: inference runs all four passes.

:func:`states` takes ``stacks``, one set of layers and final norm a PASS:
the untied twin ``tests/test_ouro.py`` ties the loop to (T copies set
equal give per-copy gradients whose sum is the looped model's).

The precision control is ``reference/olmoe.py``'s: under
:func:`computed_in` every matmul takes its operands rounded to a coarser
dtype.
"""
import math

import jax
import jax.numpy as jnp
from jax.scipy.special import xlogy

from benchmark.reference import lm
from benchmark.reference.kimi_linear import swiglu
from benchmark.reference.olmoe import (computed_in, einsum, mm,  # noqa: F401
                                       rotate_half)

T = 4                    # total_ut_steps
BETA = 0.05              # the entropy term's weight (+)
RMS_EPS = 1e-6           # rms_norm_eps
ROPE_THETA = 1e6         # rope_theta
QUERY_BLOCK = 512        # queries per block of materialised scores


def rms(x, w):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w


def rope(x):
    """x [B, S, H, D] turned by its position (the index in the sequence),
    feature i paired with i + D/2."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / ROPE_THETA ** (jnp.arange(0, dim, 2) / dim)
    ang = jnp.arange(seq)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def attention(h, a):
    """Multi-head attention on the normed input."""
    B, S, _ = h.shape
    q = rope(einsum("bsd,dhk->bshk", h, a["query"]["kernel"]))
    k = rope(einsum("bsd,dhk->bshk", h, a["key"]["kernel"]))
    v = einsum("bsd,dhk->bshk", h, a["value"]["kernel"])
    H, D = q.shape[2:]

    @jax.checkpoint
    def attend(block):
        q_rows, rows = block
        seen = rows[:, None] >= jnp.arange(S)[None, :]
        logits = einsum("bqhd,bthd->bhqt", q_rows, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), -1)
        return einsum("bhqt,bthd->bqhd", p, v)

    # one block of queries after another (``lax.map``: the compiler may
    # not run them side by side), [H, rows, S] scores live at a time
    step = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    o = jax.lax.map(attend, (
        jnp.moveaxis(q.reshape(B, S // step, step, H, D), 1, 0),
        jnp.arange(S).reshape(S // step, step)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, D)
    return einsum("bqhk,hkd->bqd", o, a["out"]["kernel"])


def layer(y, lp, output_norms=True):
    """One block application. ``output_norms`` False plants a fault of
    ``tools/loss_limit_ouro.py``: N2 and N4 left out."""
    a = attention(rms(y, lp["RMSNorm_0"]["scale"]),
                  lp["MultiHeadAttention_0"])
    y = y + (rms(a, lp["attn_out_norm"]["scale"]) if output_norms else a)
    m = swiglu(rms(y, lp["RMSNorm_1"]["scale"]), lp["mlp"])
    return y + (rms(m, lp["mlp_out_norm"]["scale"]) if output_norms else m)


def states(params, ids, stacks=None, norm_between=True):
    """[B, S] token ids -> the normed state after each pass, a list of
    arrays [B, S, d]. ``stacks`` None: each of the ``T`` passes runs
    ``params``' own layers and final norm (the looped model); else one
    {"layer_<l>", "final_ln"} a pass (the untied twin). ``norm_between``
    False plants a fault: the next pass starts from the un-normed ``y``.
    (The module-level ``T`` and ``layer`` are looked up at trace time, so
    a planted fault reaches them.)"""
    p = params["params"]
    stacks = [p] * T if stacks is None else stacks
    n_layers = sum(1 for k in stacks[0] if k.startswith("layer_"))
    x, out = p["embed"]["embedding"][ids], []
    for stack in stacks:
        y = x
        for i in range(n_layers):
            y = jax.checkpoint(lambda y, lp: layer(y, lp))(
                y, stack["layer_%d" % i])
        normed = rms(y, stack["final_ln"]["scale"])
        out.append(normed)
        x = normed if norm_between else y
    return out


@jax.checkpoint
def pass_nll(x, kernel, targets):
    """The head on one pass's state: [B, S] negative log-likelihoods."""
    logp = jax.nn.log_softmax(mm(x, kernel), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def exit_distribution(lambdas):
    """[p^1 .. p^T] from the gates [lambda^1 .. lambda^{T-1}] (the last
    pass exits with all that is left)."""
    left, out = 1.0, []
    for lam in lambdas:
        out.append(lam * left)
        left = left * (1.0 - lam)
    return out + [left]


def exit_weights(xs, p):
    """The exit distribution of the passes' states ``xs``. (Planted faults
    replace it: uniform weights, the last pass alone.)"""
    gate = p["exit_gate"]
    return exit_distribution([
        jax.nn.sigmoid(mm(x, gate["kernel"])[..., 0] + gate["bias"][0])
        for x in xs[:-1]])


def token_losses(params, batch, stacks=None):
    """[B, S]: ``sum_t p^t l^t - BETA H`` of every target position."""
    tokens = batch["tokens"]
    p = params["params"]
    xs = states(params, tokens[:, :-1], stacks)
    nll = [pass_nll(x, p["lm_head"]["kernel"], tokens[:, 1:]) for x in xs]
    mass = exit_weights(xs, p)
    entropy = -sum(xlogy(m, m) for m in mass)
    return sum(m * l for m, l in zip(mass, nll)) - BETA * entropy


def logits_fn(params, ids):
    """[B, S] token ids -> the LAST pass's [B, S, vocab] float32 logits."""
    return mm(states(params, ids)[-1], params["params"]["lm_head"]["kernel"])


def nll_sum(params, batch):
    """Sum over the target positions of the per-token loss: sum / weight
    is the training loss."""
    return jnp.sum(token_losses(params, batch))


batch_weight = lm.batch_weight


def train_check(nll_sum_fn, weight_fn, params, batch0, batch1, devices):
    """``reference/lm.py:train_check`` one sequence at a time on the first
    device: the loss is a sum over positions, so the blocks add up
    whatever the replicas."""
    return lm.train_check(nll_sum_fn, weight_fn, params, batch0, batch1,
                          devices[:1], block_rows=1)
