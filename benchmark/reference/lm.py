"""Plain float32 reference of the decoder-only language model (lm family).

Straight ``jax.numpy`` on the parameter VALUES the program initialised: no
flax module of the program, no kernel, no chunked head, no cache. It is the
architecture as the program states it (pre-LN GPT-2 style block, learned
positions, untied head, tanh GELU, LayerNorm eps 1e-6, embedding scaled by
sqrt(d)). Every matmul runs under ``default_matmul_precision("highest")``,
because a float32 matmul on a TPU is otherwise computed in bf16 passes.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LN_EPS = 1e-6
ADAM = {"lr": 1e-3, "eps": 1e-8}  # optax.adam(1e-3) defaults


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, mask):
    """Pre-LN transformer block; ``mask`` broadcastable to [B, H, Sq, Sk]
    (True = attend) or None."""
    a = p["MultiHeadAttention_0"]
    h = layer_norm(x, p["LayerNorm_0"])
    q = jnp.einsum("bsd,dhk->bshk", h, a["query"]["kernel"]) + a["query"]["bias"]
    k = jnp.einsum("bsd,dhk->bshk", h, a["key"]["kernel"]) + a["key"]["bias"]
    v = jnp.einsum("bsd,dhk->bshk", h, a["value"]["kernel"]) + a["value"]["bias"]
    logits = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", w, v)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, a["out"]["kernel"]) + a["out"]["bias"]
    h = layer_norm(x, p["LayerNorm_1"])
    h = gelu_tanh(h @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"])
    return x + h @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]


def logits_fn(params, ids):
    """[B, S] token ids -> [B, S, vocab] float32 logits."""
    p = params["params"]
    d = p["embed"]["embedding"].shape[1]
    seq = ids.shape[1]
    x = p["embed"]["embedding"][ids] * math.sqrt(d)
    x = x + p["pos_embed"]["embedding"][jnp.arange(seq)][None]
    mask = jnp.tril(jnp.ones((seq, seq), bool))[None, None]
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        x = block(x, p["layer_%d" % i], mask)
    x = layer_norm(x, p["final_ln"])
    return x @ p["lm_head"]["kernel"] + p["lm_head"]["bias"]


def nll_sum(params, batch):
    """Sum (not mean) of next-token negative log-likelihoods."""
    tokens = batch["tokens"]
    logits = logits_fn(params, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(picked)


def batch_weight(batch):
    """What the mean divides by: every target position."""
    t = np.asarray(batch["tokens"])
    return float(t.shape[0] * (t.shape[1] - 1))


def adam_first_step(p, g):
    """optax.adam's first update from zero moments: the bias-corrected
    moments are g and g*g exactly."""
    return p - ADAM["lr"] * g / (jnp.sqrt(g * g) + ADAM["eps"])


def train_check(nll_sum_fn, weight_fn, params, batch0, batch1, devices,
                block_rows=4):
    """(loss at step 0 on batch0, loss at step 1 on batch1) of one float32
    Adam(1e-3) step: gradients accumulated over blocks of ``block_rows``
    sequences per device, sharded over ``devices`` by annotation only."""
    mesh = Mesh(np.asarray(devices), ("d",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("d"))
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda a: jnp.asarray(a, jnp.float32))
    step = block_rows * len(devices)

    def blocks(batch):
        n = len(next(iter(batch.values())))
        if n % step:
            raise ValueError("batch of %d rows is not a multiple of %d"
                             % (n, step))
        for i in range(0, n, step):
            yield jax.device_put({k: np.asarray(v)[i:i + step]
                                  for k, v in batch.items()}, rows)

    with jax.default_matmul_precision("highest"):
        params = jax.device_put(f32(params), rep)
        vg = jax.jit(jax.value_and_grad(nll_sum_fn), out_shardings=rep)
        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                      donate_argnums=0)
        total0, grads = 0.0, None
        for blk in blocks(batch0):
            v, g = vg(params, blk)
            total0 += float(v)
            grads = g if grads is None else add(grads, g)
        w0 = weight_fn(batch0)
        new = jax.jit(lambda p, g: jax.tree_util.tree_map(
            lambda a, b: adam_first_step(a, b / w0), p, g),
            donate_argnums=1)(params, grads)
        fwd = jax.jit(nll_sum_fn)
        total1 = sum(float(fwd(new, blk)) for blk in blocks(batch1))
    return total0 / w0, total1 / weight_fn(batch1)


def decode_deficits(params, sequences, prompt_lens, pad_to):
    """For each (prompt + emitted tokens) sequence: the reference's full
    forward pass, and per emitted token how far its logit lies under the
    reference's maximum at that position (0 = the reference's own pick).
    Returns (max deficit over all emitted tokens, tokens checked)."""
    @jax.jit
    def deficits(params, ids, picked):
        logits = logits_fn(params, ids[None])[0]
        mine = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - mine

    worst, checked = 0.0, 0
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        for seq, plen in zip(sequences, prompt_lens):
            seq = np.asarray(seq, np.int32)
            ids = np.zeros(pad_to, np.int32)
            ids[:len(seq)] = seq
            picked = np.zeros(pad_to, np.int32)
            # position i predicts token i + 1; emitted tokens start at plen
            picked[plen - 1:len(seq) - 1] = seq[plen:]
            d = np.asarray(deficits(params, ids, picked))[plen - 1:len(seq) - 1]
            worst = max(worst, float(d.max()))
            checked += d.size
    return worst, checked
