"""Shared model layers (attention, transformer blocks).

TPU-first building blocks for the model zoo: bfloat16-friendly, static
shapes, MXU-sized matmuls. Attention routes through
``autodist_tpu.ops.attention`` so sequence-parallel (ring) execution can be
swapped in by the strategy layer without touching model code.
"""
from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from autodist_tpu.telemetry import scopes

Dtype = Any


def causal_mask(seq_len: int) -> jnp.ndarray:
    return jnp.tril(jnp.ones((1, 1, seq_len, seq_len), jnp.bool_))


def rope(x, positions, theta: float):
    """Rotary position embedding in the ``rotate_half`` form (HF
    ``apply_rotary_pos_emb``): x [B, S, H, D], positions [S] or [B, S]
    (the index in the sequence). Angles and the rotation in float32."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [.., S, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]   # [.., S, 1, D]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * jnp.cos(ang) + rotated * jnp.sin(ang)).astype(x.dtype)


def make_norm(kind: str, eps: float, dtype, name=None):
    """The block's normalisation by its config name: ``layernorm`` (scale
    and bias) or ``rmsnorm`` (``x / sqrt(mean(x^2) + eps) * scale``)."""
    if kind == "layernorm":
        return nn.LayerNorm(epsilon=eps, dtype=dtype, name=name)
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)
    raise ValueError("norm must be layernorm|rmsnorm, got %r" % (kind,))


class SparseEmbed(nn.Module):
    """Embedding with the sparse-gradient wire identity.

    Drop-in for ``nn.Embed`` whose lookup routes through
    ``autodist_tpu.ops.embedding.embedding_lookup`` with the table's
    flattened parameter name, so the lowering can synchronize gradients as
    (ids, values) pairs instead of dense vocab-sized arrays (the
    reference's IndexedSlices path). Do NOT use for tied output embeddings
    — a table with other differentiable uses is auto-detected and kept
    dense, making the named lookup pointless there."""
    num_embeddings: int
    features: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, ids):
        from autodist_tpu.ops.embedding import embedding_lookup
        table = self.param(
            "embedding",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                             out_axis=0),
            (self.num_embeddings, self.features), self.param_dtype)
        name = "/".join(("params",) + tuple(self.path) + ("embedding",))
        return embedding_lookup(table.astype(self.dtype), ids, name=name)


class MultiHeadAttention(nn.Module):
    """Standard MHA with an injectable attention implementation.

    Three modes share one parameter set (submodules are created in the
    same order on every path, so flax resolves identical names):

    - training/eval (default): full-sequence attention, optionally
      through ``attn_fn``;
    - prefill (``return_kv=True``): same, but also returns the projected
      ``(k, v)`` [B, S, H, D] so the caller can seed a decode cache;
    - decode (``cache=(k_cache, v_cache)`` + ``cursor``): x is [B, 1, d],
      the new K/V row is written at ``cursor`` (gated by ``alive`` so
      dead slots never mutate their cache) and attention runs against
      the live cache prefix via ``ops.attention.cached_attention`` (or
      the flash decode inner loop when ``decode_attn="flash"``).
    """
    num_heads: int
    head_dim: int
    dtype: Dtype = jnp.float32
    attn_fn: Optional[Callable] = None  # (q, k, v, mask) -> out
    decode_attn: str = "reference"      # "reference" | "flash"
    use_bias: bool = True
    # RMSNorm over ALL projected features of q and of k, before the head
    # split (OLMoE's QK-norm); None = off
    qk_norm_eps: Optional[float] = None
    rope_theta: Optional[float] = None  # rotary q and k; needs positions

    @nn.compact
    def __call__(self, x, mask=None, cache=None, cursor=None, alive=None,
                 return_kv=False, positions=None):
        d_model = x.shape[-1]
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            features=(self.num_heads, self.head_dim), dtype=self.dtype,
            axis=-1, use_bias=self.use_bias, name=name)
        q = dense("query")(x)
        k = dense("key")(x)
        v = dense("value")(x)
        if self.qk_norm_eps is not None:
            def full_width_norm(t, name):
                flat = t.reshape(t.shape[:-2] + (-1,))
                return make_norm("rmsnorm", self.qk_norm_eps, self.dtype,
                                 name)(flat).reshape(t.shape)
            q = full_width_norm(q, "q_norm")
            k = full_width_norm(k, "k_norm")
        if self.rope_theta is not None:
            if positions is None:
                raise ValueError("rotary attention needs positions")
            q = rope(q, positions, self.rope_theta)
            k = rope(k, positions, self.rope_theta)
        new_cache = None
        if cache is not None:
            from autodist_tpu.ops.attention import (cached_attention,
                                                    flash_cached_attention)
            if cursor is None:
                raise ValueError("decode mode needs a cursor with the cache")
            k_cache, v_cache = cache
            T = k_cache.shape[1]
            # one-hot write at the cursor row; dead slots write nothing
            write = jnp.arange(T)[None, :] == cursor[:, None]
            if alive is not None:
                write = write & alive[:, None]
            sel = write[..., None, None]
            k_cache = jnp.where(sel, k.astype(k_cache.dtype), k_cache)
            v_cache = jnp.where(sel, v.astype(v_cache.dtype), v_cache)
            attn = (flash_cached_attention if self.decode_attn == "flash"
                    else cached_attention)
            out = attn(q[:, 0], k_cache, v_cache, cursor)[:, None]
            new_cache = (k_cache, v_cache)
        elif self.attn_fn is not None:
            out = self.attn_fn(q, k, v, mask)
        else:
            scale = 1.0 / np.sqrt(self.head_dim)
            logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
            if mask is not None:
                logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
            weights = nn.softmax(logits.astype(jnp.float32)).astype(self.dtype)
            out = jnp.einsum("...hqk,...khd->...qhd", weights, v)
        out = nn.DenseGeneral(features=d_model, axis=(-2, -1),
                              dtype=self.dtype, use_bias=self.use_bias,
                              name="out")(out)
        if cache is not None:
            return out, new_cache
        if return_kv:
            return out, (k, v)
        return out


class MoEFeedForward(nn.Module):
    """Routed SwiGLU feed-forward: ``num_experts`` experts of width
    ``expert_dim``, ``experts_per_token`` chosen per token, none dropped
    (``parallel/expert.py:dropless_moe_ffn``). Expert weights are stacked
    [E, d, f] / [E, f, d]. Sows the layer's two router losses into the
    ``losses`` collection (``router_lb``, ``router_z``) and its load into
    ``counters`` (``max_expert_pairs``, ``routed_pairs``): the loss adds
    the first to itself and hands the second to
    ``telemetry.device_counters``."""
    num_experts: int
    experts_per_token: int
    expert_dim: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from autodist_tpu.parallel.expert import dropless_moe_ffn
        d, E, f = x.shape[-1], self.num_experts, self.expert_dim
        stacked = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=1, out_axis=2, batch_axis=0)
        router = self.param("router", nn.initializers.lecun_normal(), (d, E))
        w_gate = self.param("gate_proj", stacked, (E, d, f))
        w_up = self.param("up_proj", stacked, (E, d, f))
        w_down = self.param("down_proj", stacked, (E, f, d))
        out, lb, z, counts = dropless_moe_ffn(
            x, router, w_gate, w_up, w_down, self.experts_per_token,
            self.dtype)
        self.sow("losses", "router_lb", lb)
        self.sow("losses", "router_z", z)
        self.sow("counters", "max_expert_pairs", jnp.max(counts))
        self.sow("counters", "routed_pairs", jnp.sum(counts))
        return out


class TransformerBlock(nn.Module):
    """Pre-norm block: attention and a feed-forward, each behind a norm
    and added to the residual. The defaults are the GPT-2 style block
    (LayerNorm, biased projections, GELU MLP of width ``mlp_dim``); the
    fields after ``decode_attn`` are architecture read from a model's
    public config (``models/lm.py:LMConfig``): with ``num_experts`` the
    feed-forward is the routed SwiGLU one and ``mlp_dim`` is ONE expert's
    width."""
    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: Dtype = jnp.float32
    dropout_rate: float = 0.0
    attn_fn: Optional[Callable] = None
    decode_attn: str = "reference"
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    attention_bias: bool = True
    qk_norm: bool = False
    rope_theta: Optional[float] = None
    num_experts: int = 0
    experts_per_token: int = 0

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, cache=None,
                 cursor=None, alive=None, return_kv=False, positions=None):
        kv = None
        h = make_norm(self.norm, self.norm_eps, self.dtype)(x)
        with scopes.scope(scopes.ATTENTION):
            h = MultiHeadAttention(
                self.num_heads, self.head_dim, self.dtype, self.attn_fn,
                decode_attn=self.decode_attn, use_bias=self.attention_bias,
                qk_norm_eps=self.norm_eps if self.qk_norm else None,
                rope_theta=self.rope_theta)(
                h, mask, cache=cache, cursor=cursor, alive=alive,
                return_kv=return_kv, positions=positions)
        if cache is not None or return_kv:
            h, kv = h
        if self.dropout_rate:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        x = x + h
        h = make_norm(self.norm, self.norm_eps, self.dtype)(x)
        if self.num_experts:
            h = MoEFeedForward(self.num_experts, self.experts_per_token,
                               self.mlp_dim, self.dtype, name="moe")(h)
        else:
            h = nn.Dense(self.mlp_dim, dtype=self.dtype)(h)
            h = nn.gelu(h)
            h = nn.Dense(x.shape[-1], dtype=self.dtype)(h)
        if self.dropout_rate:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        x = x + h
        if cache is not None or return_kv:
            return x, kv
        return x
