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

    @nn.compact
    def __call__(self, x, mask=None, cache=None, cursor=None, alive=None,
                 return_kv=False):
        d_model = x.shape[-1]
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            features=(self.num_heads, self.head_dim), dtype=self.dtype,
            axis=-1, name=name)
        q = dense("query")(x)
        k = dense("key")(x)
        v = dense("value")(x)
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
                              dtype=self.dtype, name="out")(out)
        if cache is not None:
            return out, new_cache
        if return_kv:
            return out, (k, v)
        return out


class TransformerBlock(nn.Module):
    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: Dtype = jnp.float32
    dropout_rate: float = 0.0
    attn_fn: Optional[Callable] = None
    decode_attn: str = "reference"

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, cache=None,
                 cursor=None, alive=None, return_kv=False):
        kv = None
        h = nn.LayerNorm(dtype=self.dtype)(x)
        with scopes.scope(scopes.ATTENTION):
            h = MultiHeadAttention(self.num_heads, self.head_dim, self.dtype,
                                   self.attn_fn,
                                   decode_attn=self.decode_attn)(
                h, mask, cache=cache, cursor=cursor, alive=alive,
                return_kv=return_kv)
        if cache is not None or return_kv:
            h, kv = h
        if self.dropout_rate:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(x.shape[-1], dtype=self.dtype)(h)
        if self.dropout_rate:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        x = x + h
        if cache is not None or return_kv:
            return x, kv
        return x
