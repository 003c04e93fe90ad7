"""BERT encoder + masked-LM pretraining.

TPU-native counterpart of the reference's BERT benchmark
(``examples/benchmark/bert.py`` + vendored ``utils/bert_*``). From-scratch
flax implementation: word/position/type embeddings, N transformer blocks,
MLM head with tied embeddings. The embedding table is gather-indexed, so
``ModelItem`` marks it sparse and Parallax routes it to load-balanced PS —
the same hybrid the reference benchmarks BERT with.
"""
import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.layers import TransformerBlock, SparseEmbed


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dtype: Any = jnp.float32

    @classmethod
    def base(cls, **kw):
        return cls(**kw)

    @classmethod
    def large(cls, **kw):
        return cls(hidden_size=1024, num_layers=24, num_heads=16,
                   mlp_dim=4096, **kw)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized config."""
        return cls(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                   mlp_dim=64, max_position=64, **kw)


class BertEncoder(nn.Module):
    config: BertConfig
    attn_fn: Optional[Any] = None

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic=True):
        cfg = self.config
        seq_len = input_ids.shape[-1]
        # SparseEmbed: MLM output is untied, so gradients for these
        # tables can ride the sparse (ids, values) wire; the small
        # position/type tables are auto-kept dense by the cost gate
        x = SparseEmbed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                        name="word_embeddings")(input_ids)
        pos = jnp.arange(seq_len)[None]
        x = x + SparseEmbed(cfg.max_position, cfg.hidden_size,
                            dtype=cfg.dtype,
                            name="position_embeddings")(pos)
        if token_type_ids is not None:
            x = x + SparseEmbed(cfg.type_vocab_size, cfg.hidden_size,
                                dtype=cfg.dtype,
                                name="token_type_embeddings")(token_type_ids)
        x = nn.LayerNorm(dtype=cfg.dtype, name="embeddings_ln")(x)
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(jnp.bool_)
        for i in range(cfg.num_layers):
            x = TransformerBlock(cfg.num_heads,
                                 cfg.hidden_size // cfg.num_heads,
                                 cfg.mlp_dim, dtype=cfg.dtype,
                                 attn_fn=self.attn_fn,
                                 name="layer_%d" % i)(x, mask, deterministic)
        return x


class BertForMLM(nn.Module):
    config: BertConfig
    attn_fn: Optional[Any] = None

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None):
        cfg = self.config
        encoder = BertEncoder(cfg, attn_fn=self.attn_fn, name="encoder")
        x = encoder(input_ids, token_type_ids, attention_mask)
        x = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="mlm_transform")(x)
        x = nn.gelu(x)
        x = nn.LayerNorm(dtype=cfg.dtype, name="mlm_ln")(x)
        logits = nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                          name="mlm_output")(x)
        return logits


def make_train_setup(config: Optional[BertConfig] = None, seq_len: int = 128,
                     batch_size: int = 32, seed: int = 0,
                     attention: str = "auto"):
    """(loss_fn, params, example_batch, apply_fn) — masked-LM objective.

    ``attention``: "xla" (fused XLA attention), "flash" (the pallas kernel
    with the padding ``attention_mask`` as segment ids,
    ``ops/flash_attention.py``), or "auto" (default): XLA below 8192
    tokens, flash at or above. XLA attention materializes the [S, S]
    logits and stops fitting around seq 8192 at bert-base geometry; the
    flash kernel's O(S) memory is what extends BERT past that wall, so
    "auto" switches where XLA stops being an option. Which side is
    faster below the wall is not measured on today's code (the records
    that said XLA, ~1.8x at 512-4096, were deleted in PR 21).
    """
    cfg = config or BertConfig.base()
    if attention == "auto":
        attention = "flash" if seq_len >= 8192 else "xla"
    attn_fn = None
    if attention == "flash":
        from autodist_tpu.ops.flash_attention import make_flash_attn_fn
        attn_fn = make_flash_attn_fn(causal=False)
    elif attention != "xla":
        raise ValueError("attention must be 'auto', 'flash' or 'xla'")
    model = BertForMLM(cfg, attn_fn=attn_fn)
    rng = jax.random.PRNGKey(seed)
    ids0 = jnp.zeros((1, seq_len), jnp.int32)
    # jitted init: ONE device dispatch for the whole parameter tree
    # (eager flax init issues one dispatch per initializer)
    variables = jax.jit(model.init)(rng, ids0, ids0,
                                    jnp.ones((1, seq_len), jnp.int32))

    def loss_fn(params, batch):
        logits = model.apply(params, batch["input_ids"],
                             batch["token_type_ids"], batch["attention_mask"])
        logp = jax.nn.log_softmax(logits)
        # gather, not one_hot: a [tokens, vocab] one-hot would double the
        # biggest tensor in the program for the same math
        per_tok = -jnp.take_along_axis(
            logp, batch["labels"][..., None], axis=-1)[..., 0]
        weights = batch["mlm_weights"].astype(per_tok.dtype)
        return jnp.sum(per_tok * weights) / jnp.maximum(jnp.sum(weights), 1.0)

    npr = np.random.RandomState(seed)
    example_batch = {
        "input_ids": npr.randint(0, cfg.vocab_size, (batch_size, seq_len)).astype(np.int32),
        "token_type_ids": np.zeros((batch_size, seq_len), np.int32),
        "attention_mask": np.ones((batch_size, seq_len), np.int32),
        "labels": npr.randint(0, cfg.vocab_size, (batch_size, seq_len)).astype(np.int32),
        "mlm_weights": (npr.rand(batch_size, seq_len) < 0.15).astype(np.float32),
    }
    apply_fn = lambda p, ids: model.apply(p, ids)  # noqa: E731
    return loss_fn, dict(variables), example_batch, apply_fn
