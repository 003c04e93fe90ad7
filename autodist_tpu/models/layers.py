"""Shared model layers (attention, transformer blocks).

TPU-first building blocks for the model zoo: bfloat16-friendly, static
shapes, MXU-sized matmuls. Attention routes through
``autodist_tpu.ops.attention`` so sequence-parallel (ring) execution can be
swapped in by the strategy layer without touching model code.
"""
import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu.telemetry import scopes

Dtype = Any
# the delta rule's output, by name; ``ops/kda.py:KEPT`` is the same name,
# on the per-chunk states its backward kernel reads
KDA_CORE_OUT = "kda_core_out"
# a block's dense feed-forward's gate and up products, by name: what a block
# recomputed in the backward pass keeps of it where the model's rule finds
# the room (``models/lm.py:TransformerLM._block``, ``auto_kept_layers``)
DENSE_FFN_KEPT = "dense_ffn_kept"
# a sandwich-normed sub-layer's output (the attention's ``out`` product, the
# feed-forward's ``down_proj``) as its output norm reads it, by name: the
# norm's backward needs its INPUT, so a recomputed block that does not keep
# it runs both products a second time
SUBLAYER_OUT_KEPT = "sublayer_out_kept"
# what a sequence mixer's INPUT projections made, as its element-wise pass
# reads it, by name (Mamba-2's ``[z | xBC | dt]``, KDA's q / k / v and the
# narrow halves of its low-rank pairs, the gated convolution's ``[B | C |
# u]``): that pass's backward reads it again, so a recomputed block that does
# not keep it runs the projections a second time. The gated convolution's
# core has no kept name of its own, as the delta rule and the scan have, so
# its gated product rides under this one: ``[B | C | u]`` alone LOST 0.35 %
# of the v5e's step (XLA made the product again in the prologue of
# ``out_proj``'s weight gradient and re-planned the fusions around it), the
# two together won 2.56 % (PERF.md section 6, PR 52)
MIXER_IN_KEPT = "mixer_in_kept"
# a gated softmax attention's gate projection's product ``[tokens, heads,
# head_dim]``, by name: the sigmoid's and the product's backward read it, so
# a recomputed block that does not keep it runs the projection a second time
ATTN_GATE_KEPT = "attn_gate_kept"


def mixer_in(x):
    """``x``, an output of a sequence mixer's input projections, under
    :data:`MIXER_IN_KEPT` (the identity outside a policy that saves it)."""
    return checkpoint_name(x, MIXER_IN_KEPT)


def causal_mask(seq_len: int) -> jnp.ndarray:
    return jnp.tril(jnp.ones((1, 1, seq_len, seq_len), jnp.bool_))


def rotate(x, positions, inv_freq, amplitude: float = 1.0):
    """Rotary position embedding in the ``rotate_half`` form (HF
    ``apply_rotary_pos_emb``): x [B, S, H, D], positions [S] or [B, S]
    (the index in the sequence), ``inv_freq`` [D/2] the angle a position
    adds to each pair of features, cos and sin times ``amplitude`` (YaRN's
    ``mscale`` ratio). Angles and the rotation in float32."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [.., S, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]   # [.., S, 1, D]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    # (no multiply by 1: plain RoPE's callers trace the equations they
    # always did)
    amp = (lambda t: t) if amplitude == 1.0 else (lambda t: t * amplitude)
    return (xf * amp(jnp.cos(ang))
            + rotated * amp(jnp.sin(ang))).astype(x.dtype)


def rope_inv_freq(d: int, theta: float):
    """Plain RoPE's frequencies ``theta^(-2i/D)``, [D / 2] float32."""
    return 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def rope(x, positions, theta: float):
    """:func:`rotate` by plain RoPE's frequencies."""
    return rotate(x, positions, rope_inv_freq(x.shape[-1], theta))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term ``0.1 mscale ln(factor) + 1`` (1
    where the context is not extended)."""
    return 1.0 if factor <= 1 else float(0.1 * mscale * np.log(factor) + 1.0)


def rotary_inv_freq(dim: int, theta: float, yarn: "Optional[YarnConfig]"):
    """The angle a position adds to each of ``dim / 2`` rotary pairs,
    float32, computed once, host side. Plain RoPE: pair i turns
    ``theta^(-2i/dim)`` a position. With ``yarn`` YaRN's blend (arXiv
    2309.00071, "NTK-by-parts"; the released DeepSeek-V2 code): that
    (``extra``polation, as trained) or that over ``factor``
    (``inter``polation), by a linear ramp between the pairs that make
    ``beta_fast`` and ``beta_slow`` whole turns over the original window."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return extra.astype(np.float32)

    def pair_turning(rotations):
        return (dim * np.log(yarn.original_max_position_embeddings
                             / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))
    low = max(int(np.floor(pair_turning(yarn.beta_fast))), 0)
    high = min(int(np.ceil(pair_turning(yarn.beta_slow))), dim - 1)
    ramp = (np.arange(dim // 2) - low) / max(high - low, 1e-3)
    keep = 1.0 - np.clip(ramp, 0.0, 1.0)       # 1 = extrapolated as trained
    return (extra / yarn.factor * (1.0 - keep) + extra * keep).astype(
        np.float32)


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


@dataclasses.dataclass(frozen=True)
class IndexerConfig:
    """A learned sparse attention's indexer (``sa_config``): ``num_heads``
    heads of ``head_dim`` over ONE key head choose ``topk`` keys a query,
    its scores made ``q_chunk`` queries at a time. ``rope_dim`` of the
    features are rotated (by the attention's ``rope_theta``)."""
    num_heads: int
    head_dim: int
    topk: int
    q_chunk: int = 512
    rope_dim: int = 0


class SparseIndexer(nn.Module):
    """DeepSeek Sparse Attention's indexer on the block's normed input x
    with the gradient STOPPED (the choice is discrete: the NLL has no
    gradient to these weights; DeepSeek-V3.2 trains them by a loss of
    their own, which no config here carries): ``q_idx = x W_q`` in heads,
    ``k_idx = LayerNorm(x W_k)`` one head, ``w = x W_w`` a weight a head;
    the first ``rope_dim`` features of q_idx and k_idx rotated by
    position. Returns ``ops/dsa.py:chosen_keys``' selection [B, S, S] and
    sows ``selected_pairs`` / ``causal_pairs`` into ``counters``. All in
    float32, whatever the model's dtype."""
    cfg: IndexerConfig
    rope_theta: Optional[float] = None

    @nn.compact
    def __call__(self, x, positions):
        from autodist_tpu.ops import dsa
        c = self.cfg
        x = jax.lax.stop_gradient(x).astype(jnp.float32)
        with scopes.scope(scopes.DSA_INDEX):
            dense = lambda n, name: nn.Dense(  # noqa: E731
                n, use_bias=False, precision=jax.lax.Precision.HIGHEST,
                name=name)
            q = dense(c.num_heads * c.head_dim, "wq")(x).reshape(
                x.shape[:-1] + (c.num_heads, c.head_dim))
            k = nn.LayerNorm(epsilon=1e-6, name="k_norm")(
                dense(c.head_dim, "wk")(x))[..., None, :]
            w = dense(c.num_heads, "weights_proj")(x)
            if c.rope_dim and self.rope_theta is not None:
                def turn(t):
                    pe, nope = jnp.split(t, [c.rope_dim], axis=-1)
                    return jnp.concatenate(
                        [rope(pe, positions, self.rope_theta), nope], axis=-1)
                q, k = turn(q), turn(k)
        chosen = dsa.chosen_keys(q, k[..., 0, :], w, c.topk, c.q_chunk)
        S = x.shape[-2]
        self.sow("counters", "selected_pairs",
                 jnp.sum(chosen, dtype=jnp.int32))
        self.sow("counters", "causal_pairs",
                 jnp.int32(x.size // x.shape[-1] * (S + 1) // 2))
        return chosen


class NormScale(nn.Module):
    """An ``nn.RMSNorm``'s learned weight alone, under the norm's own name:
    what a pass that norms inside a kernel reads of the parameter tree the
    ``jnp`` form's ``make_norm("rmsnorm", ...)`` initialised."""

    @nn.compact
    def __call__(self, features):
        return self.param("scale", nn.initializers.ones, (features,))


def attn_inputs(q, k, norms, positions, rope_theta):
    """The ``jnp`` form of what lies between a softmax attention's
    projections and its core: q [B, S, H, D] and k [B, S, Hkv, D] as the
    projections made them -> q and k as the core reads them. ``norms``:
    the (q's, k's) norms to apply, in order (OLMoE's over all projected
    features, Qwen3's a head; each rounds to the model's dtype); then,
    with ``rope_theta``, the rotation by ``positions`` (float32, rounded
    once more). v has no arithmetic. ``ops/attn_pre.py`` is the same
    values in one pass a direction, in the flash kernels' layout, and is
    tested against this."""
    for q_norm, k_norm in norms:
        q, k = q_norm(q), k_norm(k)
    if rope_theta is not None:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    return q, k


class MultiHeadAttention(nn.Module):
    """Softmax attention over heads with an injectable attention
    implementation: ``num_heads`` query heads of ``head_dim`` (its own
    size, not ``d_model / num_heads``) over as many K/V heads, or over
    ``num_kv_heads`` fewer that groups of query heads share (grouped-query
    attention: query head h reads K/V head ``h // (num_heads /
    num_kv_heads)``); optionally an RMSNorm of q and k (over all projected
    features, OLMoE's, or per head, Qwen3's), rotary positions, a
    learned choice of the keys each query attends (``indexer``), a
    sliding ``window``: a query sees the latest ``window`` keys, its own
    position counted (``ops/flash_attention.py`` says the same of its
    kernels, which then walk the band's tiles alone), and an output gate
    (``gated``: a fifth projection ``gate`` of the layer's input to every
    head's features, whose sigmoid multiplies the core's output before the
    output projection, ``(o * sigmoid(x W_g)) W_o``; afmoe's).

    Three modes share one parameter set (submodules are created in the
    same order on every path, so flax resolves identical names):

    - training/eval (default): full-sequence attention, optionally
      through ``attn_fn``. Where that is the flash kernels' adapter and
      the layer norms a head or rotates at heads of whole 128-lane tiles
      (``ops/attn_pre.py:runs_fused``, from these fields and the shapes
      alone), everything element-wise between the projections and the core
      is one pallas pass a direction, ``attn_pre``, on q and k in the
      kernels' own [B, H, S, D] (v is transposed alone); every other layer
      and mode runs :func:`attn_inputs`, the ``jnp`` form;
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
    num_kv_heads: Optional[int] = None  # None = as many as query heads
    # RMSNorm over each head's ``head_dim`` features of q and of k, one
    # learned weight shared by the heads (Qwen3's); None = off
    head_norm_eps: Optional[float] = None
    # a learned choice of the keys each query attends; None = all it sees
    indexer: Optional[IndexerConfig] = None
    # the latest keys a query sees, itself counted; None = every earlier one
    window: Optional[int] = None
    # the core's output times the sigmoid of a ``gate`` projection of x
    gated: bool = False

    @nn.compact
    def __call__(self, x, mask=None, cache=None, cursor=None, alive=None,
                 return_kv=False, positions=None):
        from autodist_tpu.ops import attn_pre
        d_model = x.shape[-1]
        kv_heads = self.num_kv_heads or self.num_heads
        if self.num_heads % kv_heads:
            raise ValueError("%d query heads do not share %d K/V heads"
                             % (self.num_heads, kv_heads))
        dense = lambda name, heads=self.num_heads: nn.DenseGeneral(  # noqa: E731
            features=(heads, self.head_dim), dtype=self.dtype,
            axis=-1, use_bias=self.use_bias, name=name)
        q = dense("query")(x)
        k = dense("key", kv_heads)(x)
        v = dense("value", kv_heads)(x)
        if self.rope_theta is not None and positions is None:
            raise ValueError("rotary attention needs positions")
        # (the pass holds no parameter of its own: an init traces no kernel)
        heads_first = (
            mask is None and cache is None and not return_kv
            and not self.is_initializing() and attn_pre.runs_fused(
                self.attn_fn, x.shape[-2], self.head_dim,
                self.head_norm_eps is not None, self.rope_theta is not None,
                self.qk_norm_eps is not None))
        if heads_first:
            q, k = attn_pre.attn_pre(
                q, k,
                None if self.head_norm_eps is None else tuple(
                    NormScale(name=name)(self.head_dim)
                    for name in ("q_norm", "k_norm")),
                self.head_norm_eps, positions,
                None if self.rope_theta is None else rope_inv_freq(
                    self.head_dim, self.rope_theta))
            v = v.transpose(0, 2, 1, 3)
        else:
            norms = []
            if self.qk_norm_eps is not None:
                def full_width(name):
                    norm = make_norm("rmsnorm", self.qk_norm_eps, self.dtype,
                                     name)
                    return lambda t: norm(
                        t.reshape(t.shape[:-2] + (-1,))).reshape(t.shape)
                norms.append((full_width("q_norm"), full_width("k_norm")))
            if self.head_norm_eps is not None:
                norms.append(tuple(
                    make_norm("rmsnorm", self.head_norm_eps, self.dtype, name)
                    for name in ("q_norm", "k_norm")))
            q, k = attn_inputs(q, k, norms, positions, self.rope_theta)
        new_cache = None
        if self.window is not None and (cache is not None or return_kv):
            raise NotImplementedError(
                "prefill and cached decode keep every K/V row and attend all "
                "of them: a sliding-window layer (window %d) needs a cache "
                "that forgets, which serving does not have yet" % self.window)
        grouped_or_chosen = (self.indexer is not None
                             or kv_heads != self.num_heads
                             or self.window is not None)
        if grouped_or_chosen and (cache is not None or return_kv):
            raise NotImplementedError(
                "prefill and cached decode keep as many K/V rows as query "
                "heads and attend all of them: grouped K/V heads and an "
                "indexer's own key cache have no decode path yet")
        if grouped_or_chosen:
            out = self._grouped_or_chosen(x, q, k, v, mask, positions,
                                          heads_first)
        elif cache is not None:
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
            with scopes.scope(scopes.ATTN_CORE):
                out = attn(q[:, 0], k_cache, v_cache, cursor)[:, None]
            new_cache = (k_cache, v_cache)
        else:
            with scopes.scope(scopes.ATTN_CORE):
                out = self._plain_core(q, k, v, mask, heads_first)
        if self.gated:
            with scopes.scope(scopes.ATTN_GATE):
                gate = checkpoint_name(dense("gate")(x), ATTN_GATE_KEPT)
                out = out * nn.sigmoid(gate)
        out = nn.DenseGeneral(features=d_model, axis=(-2, -1),
                              dtype=self.dtype, use_bias=self.use_bias,
                              name="out")(out)
        if cache is not None:
            return out, new_cache
        if return_kv:
            return out, (k, v)
        return out

    def _plain_core(self, q, k, v, mask, heads_first):
        """The core over as many K/V heads as query heads: ``attn_fn`` or
        XLA's scores. ``heads_first``: q, k and v are ``attn_pre``'s,
        [B, H, S, D] (only with the flash adapter, which takes them so)."""
        if heads_first:
            return self.attn_fn(q, k, v, mask, heads_first=True)
        if self.attn_fn is not None:
            return self.attn_fn(q, k, v, mask)
        scale = 1.0 / np.sqrt(self.head_dim)
        logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
        if mask is not None:
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        weights = nn.softmax(logits.astype(jnp.float32)).astype(self.dtype)
        return jnp.einsum("...hqk,...khd->...qhd", weights, v)

    def _grouped_or_chosen(self, x, q, k, v, mask, positions, heads_first):
        """The core over K/V heads that groups of query heads share, with
        an indexer over the keys it chose, with a ``window`` over the band
        it leaves (under ``swa_core``; the others under ``dsa_core``):
        through ``attn_fn`` (the flash kernels take all three as they are,
        and ``attn_pre``'s operands ``heads_first``) or XLA's scores with
        the K/V heads repeated."""
        from autodist_tpu.ops.attention import (causal_band,
                                                reference_attention)
        chosen = None
        if self.indexer is not None:
            chosen = SparseIndexer(self.indexer, self.rope_theta,
                                   name="indexer")(x, positions)
        core = scopes.DSA_CORE if self.window is None else scopes.SWA_CORE
        with scopes.scope(scopes.ATTN_CORE), scopes.scope(core):
            if self.attn_fn is not None:
                # (an attention function that knows no selection or window
                # still serves grouped heads)
                carried = {} if chosen is None else {"select": chosen}
                if self.window is not None:
                    carried["window"] = self.window
                if heads_first:
                    carried["heads_first"] = True
                return self.attn_fn(q, k, v, mask, **carried)
            group = self.num_heads // k.shape[-2]
            if group > 1:
                k, v = (jnp.repeat(t, group, axis=-2) for t in (k, v))
            if chosen is not None:
                chosen = (chosen != 0)[:, None]
                mask = chosen if mask is None else mask & chosen
            if self.window is not None:
                band = causal_band(q.shape[-3], k.shape[-3],
                                   self.window)[None, None]
                mask = band if mask is None else mask & band
            return reference_attention(q, k, v, mask)


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """Kimi Delta Attention's sizes (``linear_attn_config``)."""
    num_heads: int
    head_dim: int
    conv_size: int


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """``rope_scaling`` of ``type: yarn`` as a config.json publishes it."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Latent attention's sizes and its position signal: ``rope_theta``
    None = NoPE (the ``qk_rope_head_dim`` features exist and are not
    rotated); a base rotates q's and the shared key's ``qk_rope_head_dim``
    features, by plain RoPE's frequencies or, with ``yarn``, by YaRN's
    blended ones, which also rescale the softmax."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: Optional[float] = None
    yarn: Optional[YarnConfig] = None


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """A routed feed-forward's router and what stands beside it: how it
    scores (``activation`` "softmax" | "sigmoid", the latter choosing by
    score + a bias), whether the gates are renormalised over the chosen
    and scaled, whether a softmax router's balance loss is taken per
    sequence (``seq_aux``) or over all tokens with a z-loss (OLMoE's
    pair), the experts every token passes, and the SHARE of the experts
    this layer holds (None = all of them). ``gated`` is the form of every
    expert, routed and shared: ``down(silu(gate x) * up x)`` with three
    matrices, or ``down(relu(up x)^2)`` with two (Nemotron-H's ``relu2``);
    ``shared_width`` the shared expert's own width where it is not
    ``shared_experts`` times a routed expert's; ``gate_activation`` what a
    gated ROUTED expert's gate passes ("silu", or "relu": ReGLU,
    ``down(relu(gate x) * up x)``); ``reads_mixer_input``: the router's
    logits are taken from what the block's FIRST norm produced, the token
    mixer's input, and not from the feed-forward's own normed input, which
    the experts still read (SmallThinker's "router before attention"). The
    defaults are OLMoE's."""
    activation: str = "softmax"
    renormalize: bool = False
    scaling_factor: float = 1.0
    shared_experts: int = 0
    held: Optional[Tuple[int, ...]] = None
    seq_aux: bool = False
    gated: bool = True
    shared_width: int = 0
    gate_activation: str = "silu"
    reads_mixer_input: bool = False


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    """A Mamba-2 mixer's sizes as ``nemotron_h``'s config names them:
    ``num_heads`` heads of ``head_dim`` (the inner width is their product,
    not ``expand`` times the hidden size), ``n_groups`` groups of B and C
    of ``state_size``, a causal filter of ``conv_size`` taps, the dual
    form's ``chunk``."""
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_size: int
    chunk: int


def linear(features, dtype, name):
    """A projection without a bias (every one of Kimi-Linear's, a gated
    short convolution's two)."""
    return nn.Dense(features, dtype=dtype, use_bias=False, name=name)


def rms_normalize(x, eps):
    """x / sqrt(mean(x^2) + eps) over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


class SwiGLU(nn.Module):
    """down(silu(gate(x)) * up(x)), no bias. With ``kept`` the gate's and
    the up projection's products carry that name (``checkpoint_name``: the
    identity but under a policy that saves it), which SiLU's and the
    product's backward read: of the 11 matmuls a step makes of a recomputed
    SwiGLU (3 forward, 6 backward, the two the backward reads made again) a
    block that keeps the name leaves 9, for 4 T f bytes. ``silu(g) * u``
    carries none: the forward would have to write it, which costs more
    than remaking it saves (PERF.md section 6, PR 41). The shared experts'
    SwiGLU carries the dense name (``MoEFeedForward``): a block is dense or
    routed, never both, so the name says which products and the block's
    policy whose (``models/lm.py:auto_kept_layers`` books each in turn)."""
    width: int
    dtype: Dtype = jnp.float32
    kept: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        def product(name):
            out = linear(self.width, self.dtype, name)(x)
            return checkpoint_name(out, self.kept) if self.kept else out
        h = nn.silu(product("gate_proj")) * product("up_proj")
        return linear(x.shape[-1], self.dtype, "down_proj")(h)


class Relu2MLP(nn.Module):
    """down(relu(up(x))^2), no bias and no gate (``mlp_hidden_act: relu2``).
    With ``kept`` the up projection's product carries that name, which the
    squared ReLU's backward reads: ONE array of 2 T f bytes where a SwiGLU
    names two."""
    width: int
    dtype: Dtype = jnp.float32
    kept: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        u = linear(self.width, self.dtype, "up_proj")(x)
        u = checkpoint_name(u, self.kept) if self.kept else u
        return linear(x.shape[-1], self.dtype, "down_proj")(
            jnp.square(nn.relu(u)))


class MoEFeedForward(nn.Module):
    """Routed SwiGLU feed-forward: ``num_experts`` experts of width
    ``expert_dim``, ``experts_per_token`` chosen per token, none dropped
    (``parallel/expert.py:dropless_moe_ffn``). Expert weights are stacked
    [E, d, f] / [E, f, d]. Sows the layer's two router losses into the
    ``losses`` collection (``router_lb``, ``router_z``) and its load into
    ``counters`` (``max_expert_pairs``, ``routed_pairs``): the loss adds
    the first to itself and hands the second to
    ``telemetry.device_counters``.

    ``router`` (:class:`RouterConfig`) says the rest. A softmax router
    sows its losses (``router_z`` stays 0 under ``seq_aux``); a sigmoid
    router has none and chooses by score plus ``e_score_correction_bias``
    (it only chooses, so no gradient reaches it, and no rule here updates
    it: it stays at its initial zero). With a share (``held``) the stacks
    hold only the experts this layer HOLDS, the losses are still over all
    the router's outputs, and ``counters`` also gets ``chosen_pairs`` (all
    T x k). The shared experts (one SwiGLU as wide as all of them, the
    released DeepSeek and Kimi code's own form) are added in full. Experts
    without a gate (``router.gated`` False) hold no ``gate_proj``, routed
    or shared. ``router_input`` (x's shape): what the router's logits are
    taken from where that is not x itself."""
    num_experts: int
    experts_per_token: int
    expert_dim: int
    dtype: Dtype = jnp.float32
    router: RouterConfig = RouterConfig()

    @nn.compact
    def __call__(self, x, router_input=None):
        from autodist_tpu.parallel.expert import Routing, dropless_moe_ffn
        d, E, f = x.shape[-1], self.num_experts, self.expert_dim
        cfg = self.router
        held = E if cfg.held is None else len(cfg.held)
        stacked = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=1, out_axis=2, batch_axis=0)
        router = self.param("router", nn.initializers.lecun_normal(), (d, E))
        w_gate = self.param("gate_proj", stacked, (held, d, f)) \
            if cfg.gated else None
        w_up = self.param("up_proj", stacked, (held, d, f))
        w_down = self.param("down_proj", stacked, (held, f, d))
        softmax = cfg.activation == "softmax"
        bias = None if softmax else self.param(
            "e_score_correction_bias", nn.initializers.zeros, (E,))
        out, lb, z, counts = dropless_moe_ffn(
            x, router, w_gate, w_up, w_down, self.experts_per_token,
            self.dtype, Routing(cfg.activation, cfg.renormalize,
                                cfg.scaling_factor, bias),
            held=cfg.held, seq_aux=cfg.seq_aux, router_input=router_input,
            gate_activation=cfg.gate_activation)
        if softmax:
            self.sow("losses", "router_lb", lb)
            self.sow("losses", "router_z", z)
        self.sow("counters", "max_expert_pairs", jnp.max(counts))
        self.sow("counters", "routed_pairs", jnp.sum(counts))
        if cfg.held is not None:
            pairs = x.size // d * self.experts_per_token
            self.sow("counters", "chosen_pairs", jnp.int32(pairs))
        if cfg.shared_experts:
            ffn = SwiGLU if cfg.gated else Relu2MLP
            with scopes.scope(scopes.MOE), scopes.scope(scopes.MOE_SHARED):
                out = out + ffn(cfg.shared_width or cfg.shared_experts * f,
                                self.dtype, DENSE_FFN_KEPT, name="shared")(x)
        return out


# one filter a channel of a depthwise causal convolution, [taps, channels]
# (a gated short convolution's, KDA's three, a Mamba-2 mixer's)
conv_filter_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "uniform", in_axis=0, out_axis=1)


def a_log_init(key, shape):
    """``A_log = log U(1, 16)``: the decay's rate a head (KDA, Mamba-2)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def dt_bias_init(key, shape):
    """The inverse softplus of a log-uniform draw in [1e-3, 1e-1]: a time
    step's bias (KDA, Mamba-2)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(x, w):
    """Depthwise causal convolution along the sequence: x [B, S, C], w
    [K, C]; y_t = sum_j w_j * x_{t-K+1+j}, zeros before the start."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0)])
    return sum(padded[:, j:j + S] * w[j] for j in range(K))


class ShortConv(nn.Module):
    """LFM2's gated short convolution as a token mixer: ``[B, C, u] =
    W_in x`` (d -> 3 d, split in thirds in that order), ``z = B * u``, a
    depthwise causal convolution of ``kernel_size`` taps (``conv_L_cache``)
    over z (one filter a channel, zeros before the sequence's start, no
    activation), ``y = W_out (C * conv(z))``. No bias, no position signal,
    no state beyond the last ``kernel_size - 1`` inputs."""
    kernel_size: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        bcu = mixer_in(linear(3 * d, self.dtype, "in_proj")(x))
        w = self.param("conv", conv_filter_init, (self.kernel_size, d))
        with scopes.scope(scopes.CONV_CORE):
            b, c, u = jnp.split(bcu, 3, axis=-1)
            y = mixer_in(c * causal_conv(b * u, w.astype(self.dtype)))
        return linear(d, self.dtype, "out_proj")(y)


class KimiDeltaAttention(nn.Module):
    """The KDA token mixer (arXiv 2510.26692): q, k, v through a short
    causal convolution and SiLU, q and k L2-normalised per head, a
    per-channel decay and a per-head write strength, the gated delta rule
    (``ops/kda.py``), a per-head RMSNorm gated by a sigmoid, the output
    projection. No bias on a projection; no position signal. The decay is
    ``-exp(A_log) * softplus(f(x) + dt_bias)`` with A_log per head, f and
    the output gate low-rank through ``head_dim`` features.

    Where the delta rule runs as kernels (``ops/kda.py:runs_as_kernels``:
    heads of whole 128-lane tiles), everything element-wise between the
    projections and the core, and between the core and ``o_proj``, runs as
    one pass over HBM a direction in the kernels' own [B, S, H * d] layout
    (``kda_pre``, ``kda_post``); narrower heads take the ``jnp`` form
    below, which is what the fused passes are tested against."""
    cfg: KDAConfig
    norm_eps: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from autodist_tpu.ops import kda
        H, D, K = self.cfg.num_heads, self.cfg.head_dim, self.cfg.conv_size
        dense = lambda n, name: linear(n, self.dtype, name)  # noqa: E731
        # (the core and the passes around it hold no parameter: an init
        # traces no kernel for them)
        fused = not self.is_initializing() and kda.runs_as_kernels(D, D)

        # what a block recomputed in the backward pass keeps of the input
        # projections where the model's rule finds the room: q, k and v as
        # ``kda_pre`` reads them, the [T, d] halves of the two low-rank pairs
        # (their wide halves, 8.6 GFLOP each, are made again) and b's [T, H]
        def projected(name):
            w = self.param(name + "_conv", conv_filter_init, (K, H * D))
            return mixer_in(dense(H * D, name + "_proj")(x)), w

        (xq, wq), (xk, wk), (xv, wv) = (projected(n) for n in "qkv")
        a_log = self.param("A_log", a_log_init, (H,))
        # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt_bias = self.param("dt_bias", dt_bias_init, (H * D,))
        f = dense(H * D, "f_b_proj")(mixer_in(dense(D, "f_a_proj")(x)))
        beta = nn.sigmoid(mixer_in(dense(H, "b_proj")(x)).astype(jnp.float32))
        if fused:
            q, k, v, g = kda.kda_pre(xq, xk, xv, f, wq, wk, wv, a_log,
                                     dt_bias, self.dtype)
            with scopes.scope(scopes.KDA_SCAN):
                o, _ = kda.kda_whole_chunks(q, k, v, g, beta, self.dtype)
        else:
            q, k, v, g = kda_inputs(xq, xk, xv, f, wq, wk, wv, a_log, dt_bias,
                                    self.dtype)
            if self.is_initializing():
                o = v.astype(self.dtype)
            else:
                with scopes.scope(scopes.KDA_SCAN):
                    o, _ = kda.kda_chunked(q, k, v, g, beta, self.dtype)
        # a block recomputed in the backward pass keeps this (and, by the
        # same name, the kernels' per-chunk states) and does not run the
        # core again (``models/lm.py:TransformerLM._block``)
        o = checkpoint_name(o, KDA_CORE_OUT)
        scale = self.param("o_norm", nn.initializers.ones, (D,))
        gate = dense(H * D, "g_b_proj")(mixer_in(dense(D, "g_a_proj")(x)))
        gated = kda.kda_post if fused else kda_output
        return dense(x.shape[-1], "o_proj")(
            gated(o, gate, scale, self.norm_eps, self.dtype))


def kda_inputs(xq, xk, xv, f, wq, wk, wv, a_log, dt_bias, dtype):
    """The ``jnp`` form of what lies between a KDA mixer's projections and
    its delta rule: the outputs [B, S, H * d] of ``q_proj``, ``k_proj``,
    ``v_proj`` and ``f_b_proj``, the [K, H * d] filters, ``A_log`` [H],
    ``dt_bias`` [H * d] -> q, k (float32, L2-normalised per head, q times
    d^-0.5), v (``dtype``) and the log decay g (float32), [B, S, H, d]."""
    d = xq.shape[-1] // a_log.shape[0]
    heads = lambda t: t.reshape(t.shape[:-1] + (-1, d))  # noqa: E731
    q, k, v = (heads(nn.silu(causal_conv(t, w.astype(dtype))))
               for t, w in ((xq, wq), (xk, wk), (xv, wv)))
    l2 = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q = l2(q.astype(jnp.float32)) * d ** -0.5
    k = l2(k.astype(jnp.float32))
    g = -jnp.exp(a_log)[:, None] * heads(
        jax.nn.softplus(f.astype(jnp.float32) + dt_bias))
    return q, k, v, g


def kda_output(o, gate, o_norm, eps, dtype):
    """The ``jnp`` form of what lies between the delta rule and ``o_proj``:
    o [B, seq, H, d], the gate projection's output [B, seq, H * d] and
    ``o_norm`` [d] -> ``rms_normalize(o) * o_norm * sigmoid(gate)``,
    [B, seq, H * d] in ``dtype``."""
    gate = nn.sigmoid(gate.reshape(o.shape).astype(jnp.float32))
    o = (rms_normalize(o, eps) * o_norm * gate).astype(dtype)
    return o.reshape(o.shape[:-2] + (-1,))


class Mamba2Mixer(nn.Module):
    """The Mamba-2 token mixer (arXiv 2405.21060) as ``nemotron_h`` builds
    it: ``[z | xBC | dt] = W_in u`` (widths inner | inner + 2 G N | H with
    inner = H P); ``xBC <- silu(conv(xBC) + b)``, a depthwise causal filter
    with a bias; ``xBC`` split into x [H, P], B [G, N], C [G, N]; ``dt <-
    softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)`` one scalar a
    head; the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D x_t`` (``ops/ssd.py``: two pallas kernels where
    the heads fill whole tiles, their y and per-chunk states kept by name
    across a recomputed block, the ``jnp`` form where they do not);
    ``y <- N_g(y * silu(z))``,
    the gate FIRST and the RMS statistic over each group's inner / G
    features, one learned weight of inner; ``W_out y``. No bias on a
    projection, no position signal. Sows ``chunk_carry`` into
    ``counters``: what of a chunk's incoming state survives it.

    Two renderings of what lies between the projections, chosen by the
    shapes (``ops/ssd.py:mixer_runs_fused``; no argument, flag or
    environment variable): at the published widths everything element-wise
    before and after the scan is one pallas pass a direction on
    ``in_proj``'s output TOKENS LAST (``ssd.mamba_pre`` writes the scan's
    operands in the scan's layouts, ``ssd.mamba_post`` reads its y as it
    leaves the kernel); anything narrower takes :func:`mamba_inputs` and
    :func:`mamba_output` below, the ``jnp`` form the passes are tested
    against."""
    cfg: Mamba2Config
    norm_eps: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from autodist_tpu.ops import ssd
        c = self.cfg
        H, P, G, N = c.num_heads, c.head_dim, c.n_groups, c.state_size
        inner, conv_dim = H * P, H * P + 2 * G * N
        zxbcdt = linear(inner + conv_dim + H, self.dtype, "in_proj")(x)
        w = self.param("conv", conv_filter_init, (c.conv_size, conv_dim))
        b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        a_log = self.param("A_log", a_log_init, (H,))
        d_skip = self.param("D", nn.initializers.ones, (H,))
        # softplus(dt_bias) log-uniform in [time_step_min, time_step_max]
        # (its floor, 1e-4, lies under the range)
        dt_bias = self.param("dt_bias", dt_bias_init, (H,))
        scale = self.param("norm", nn.initializers.ones, (inner,))
        if not self.is_initializing() and ssd.mixer_runs_fused(
                P, N, H // G, G, c.chunk, c.conv_size):
            # (the passes hold no parameter: an init traces no kernel; a
            # block recomputed in the backward pass keeps ``in_proj``'s output
            # by name where the model's rule finds the room, IN THE FORM THE
            # PASS READS: the buffer the forward writes anyway, and no
            # transpose of it)
            xs, dt, dta, bs, cs, zx = ssd.mamba_pre(
                mixer_in(jnp.moveaxis(zxbcdt, 1, 2)), w, b, dt_bias, a_log,
                G, N, c.chunk, self.dtype)
            with scopes.scope(scopes.SSD_SCAN):
                y, total = ssd.ssd_tokens_last(xs, dt, dta, bs, cs, d_skip,
                                               c.chunk, self.dtype)
                carry = jnp.mean(jnp.exp(total))
            y = jnp.moveaxis(ssd.mamba_post(y, zx, scale, self.norm_eps,
                                            self.dtype), 1, 2)
        else:
            xs, dt, a, bs, cs, z = mamba_inputs(mixer_in(zxbcdt), w, b,
                                                dt_bias, a_log, c, self.dtype)
            with scopes.scope(scopes.SSD_SCAN):
                y, carry = ssd.ssd_chunked(xs, dt, a, bs, cs, d_skip, c.chunk,
                                           self.dtype)
            y = mamba_output(y, z, scale, G, self.norm_eps, self.dtype)
        self.sow("counters", "chunk_carry", carry)
        return linear(x.shape[-1], self.dtype, "out_proj")(y)


def mamba_inputs(zxbcdt, w, b, dt_bias, a_log, cfg, dtype):
    """The ``jnp`` form of what lies between a Mamba-2 mixer's ``in_proj``
    and its recurrence: ``in_proj``'s output [B, S, inner + conv_dim + H]
    (``[z | xBC | dt]``), the filter [K, conv_dim] and its bias, ``dt_bias``
    and ``A_log`` [H] -> ``ssd_chunked``'s x [B, S, H, P], dt [B, S, H]
    (float32, after its softplus), a [H] (negative), B and C [B, S, G, N],
    and the gate z [B, S, inner]."""
    H, P, G, N = cfg.num_heads, cfg.head_dim, cfg.n_groups, cfg.state_size
    inner, conv_dim = H * P, H * P + 2 * G * N
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
    xbc = nn.silu(causal_conv(xbc, w.astype(dtype)) + b.astype(dtype))
    xs, bs, cs = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    heads = lambda t, n: t.reshape(t.shape[:-1] + (n, -1))  # noqa: E731
    return (heads(xs, H), jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
            -jnp.exp(a_log), heads(bs, G), heads(cs, G), z)


def mamba_output(y, z, scale, groups, eps, dtype):
    """The ``jnp`` form of what lies between the recurrence and
    ``out_proj``: y [B, S, H, P], the gate z [B, S, inner] and the norm's
    weight [inner] -> ``rms_normalize`` over each of the ``groups`` groups'
    inner / groups features of ``y * silu(z)`` (the gate FIRST), times the
    weight, [B, S, inner] in ``dtype``."""
    y = y.reshape(z.shape) * nn.silu(z)
    y = y.reshape(y.shape[:-1] + (groups, -1))
    return (rms_normalize(y, eps).reshape(z.shape) * scale).astype(dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention in its TRAINING form (no absorbed
    matrices, no latent cache): a full-rank q of ``nope + rope`` features a
    head; k and v up-projected from an RMS-normalised latent of
    ``kv_lora_rank``, k's last ``rope`` features projected straight from x
    and shared by all heads; scores over ``nope + rope`` features, values
    of ``v_head_dim``. With ``rope_theta`` None nothing is rotated (Kimi-
    Linear); with it q's last ``rope`` features and the shared key are
    rotated in the ``rotate_half`` pairing, and with ``yarn`` by YaRN's
    blended frequencies, cos and sin times ``ms(mscale) / ms(mscale_all_dim)``
    and the scores times ``ms(mscale_all_dim)^2`` (DeepSeek-V2)."""
    num_heads: int
    cfg: MLAConfig
    norm_eps: float
    dtype: Dtype = jnp.float32
    attn_fn: Optional[Callable] = None  # (q, k, v, mask) -> out

    @nn.compact
    def __call__(self, x, mask=None, positions=None):
        c, H = self.cfg, self.num_heads
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        dense = lambda n, name: linear(n, self.dtype, name)  # noqa: E731
        q = dense(H * qk, "q_proj")(x).reshape(x.shape[:-1] + (H, qk))
        kv_a = dense(c.kv_lora_rank + c.qk_rope_head_dim, "kv_a_proj")(x)
        latent, k_pe = jnp.split(kv_a, [c.kv_lora_rank], axis=-1)
        latent = make_norm("rmsnorm", self.norm_eps, self.dtype,
                           "kv_a_norm")(latent)
        kv = dense(H * (c.qk_nope_head_dim + c.v_head_dim), "kv_b_proj")(
            latent).reshape(x.shape[:-1] + (H, -1))
        k_nope, v = jnp.split(kv, [c.qk_nope_head_dim], axis=-1)
        k_pe = k_pe[..., None, :]
        if c.rope_theta is not None:
            if positions is None:
                raise ValueError("rotary attention needs positions")
            inv_freq = rotary_inv_freq(c.qk_rope_head_dim, c.rope_theta,
                                       c.yarn)
            amplitude = 1.0 if c.yarn is None else (
                yarn_mscale(c.yarn.factor, c.yarn.mscale)
                / yarn_mscale(c.yarn.factor, c.yarn.mscale_all_dim))
            q_nope, q_pe = jnp.split(q, [c.qk_nope_head_dim], axis=-1)
            q = jnp.concatenate(
                [q_nope, rotate(q_pe, positions, inv_freq, amplitude)],
                axis=-1)
            k_pe = rotate(k_pe, positions, inv_freq, amplitude)
        if c.yarn is not None and c.yarn.mscale_all_dim:
            # YaRN's temperature: the scores times ms(mscale_all_dim)^2, put
            # onto q (in float32: bfloat16 would round the factor itself by
            # a quarter of a percent) so that the attention function below
            # stays the one every model shares
            q = (q.astype(jnp.float32) * yarn_mscale(
                c.yarn.factor, c.yarn.mscale_all_dim) ** 2).astype(q.dtype)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + k_pe.shape[-1:])],
            axis=-1)
        from autodist_tpu.ops.attention import reference_attention
        # (both scale the scores by 1 / sqrt(nope + rope), on top of
        # YaRN's factor above, and take values narrower than the scores'
        # features)
        with scopes.scope(scopes.ATTN_CORE), scopes.scope(scopes.MLA_CORE):
            out = (self.attn_fn or reference_attention)(q, k, v, mask)
        return dense(x.shape[-1], "o_proj")(
            out.reshape(out.shape[:-2] + (H * c.v_head_dim,)))


class TransformerBlock(nn.Module):
    """Pre-norm block: attention and a feed-forward, each behind a norm
    and added to the residual. The defaults are the GPT-2 style block
    (LayerNorm, biased projections, GELU MLP of width ``mlp_dim``); the
    fields after ``decode_attn`` are architecture read from a model's
    public config (``models/lm.py:LMConfig``): with ``num_experts`` the
    feed-forward is the routed SwiGLU one and ``mlp_dim`` is ONE expert's
    width. A model whose layers differ names each block's token mixer by
    its sizes (``kda``, ``mla`` or ``conv_size`` instead of the softmax
    attention above) and gives a leading dense layer its SwiGLU width
    (``dense_dim``). ``sandwich_norm`` norms each sub-layer's OUTPUT too,
    before it joins the residual (``x + N(f(N(x)))``: four norms a block,
    the looped Ouro models' block), and names what the two output norms
    read (:data:`SUBLAYER_OUT_KEPT`). ``only`` leaves out the half a layer
    of single sub-layers does not have: "mixer" = ``x + Mix(N(x))`` alone,
    "ffn" = ``x + FFN(N(x))`` alone (the Nemotron-H layers, each ONE
    sub-layer behind ONE norm). ``window`` is THIS layer's sliding window
    and ``rope_theta`` THIS layer's rotation (a model may window and rotate
    some layers and not others); a router that ``reads_mixer_input`` is
    handed the first norm's output; ``gated_attention`` gives the softmax
    attention its output gate."""
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
    kda: Optional[KDAConfig] = None
    mla: Optional[MLAConfig] = None
    dense_dim: int = 0
    router: RouterConfig = RouterConfig()
    num_kv_heads: Optional[int] = None
    qk_head_norm: bool = False
    indexer: Optional[IndexerConfig] = None
    conv_size: int = 0
    sandwich_norm: bool = False
    mamba: Optional[Mamba2Config] = None
    only: Optional[str] = None          # None (both) | "mixer" | "ffn"
    window: Optional[int] = None
    gated_attention: bool = False

    def _mix(self, h, mask, cache, cursor, alive, return_kv, positions):
        """The block's token mixer on the normed input."""
        if (self.kda is None and self.mla is None and not self.conv_size
                and self.mamba is None):
            return MultiHeadAttention(
                self.num_heads, self.head_dim, self.dtype, self.attn_fn,
                decode_attn=self.decode_attn, use_bias=self.attention_bias,
                qk_norm_eps=self.norm_eps if self.qk_norm else None,
                rope_theta=self.rope_theta, num_kv_heads=self.num_kv_heads,
                head_norm_eps=self.norm_eps if self.qk_head_norm else None,
                indexer=self.indexer, window=self.window,
                gated=self.gated_attention)(
                h, mask, cache=cache, cursor=cursor, alive=alive,
                return_kv=return_kv, positions=positions)
        if cache is not None or return_kv:
            raise NotImplementedError(
                "prefill and cached decode keep K/V rows only: a kda "
                "layer's recurrent state, an mla layer's latent, a conv "
                "layer's last inputs and a mamba2 layer's state and filter "
                "inputs have no cache yet")
        if self.mamba is not None:
            with scopes.scope(scopes.MAMBA):
                return Mamba2Mixer(self.mamba, self.norm_eps, self.dtype,
                                   name="mamba")(h)
        if self.conv_size:
            with scopes.scope(scopes.CONV_MIX):
                return ShortConv(self.conv_size, self.dtype, name="conv")(h)
        if self.kda is not None:
            with scopes.scope(scopes.KDA):
                return KimiDeltaAttention(self.kda, self.norm_eps, self.dtype,
                                          name="kda")(h)
        with scopes.scope(scopes.MLA):
            return LatentAttention(self.num_heads, self.mla, self.norm_eps,
                                   self.dtype, self.attn_fn, name="mla")(
                h, mask, positions)

    def _mixer_sublayer(self, x, mask, deterministic, cache, cursor, alive,
                        return_kv, positions):
        kv = None
        normed = make_norm(self.norm, self.norm_eps, self.dtype)(x)
        with scopes.scope(scopes.ATTENTION):
            h = self._mix(normed, mask, cache, cursor, alive, return_kv,
                          positions)
        if cache is not None or return_kv:
            h, kv = h
        if self.sandwich_norm:
            h = make_norm(self.norm, self.norm_eps, self.dtype,
                          "attn_out_norm")(
                checkpoint_name(h, SUBLAYER_OUT_KEPT))
        if self.dropout_rate:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        return x + h, kv, normed

    def _ffn_sublayer(self, x, deterministic, mixer_input=None):
        """``mixer_input``: the first norm's output, for a router that
        reads it."""
        h = make_norm(self.norm, self.norm_eps, self.dtype)(x)
        if self.dense_dim:
            with scopes.scope(scopes.DENSE_FFN):
                h = SwiGLU(self.dense_dim, self.dtype, DENSE_FFN_KEPT,
                           name="mlp")(h)
        elif self.num_experts:
            h = MoEFeedForward(self.num_experts, self.experts_per_token,
                               self.mlp_dim, self.dtype, self.router,
                               name="moe")(
                h, mixer_input if self.router.reads_mixer_input else None)
        else:
            with scopes.scope(scopes.DENSE_FFN):
                h = nn.Dense(self.mlp_dim, dtype=self.dtype)(h)
                h = nn.gelu(h)
                h = nn.Dense(x.shape[-1], dtype=self.dtype)(h)
        if self.sandwich_norm:
            h = make_norm(self.norm, self.norm_eps, self.dtype,
                          "mlp_out_norm")(
                checkpoint_name(h, SUBLAYER_OUT_KEPT))
        if self.dropout_rate:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        return x + h

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, cache=None,
                 cursor=None, alive=None, return_kv=False, positions=None):
        kv = mixer_input = None
        if self.only != "ffn":
            x, kv, mixer_input = self._mixer_sublayer(
                x, mask, deterministic, cache, cursor, alive, return_kv,
                positions)
        elif cache is not None or return_kv:
            raise NotImplementedError(
                "prefill and cached decode read K/V rows from every layer: "
                "a layer that is its feed-forward alone has none")
        if self.only != "mixer":
            x = self._ffn_sublayer(x, deterministic, mixer_input)
        if cache is not None or return_kv:
            return x, kv
        return x
