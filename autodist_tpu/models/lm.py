"""Decoder-only language model (the lm1b benchmark family).

TPU-native counterpart of the reference's 1B-word LM example
(``examples/lm1b/language_model.py`` — an LSTM with sampled softmax, metric
words/sec ``lm1b_train.py:62-75``). Re-designed transformer-first for TPU —
LSTMs serialize on the sequence axis and starve the MXU; a causal
transformer with ``lax``-friendly static shapes is the idiomatic
equivalent at the same objective (next-word prediction on lm1b). The token
embedding and the lm_head are UNTIED by default so the big table can
ride the sparse (ids, values) gradient wire (``models/layers.SparseEmbed``
— a tied table, ``LMConfig.tie_embedding``, has a dense gradient through
the logits and is auto-kept dense). The big embedding table is the
PartitionedPS stress case, as in the reference benchmark.
"""
import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.layers import (ATTN_GATE_KEPT, DENSE_FFN_KEPT,
                                        KDA_CORE_OUT, MIXER_IN_KEPT,
                                        SUBLAYER_OUT_KEPT,
                                        IndexerConfig, KDAConfig,
                                        Mamba2Config, MLAConfig, RouterConfig,
                                        SparseEmbed, TransformerBlock,
                                        YarnConfig, causal_mask, make_norm)
from autodist_tpu.telemetry import spans as tel
from autodist_tpu.telemetry import device_counters, scopes

# what a routed layer sows into ``counters`` and the loss reports as the
# device counters ``moe.<name>``, summed over layers
ROUTER_LOAD = ("max_expert_pairs", "routed_pairs")
# ... and a layer that holds a share of its experts: every pair its
# router chose, held here or not
SHARE_LOAD = ROUTER_LOAD + ("chosen_pairs",)
# what a sparse attention's indexer sows and the loss reports as
# ``dsa.<name>``: the (query, key) pairs it chose, and all a query sees
INDEXER_CHOICE = ("selected_pairs", "causal_pairs")
LAYER_TYPES = ("attention", "kda", "mla", "conv", "mamba2", "moe")
# ``nemotron_h``'s ``hybrid_override_pattern``, a letter a layer (its "-", a
# dense feed-forward alone, is not built)
NEMOTRON_H_LAYERS = {"M": "mamba2", "*": "attention", "E": "moe"}
YARN_KEYS = tuple(f.name for f in dataclasses.fields(YarnConfig))


@dataclasses.dataclass
class LMConfig:
    vocab_size: int = 32000
    d_model: int = 512
    num_layers: int = 6
    num_heads: int = 8
    mlp_dim: int = 2048
    max_seq_len: int = 256
    dtype: Any = jnp.float32
    # Architecture, as a model's public config.json names it. The
    # defaults are the GPT-2 style model lm1b runs; a preset below sets
    # what its source publishes. These are not tuning knobs: each value
    # is another model, none selects between two ways to compute one.
    norm: str = "layernorm"         # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-6
    # rotary positions on q and k with this base; None = a learned table
    rope_theta: Optional[float] = None
    # the source's ``rope_scaling`` dict: None, or ``type: yarn`` with
    # YaRN's six numbers (blended frequencies and a softmax scale of its
    # own, on latent attention's rotary features)
    rope_scaling: Optional[dict] = None
    qk_norm: bool = False           # RMSNorm over the projected q and k
    # the softmax attention's heads where they are not ``num_heads`` of
    # ``d_model / num_heads``: a head's size of its own, fewer K/V heads
    # that groups of query heads share, and the RMSNorm of q and k taken
    # per head (one weight of ``head_dim``) instead of ``qk_norm``'s
    head_dim: Optional[int] = None
    num_kv_heads: Optional[int] = None
    qk_head_norm: bool = False
    # the softmax attention's output is gated: ``(o * sigmoid(u W_g)) W_o``
    # with a fifth projection ``W_g`` of the layer's normed input u to every
    # head's features (afmoe's gated attention)
    gated_attention: bool = False
    # a learned sparse attention (``sa_config``): > 0 heads of an indexer
    # that chooses ``indexer_topk`` keys for every query, its scores made
    # ``indexer_q_chunk`` queries at a time, ``indexer_rope_dim`` of its
    # features rotated; 0 = every query attends all it sees
    indexer_num_heads: int = 0
    indexer_head_dim: int = 0
    indexer_topk: int = 0
    indexer_q_chunk: int = 512
    indexer_rope_dim: int = 0
    attention_bias: bool = True
    head_bias: bool = True
    embed_scale: bool = True        # token embedding x sqrt(d_model)
    # logits = h E^T with E the token embedding: no ``lm_head`` of its own
    tie_embedding: bool = False
    # > 0: the feed-forward is a routed SwiGLU one, ``experts_per_token``
    # of ``num_experts`` experts each of width ``mlp_dim``, no token
    # dropped; 0: the GELU MLP of width ``mlp_dim``
    num_experts: int = 0
    experts_per_token: int = 0
    router_aux_loss_coef: float = 0.0   # load-balance loss, per layer
    router_z_loss_coef: float = 0.0
    # the balance loss taken per SEQUENCE and summed over the routed
    # layers (DeepSeek-V2's ``seq_aux``; ``router_aux_loss_coef`` is its
    # ``aux_loss_alpha``), not over all tokens and averaged over layers
    seq_aux: bool = False
    # A model whose layers differ. ``layer_types[i]`` is layer i's token
    # mixer: "attention" (the softmax attention above), "kda" (Kimi Delta
    # Attention: ``kda_*``), "mla" (latent attention: the four widths
    # below; rotary on ``qk_rope_head_dim`` features iff ``rope_theta``,
    # by ``rope_scaling``'s frequencies where it is given), "conv" (a
    # gated short convolution of ``conv_size`` taps, ``conv_L_cache``, without
    # a bias) or "mamba2" (a Mamba-2 state-space mixer: ``mamba_*`` and
    # ``ssm_state_size``).
    # None = "attention" in every layer.
    layer_types: Optional[Tuple[str, ...]] = None
    # every layer is ONE sub-layer behind one norm, ``x + f(N(x))``
    # (``nemotron_h``): an "attention" or "mamba2" layer is its mixer
    # alone, and ``layer_types`` may name a layer "moe", its routed
    # feed-forward alone
    single_sublayer: bool = False
    conv_size: int = 0
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 0
    ssm_state_size: int = 0
    mamba_conv_size: int = 0
    mamba_chunk: int = 0
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_size: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the first k layers' feed-forward is a dense SwiGLU of ``dense_dim``
    first_k_dense_replace: int = 0
    dense_dim: int = 0
    # "softmax": a probability over all experts, the router losses apply
    # (OLMoE, DeepSeek-V2); "sigmoid": a score per expert, chosen by
    # score + bias, no router loss (Kimi-Linear). Either way the gates
    # are the chosen scores, renormalised over the chosen where
    # ``moe_renormalize``, times ``routed_scaling_factor``
    router_activation: str = "softmax"
    moe_renormalize: bool = False
    routed_scaling_factor: float = 1.0
    num_shared_experts: int = 0     # SwiGLU experts every token passes
    # every expert, routed and shared, is ``down(silu(gate x) * up x)`` or,
    # False, ``down(relu(up x)^2)``: two matrices and no gate
    expert_gated: bool = True
    # the shared expert's own width; 0 = ``num_shared_experts x mlp_dim``
    shared_expert_dim: int = 0
    # the experts of ``num_experts`` whose weights THIS model holds: one
    # chip's share under expert parallelism (``parallel/expert.py``);
    # None = all. The router scores all ``num_experts`` either way.
    experts_held: Optional[Tuple[int, ...]] = None
    # A looped model (the Ouro family, arXiv 2510.25741): the WHOLE stack
    # of layers and the final norm run ``loop_steps`` times over the same
    # weights, each pass on the previous pass's normed output; the head
    # reads after every pass and an exit gate ``sigmoid(w x + b)`` on each
    # pass's state turns the passes' losses into one, less
    # ``exit_entropy_coef`` times the entropy of the exit distribution
    # (``make_train_setup``). 1 = every layer once, no gate.
    loop_steps: int = 1
    exit_entropy_coef: float = 0.0
    # each sub-layer's OUTPUT is normed too before it joins the residual
    # (four norms a block)
    sandwich_norm: bool = False
    # A model whose softmax-attention layers differ in what a query sees and
    # in their position signal, a flag a layer as a config.json publishes
    # them (SmallThinker's ``sliding_window_layout`` / ``rope_layout``):
    # layer i sees the latest ``sliding_window`` keys (its own position
    # counted) where ``window_layers[i]``, every earlier key where not; it
    # rotates q and k by ``rope_theta`` where ``rope_layers[i]``, and has no
    # position signal at all where not. None = no layer has a window / every
    # layer rotates where ``rope_theta`` is set.
    sliding_window: int = 0
    window_layers: Optional[Tuple[int, ...]] = None
    rope_layers: Optional[Tuple[int, ...]] = None
    # a routed layer's router reads what the block's FIRST norm produced (the
    # token mixer's input; "router before attention"), not the feed-forward's
    # own normed input, which the experts still read
    router_reads_mixer_input: bool = False
    # what a gated routed expert's gate passes: "silu" (SwiGLU) | "relu"
    # (ReGLU, ``down(relu(gate x) * up x)``)
    expert_gate_activation: str = "silu"

    def __post_init__(self):
        if self.num_experts and not (
                0 < self.experts_per_token <= self.num_experts):
            raise ValueError(
                "a routed feed-forward needs 0 < experts_per_token <= "
                "num_experts, got %d of %d" % (self.experts_per_token,
                                               self.num_experts))
        types = self.layer_types
        if types is not None and (
                len(types) != self.num_layers or set(types) - set(LAYER_TYPES)):
            raise ValueError(
                "layer_types names one of %s for each of the %d layers, got "
                "%r" % (LAYER_TYPES, self.num_layers, types))
        if ("conv" in (types or ())) != bool(self.conv_size):
            raise ValueError(
                "conv_size is the taps of the layers layer_types names "
                "'conv': got conv_size %d with %r" % (self.conv_size, types))
        mamba = (self.mamba_num_heads, self.mamba_head_dim,
                 self.mamba_n_groups, self.ssm_state_size,
                 self.mamba_conv_size, self.mamba_chunk)
        if not ((all(mamba)
                 and not self.mamba_num_heads % self.mamba_n_groups)
                if "mamba2" in (types or ()) else not any(mamba)):
            raise ValueError(
                "mamba_num_heads (a multiple of mamba_n_groups), "
                "mamba_head_dim, mamba_n_groups, ssm_state_size, "
                "mamba_conv_size and mamba_chunk are the sizes of the layers "
                "layer_types names 'mamba2': got %r with %r" % (mamba, types))
        single_only = {"mamba2", "moe"} & set(types or ())
        if single_only and not self.single_sublayer:
            raise ValueError(
                "%s layers are built as single sub-layers alone "
                "(single_sublayer): a block of a mixer AND a feed-forward "
                "has neither" % sorted(single_only))
        if self.single_sublayer:
            not_built = [what for what, on in (
                ("no layer_types", types is None),
                ("kda, mla or conv layers",
                 set(types or ()) - {"attention", "mamba2", "moe"}),
                ("loop_steps > 1", self.loop_steps > 1),
                ("sandwich_norm", self.sandwich_norm),
                ("an indexer", self.indexer_num_heads),
                ("a dense feed-forward layer (first_k_dense_replace, "
                 "dense_dim; the pattern's '-')",
                 self.first_k_dense_replace or self.dense_dim),
                ("'moe' layers without num_experts",
                 "moe" in (types or ()) and not self.num_experts)) if on]
            if not_built:
                raise ValueError(
                    "single_sublayer builds 'attention', 'mamba2' and routed "
                    "'moe' layers, each ONE sub-layer behind one norm; not "
                    "built with it: " + "; ".join(not_built))
        if self.tie_embedding and self.head_bias:
            raise ValueError("a tied head is h E^T alone: head_bias is set")
        if self.router_activation not in ("softmax", "sigmoid"):
            raise ValueError("router_activation must be softmax|sigmoid, got "
                             "%r" % (self.router_activation,))
        routed_only = (self.moe_renormalize, self.routed_scaling_factor != 1.0,
                       self.num_shared_experts, self.experts_held is not None,
                       not self.expert_gated, self.shared_expert_dim,
                       self.router_aux_loss_coef, self.router_z_loss_coef,
                       self.seq_aux)
        if not self.num_experts and any(routed_only):
            raise ValueError(
                "renormalised or scaled gates, shared experts, a share of "
                "the experts, their form and the router losses belong to a "
                "routed feed-forward: num_experts is 0")
        if self.shared_expert_dim and not self.num_shared_experts:
            raise ValueError("shared_expert_dim is the width of the shared "
                             "expert: num_shared_experts is 0")
        if self.router_activation == "sigmoid" and (
                self.router_aux_loss_coef or self.router_z_loss_coef
                or self.seq_aux):
            raise ValueError(
                "the router losses (router_aux_loss_coef, router_z_loss_coef, "
                "seq_aux) are those of a softmax router; a sigmoid router "
                "has none here")
        if self.seq_aux and self.router_z_loss_coef:
            raise ValueError(
                "seq_aux is DeepSeek-V2's balance loss, per sequence and "
                "summed over the routed layers: it comes without a z-loss")
        if self.rope_scaling is not None:
            scaling = dict(self.rope_scaling)
            if scaling.pop("type", None) != "yarn" \
                    or set(scaling) != set(YARN_KEYS):
                raise ValueError(
                    "rope_scaling is None or {type: 'yarn'} with %s, got %r"
                    % (", ".join(YARN_KEYS), self.rope_scaling))
            if self.rope_theta is None or set(types or ("attention",)) \
                    != {"mla"}:
                raise ValueError(
                    "rope_scaling (YaRN) blends the frequencies of latent "
                    "attention's rotary features: it needs rope_theta and "
                    "layer_types of 'mla' alone")
        if self.indexer_num_heads and not (
                self.indexer_head_dim and self.indexer_topk
                and self.indexer_rope_dim <= self.indexer_head_dim
                and set(types or ("attention",)) == {"attention"}):
            raise ValueError(
                "an indexer chooses keys for the softmax attention: it needs "
                "indexer_head_dim, indexer_topk and layer_types of "
                "'attention' alone")
        if self.gated_attention and "attention" not in (
                types or ("attention",)):
            raise ValueError(
                "gated_attention gates the softmax attention's output: "
                "layer_types %r names no 'attention' layer" % (types,))
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm (over all features) or qk_head_norm "
                             "(per head), not both")
        if self.loop_steps < 1 or (self.loop_steps == 1
                                   and self.exit_entropy_coef):
            raise ValueError(
                "loop_steps counts the passes over the stack (>= 1), and "
                "exit_entropy_coef belongs to the exit gate of a looped "
                "model: got loop_steps %d with exit_entropy_coef %g"
                % (self.loop_steps, self.exit_entropy_coef))
        if self.loop_steps > 1 and (self.num_experts
                                    or self.indexer_num_heads):
            raise ValueError(
                "loop_steps > 1 scans the stack over shared weights: a "
                "routed layer's losses and counters and an indexer's "
                "choice, sown once a pass, have no way out of the scan yet")
        for name, layout in (("window_layers", self.window_layers),
                             ("rope_layers", self.rope_layers)):
            if layout is not None and (
                    len(layout) != self.num_layers
                    or set(layout) - {0, 1}
                    or set(types or ("attention",)) != {"attention"}):
                raise ValueError(
                    "%s is a 0 / 1 flag for each of the %d layers of a model "
                    "of softmax attention alone, got %r with layer_types %r"
                    % (name, self.num_layers, layout, types))
        if bool(self.sliding_window) != bool(self.window_layers
                                             and any(self.window_layers)) \
                or self.sliding_window < 0:
            raise ValueError(
                "sliding_window (>= 1 keys, the query's own counted) is the "
                "window of the layers window_layers flags: got %d with %r"
                % (self.sliding_window, self.window_layers))
        if self.rope_layers is not None and self.rope_theta is None:
            raise ValueError("rope_layers flags the layers that rotate by "
                             "rope_theta, which is None")
        if self.expert_gate_activation not in ("silu", "relu"):
            raise ValueError("expert_gate_activation must be silu|relu, got "
                             "%r" % (self.expert_gate_activation,))
        if (self.router_reads_mixer_input
                or self.expert_gate_activation != "silu") and (
                not self.num_experts or self.single_sublayer
                or not self.expert_gated or self.num_shared_experts):
            raise ValueError(
                "router_reads_mixer_input and expert_gate_activation belong "
                "to a block of a mixer AND a routed feed-forward of gated "
                "experts without a shared one (a single sub-layer has no "
                "first norm to read; the shared SwiGLU's gate is SiLU)")
        held = self.experts_held
        if held is not None and (
                not held or len(set(held)) != len(held)
                or not all(0 <= e < self.num_experts for e in held)):
            raise ValueError("experts_held names distinct experts of the %d, "
                             "got %r" % (self.num_experts, held))

    @classmethod
    def lm1b(cls, **kw):
        return cls(vocab_size=793470 // 8, d_model=1024, num_layers=8,
                   num_heads=16, mlp_dim=4096, **kw)

    @classmethod
    def olmoe_1b_7b(cls, **kw):
        """OLMoE-1B-7B-0125-Instruct as its ``config.json`` publishes it
        (huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct; arXiv
        2409.02060): 16 layers of RMSNorm, QK-norm, RoPE, bias-free
        attention and a dropless top-8-of-64 SwiGLU feed-forward, an
        untied bias-free head. ``intermediate_size`` 1024 is one expert's
        width; the two router loss coefficients are the paper's."""
        kw.setdefault("num_layers", 16)
        kw.setdefault("max_seq_len", 4096)
        return cls(vocab_size=50304, d_model=2048, num_heads=16,
                   mlp_dim=1024, norm="rmsnorm", norm_eps=1e-5,
                   rope_theta=10000.0, qk_norm=True, attention_bias=False,
                   head_bias=False, embed_scale=False, num_experts=64,
                   experts_per_token=8, router_aux_loss_coef=0.01,
                   router_z_loss_coef=0.001, **kw)

    @classmethod
    def kimi_linear_48b_a3b(cls, **kw):
        """Kimi-Linear-48B-A3B-Instruct as its ``config.json`` publishes it
        (huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; arXiv
        2510.26692): 27 pre-norm RMSNorm layers without a bias or a
        position signal, three KDA layers (32 heads of 128, conv 4) to one
        NoPE latent-attention layer (32 heads, latent 512, 128 + 64 score
        features, values of 128); a dense SwiGLU of 9,216 in layer 1, then
        256 sigmoid-routed SwiGLU experts of 1,024, 8 a token,
        renormalised gates x 2.446, one shared expert; an untied head.
        ``num_layers`` cuts the published pattern from its start."""
        full_attn = (4, 8, 12, 16, 20, 24, 27)   # numbered from 1
        n = kw.setdefault("num_layers", 27)
        kw.setdefault("max_seq_len", 1048576)
        kw.setdefault("layer_types", tuple(
            "mla" if i + 1 in full_attn else "kda" for i in range(n)))
        return cls(vocab_size=163840, d_model=2304, num_heads=32,
                   mlp_dim=1024, norm="rmsnorm", norm_eps=1e-5,
                   attention_bias=False, head_bias=False, embed_scale=False,
                   kda_num_heads=32, kda_head_dim=128, kda_conv_size=4,
                   kv_lora_rank=512, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128,
                   first_k_dense_replace=1, dense_dim=9216,
                   num_experts=256, experts_per_token=8,
                   router_activation="sigmoid", moe_renormalize=True,
                   routed_scaling_factor=2.446, num_shared_experts=1, **kw)

    @classmethod
    def deepseek_v2_lite(cls, **kw):
        """DeepSeek-V2-Lite as its ``config.json`` publishes it
        (huggingface.co/deepseek-ai/DeepSeek-V2-Lite; arXiv 2405.04434): 27
        pre-norm RMSNorm layers without a bias, EVERY one latent attention
        (16 heads, no low-rank q, latent 512, 128 + 64 score features,
        values of 128) whose 64 rotary features turn by YaRN's blended
        frequencies (factor 40 over an original window of 4,096) under a
        softmax scale of its own; a dense SwiGLU of 10,944 in layer 0, then
        64 softmax-routed SwiGLU experts of 1,408, 6 a token, the gate the
        probability itself, beside 2 shared experts; the balance loss per
        sequence (``seq_aux``) times ``aux_loss_alpha`` 0.001, summed over
        the routed layers; an untied head."""
        n = kw.setdefault("num_layers", 27)
        kw.setdefault("max_seq_len", 163840)
        kw.setdefault("layer_types", ("mla",) * n)
        kw.setdefault("rope_scaling", {
            "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 0.707, "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096})
        return cls(vocab_size=102400, d_model=2048, num_heads=16,
                   mlp_dim=1408, norm="rmsnorm", norm_eps=1e-6,
                   rope_theta=10000.0, attention_bias=False, head_bias=False,
                   embed_scale=False, kv_lora_rank=512, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128,
                   first_k_dense_replace=1, dense_dim=10944, num_experts=64,
                   experts_per_token=6, num_shared_experts=2,
                   router_aux_loss_coef=0.001, seq_aux=True, **kw)

    @classmethod
    def keye_vl2_30b_a3b(cls, **kw):
        """Keye-VL-2.0-30B-A3B's language model as its ``config.json``
        publishes it (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B), text
        only: 48 pre-norm RMSNorm layers without a bias, each 32 query
        heads over 4 K/V heads of 128 (4,096 query features from a hidden
        size of 2,048) with a per-head RMSNorm of q and k and RoPE at theta
        1e7 (M-RoPE's sections are plain RoPE for text), a sparse
        attention indexer (16 heads of 64 over one key head) that chooses
        2,048 keys for every query, and 128 softmax-routed SwiGLU experts
        of 768, 8 a token, gates renormalised over the chosen, no shared
        expert; an untied head. The per-head norm and the indexer's
        LayerNorm and half-rotated features are assumptions the
        benchmark's configuration file lists."""
        kw.setdefault("num_layers", 48)
        kw.setdefault("max_seq_len", 262144)
        return cls(vocab_size=151936, d_model=2048, num_heads=32,
                   head_dim=128, num_kv_heads=4, qk_head_norm=True,
                   mlp_dim=768, norm="rmsnorm", norm_eps=1e-6,
                   rope_theta=1e7, attention_bias=False, head_bias=False,
                   embed_scale=False, indexer_num_heads=16,
                   indexer_head_dim=64, indexer_topk=2048,
                   indexer_q_chunk=512, indexer_rope_dim=32,
                   num_experts=128, experts_per_token=8,
                   moe_renormalize=True, **kw)

    @classmethod
    def lfm2_24b_a2b(cls, **kw):
        """LFM2-24B-A2B as its ``config.json`` publishes it
        (huggingface.co/LiquidAI/LFM2-24B-A2B, ``lfm2_moe``): 40 pre-norm
        RMSNorm layers without a bias, three gated short convolutions (3
        taps, ``conv_L_cache``) to one softmax attention (32 query heads of
        64 over 8 K/V heads, a per-head RMSNorm of q and k, RoPE at theta
        1e6), conv conv attention conv ten times; a dense SwiGLU of 11,776
        in layers 0-1 (``num_dense_layers``), then 64 sigmoid-routed SwiGLU
        experts of 1,536, 4 a token chosen by score + bias, gates
        renormalised over the chosen, no shared expert; the head is the
        embedding's transpose. ``num_layers`` cuts the published pattern
        from its start. The tied head and the per-head norm are the LFM2
        family's convention without a key in the row: assumptions the
        benchmark's configuration file lists."""
        n = kw.setdefault("num_layers", 40)
        kw.setdefault("max_seq_len", 128000)
        kw.setdefault("layer_types", tuple(
            "attention" if i % 4 == 2 else "conv" for i in range(n)))
        return cls(vocab_size=65536, d_model=2048, num_heads=32,
                   num_kv_heads=8, qk_head_norm=True, mlp_dim=1536,
                   norm="rmsnorm", norm_eps=1e-5, rope_theta=1e6,
                   attention_bias=False, head_bias=False, embed_scale=False,
                   tie_embedding=True, conv_size=3,
                   first_k_dense_replace=2, dense_dim=11776, num_experts=64,
                   experts_per_token=4, router_activation="sigmoid",
                   moe_renormalize=True, **kw)

    @classmethod
    def ouro_2_6b(cls, **kw):
        """Ouro-2.6B as its ``config.json`` publishes it
        (huggingface.co/ByteDance/Ouro-2.6B, ``model_type: ouro``; arXiv
        2510.25741): 48 layers of hidden 2,048 without a bias, 16 heads of
        128 over as many K/V heads, RoPE at theta 1e6 over all 128
        features, a SwiGLU of 5,632, RMSNorm eps 1e-6 before AND after each
        sub-layer, an untied head over 49,152 words; the whole stack and
        the final norm run ``total_ut_steps`` = 4 times over the same
        weights and an exit gate weighs the four losses. The sandwich
        norms, the normed state carried on, the gate's bias and the
        entropy term's 0.05 are the paper's, without a key in the row:
        assumptions the benchmark's configuration file lists."""
        n = kw.setdefault("num_layers", 48)
        kw.setdefault("max_seq_len", 65536)
        return cls(vocab_size=49152, d_model=2048, num_heads=16,
                   head_dim=128, mlp_dim=5632, first_k_dense_replace=n,
                   dense_dim=5632, norm="rmsnorm", norm_eps=1e-6,
                   rope_theta=1e6, attention_bias=False, head_bias=False,
                   embed_scale=False, loop_steps=4, exit_entropy_coef=0.05,
                   sandwich_norm=True, **kw)

    @classmethod
    def nemotron_twotower_30b_a3b(cls, **kw):
        """The Nemotron-H tower of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
        as its ``config.json`` publishes it
        (huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
        ``model_type: nemotron_h``): 52 layers of hidden 2,688 by
        ``hybrid_override_pattern``, each ONE sub-layer behind an RMSNorm
        (eps 1e-5), no bias on a projection: 23 Mamba-2 mixers (64 heads of
        64, 8 groups of B and C of 128, a 4-tap filter with a bias, chunks
        of 128), 6 softmax attentions (32 query heads over 2 K/V heads of
        128, no rotation and no position signal at all) and 23 routed
        feed-forwards (128 sigmoid-routed ``relu(up x)^2`` experts of 1,856,
        6 a token chosen by score + bias, gates renormalised x 2.5, beside
        one shared expert of 3,712); an untied head over 131,072 words.
        ``num_layers`` cuts the pattern from its start. The second,
        denoising tower of the release (adaLN modulation, attention that is
        bidirectional inside a block, conditioning across the towers) has
        no key in the row and is not built. The inner width from the heads,
        the gate before the grouped norm and the attention without rotation
        are ``nemotron_h``'s code without a key in the row: assumptions the
        benchmark's configuration file lists."""
        pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
        n = kw.setdefault("num_layers", len(pattern))
        kw.setdefault("max_seq_len", 262144)
        kw.setdefault("layer_types", tuple(
            NEMOTRON_H_LAYERS[c] for c in pattern[:n]))
        return cls(vocab_size=131072, d_model=2688, num_heads=32,
                   head_dim=128, num_kv_heads=2, mlp_dim=1856,
                   norm="rmsnorm", norm_eps=1e-5, attention_bias=False,
                   head_bias=False, embed_scale=False, single_sublayer=True,
                   mamba_num_heads=64, mamba_head_dim=64, mamba_n_groups=8,
                   ssm_state_size=128, mamba_conv_size=4, mamba_chunk=128,
                   num_experts=128, experts_per_token=6,
                   router_activation="sigmoid", moe_renormalize=True,
                   routed_scaling_factor=2.5, num_shared_experts=1,
                   expert_gated=False, shared_expert_dim=3712, **kw)

    @classmethod
    def smallthinker_21b_a3b(cls, **kw):
        """SmallThinker-21BA3B-Instruct as its ``config.json`` publishes it
        (huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct): 52
        pre-norm RMSNorm (eps 1e-6) layers of hidden 2,560 without a bias,
        28 query heads over 4 K/V heads of 128 (groups of SEVEN); by
        ``sliding_window_layout`` and ``rope_layout``, both [0, 1, 1, 1] x
        13, layer 4 n sees every earlier key and has NO position signal,
        layers 4 n + 1 .. 4 n + 3 see the latest 4,096 keys and rotate q
        and k over all 128 features at theta 1.5e6; 64 softmax-routed ReGLU
        experts of 768 (``down(relu(gate x) * up x)``), 6 a token, gates
        renormalised over the chosen, no shared expert, the router's logits
        taken from the ATTENTION's normed input ("router placed before
        attention"); an untied head over 151,936 words. ``num_layers`` cuts
        the two layouts from their start. The window that counts the query
        itself, the rotate-half pairing and the absence of a router loss are
        without a key in the row: assumptions the benchmark's configuration
        file lists."""
        n = kw.setdefault("num_layers", 52)
        kw.setdefault("max_seq_len", 16384)
        layout = tuple(int(i % 4 != 0) for i in range(n))
        kw.setdefault("window_layers", layout)
        kw.setdefault("rope_layers", layout)
        return cls(vocab_size=151936, d_model=2560, num_heads=28,
                   head_dim=128, num_kv_heads=4, mlp_dim=768,
                   norm="rmsnorm", norm_eps=1e-6, rope_theta=1.5e6,
                   sliding_window=4096, attention_bias=False,
                   head_bias=False, embed_scale=False, num_experts=64,
                   experts_per_token=6, moe_renormalize=True,
                   router_reads_mixer_input=True,
                   expert_gate_activation="relu", **kw)

    @classmethod
    def trinity_mini_26b_a3b(cls, **kw):
        """Trinity-Mini (26B-A3B) as its ``config.json`` publishes it
        (huggingface.co/arcee-ai/Trinity-Mini, ``model_type: afmoe``): 32
        layers of hidden 2,048 without a bias, FOUR RMSNorms (eps 1e-5) a
        block (each sub-layer's input AND output), 32 query heads over 4
        K/V heads of 128 with a per-head RMSNorm of q and k and a GATED
        output (``(o * sigmoid(u W_g)) W_o``, ``W_g`` 2,048 -> 32 x 128 on
        the layer's normed input); by ``layer_types``, three
        ``sliding_attention`` layers (the latest 2,048 keys, q and k rotated
        over all 128 features at theta 10,000) to one ``full_attention``
        layer (every earlier key, NO position signal); a dense SwiGLU of
        6,144 in layers 0-1 (``num_dense_layers``), then 128 sigmoid-routed
        SwiGLU experts of 1,024, 8 a token chosen by score + bias, gates
        renormalised over the chosen x 2.826 (``route_norm``,
        ``route_scale``), beside one shared expert; the embedding times
        sqrt(2,048) (``mup_enabled``); an untied head over 200,192 words.
        ``num_layers`` cuts ``layer_types`` from its start; a cut that
        starts elsewhere hands its own ``window_layers`` / ``rope_layers``.
        The gate, the per-head norm, the four norms and the global layers'
        missing rotation are the released implementation's without a key in
        the row: assumptions the benchmark's configuration file lists."""
        n = kw.setdefault("num_layers", 32)
        kw.setdefault("max_seq_len", 131072)
        layout = tuple(int(i % 4 != 3) for i in range(n))
        kw.setdefault("window_layers", layout)
        kw.setdefault("rope_layers", layout)
        return cls(vocab_size=200192, d_model=2048, num_heads=32,
                   head_dim=128, num_kv_heads=4, qk_head_norm=True,
                   gated_attention=True, mlp_dim=1024, norm="rmsnorm",
                   norm_eps=1e-5, rope_theta=10000.0, sliding_window=2048,
                   attention_bias=False, head_bias=False, embed_scale=True,
                   sandwich_norm=True, first_k_dense_replace=2,
                   dense_dim=6144, num_experts=128, experts_per_token=8,
                   router_activation="sigmoid", moe_renormalize=True,
                   routed_scaling_factor=2.826, num_shared_experts=1, **kw)

    @classmethod
    def tiny(cls, **kw):
        return cls(vocab_size=128, d_model=32, num_layers=2, num_heads=2,
                   mlp_dim=64, max_seq_len=64, **kw)


class TransformerLM(nn.Module):
    config: LMConfig
    attn_fn: Optional[Any] = None
    seq_parallel: bool = False  # offset positions by the seq-shard index
    decode_attn: str = "reference"  # decode inner loop: "reference"|"flash"
    # recompute each block in the backward pass (make_train_setup's rule)
    remat_blocks: bool = False
    # ... of whose routed layers the LAST this many keep their held
    # experts' hidden products, of whose dense layers the LAST this many
    # their feed-forward's, of whose sandwich-normed layers the LAST this
    # many what their two output norms read, of whose routed layers the
    # LAST this many their shared experts' hidden products, of whose
    # layers with a sequence mixer (Mamba-2, KDA, the gated convolution) the
    # LAST this many what the mixer's input projections made, and of whose
    # gated softmax-attention layers the LAST this many their gate
    # projection's product (``auto_kept_layers``'s rule for all six)
    kept_expert_layers: int = 0
    kept_dense_layers: int = 0
    kept_sublayer_out_layers: int = 0
    kept_shared_layers: int = 0
    kept_mixer_in_layers: int = 0
    kept_attn_gate_layers: int = 0

    def _embed(self, input_ids, positions):
        """Token embedding (scaled by sqrt(d) where the config says so)
        plus, for learned positions, the table's rows at ``positions``
        [1 or B, S]; rotary positions enter in the blocks' attention."""
        cfg = self.config
        # untied lm_head -> the token table can ride the sparse wire
        with scopes.scope(scopes.EMBED):
            x = SparseEmbed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                            name="embed")(input_ids)
            if cfg.embed_scale:
                x = x * np.sqrt(cfg.d_model)
            if cfg.rope_theta is None and cfg.layer_types is None:
                pos = SparseEmbed(cfg.max_seq_len, cfg.d_model,
                                  dtype=cfg.dtype,
                                  name="pos_embed")(positions)
                x = x + pos
        return x

    def _block(self, i, **kw):
        """Layer i's block: what the config says of THIS layer (its token
        mixer, a leading dense feed-forward) on top of what every layer
        shares. Under ``remat_blocks`` its activations are recomputed in
        the backward pass."""
        cfg = self.config
        kind = "attention" if cfg.layer_types is None else cfg.layer_types[i]
        if kind == "kda":
            kw["kda"] = KDAConfig(cfg.kda_num_heads, cfg.kda_head_dim,
                                  cfg.kda_conv_size)
        elif kind == "mla":
            yarn = cfg.rope_scaling and YarnConfig(
                **{k: cfg.rope_scaling[k] for k in YARN_KEYS})
            kw["mla"] = MLAConfig(cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                                  cfg.qk_rope_head_dim, cfg.v_head_dim,
                                  cfg.rope_theta, yarn)
        elif kind == "conv":
            kw["conv_size"] = cfg.conv_size
        elif kind == "mamba2":
            kw["mamba"] = Mamba2Config(
                cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
                cfg.ssm_state_size, cfg.mamba_conv_size, cfg.mamba_chunk)
        if cfg.single_sublayer:
            kw["only"] = "ffn" if kind == "moe" else "mixer"
        if cfg.window_layers is not None and cfg.window_layers[i]:
            kw["window"] = cfg.sliding_window
        if kind == "attention" and (cfg.num_kv_heads or cfg.qk_head_norm
                                    or cfg.indexer_num_heads):
            kw.update(num_kv_heads=cfg.num_kv_heads,
                      qk_head_norm=cfg.qk_head_norm,
                      indexer=IndexerConfig(
                          cfg.indexer_num_heads, cfg.indexer_head_dim,
                          cfg.indexer_topk, cfg.indexer_q_chunk,
                          cfg.indexer_rope_dim)
                      if cfg.indexer_num_heads else None)
        if i < cfg.first_k_dense_replace:
            kw["dense_dim"] = cfg.dense_dim
        elif cfg.num_experts:
            kw["router"] = RouterConfig(
                cfg.router_activation, cfg.moe_renormalize,
                cfg.routed_scaling_factor, cfg.num_shared_experts,
                cfg.experts_held, cfg.seq_aux, cfg.expert_gated,
                cfg.shared_expert_dim, cfg.expert_gate_activation,
                cfg.router_reads_mixer_input)
        # (what a core's backward kernels need of its forward kernel is
        # kept by name, or a recomputed block would run the core a second
        # time only to make it again: the delta rule's output and
        # per-chunk states; the flash kernel's output and log-sum-exp,
        # 33.5 MB a layer at 16 heads of 128 and seq 8192 against a
        # forward kernel of 3.55 ms, and its q, 50 MB against 1.5 ms of
        # projection and rotation; a sparse attention's choice of keys,
        # 67 MB a layer at seq 8192 against its index scores and a choice
        # among them for every query; the state-space scan's output and
        # the state that enters each chunk, 67 + 134 MB a layer at 64
        # heads of 64 x 128 and seq 8192. A name no op of the block carries
        # keeps nothing. The held experts' gate and up products are the
        # other way round, 4 T E f bytes a layer, 369 MB at 8 experts of
        # 1,408 on 8,192 tokens, against two of the layer's eleven expert
        # matmuls: the last ``kept_expert_layers`` routed layers keep
        # them, whose products live shortest. A dense feed-forward's are
        # the same trade, 4 T f bytes a layer APPLICATION, 92 MB at 5,632
        # wide on 4,096 tokens, against two of its eleven matmuls: the
        # last ``kept_dense_layers`` dense layers keep them, a looped
        # model's in every pass; a routed layer's SHARED experts are one
        # SwiGLU under the dense name, 92 MB at two of 1,408 on 8,192
        # tokens, kept in the last ``kept_shared_layers`` routed layers.
        # What a sandwich-normed block's two output norms READ, 2 T d bytes
        # an application each, 16.8 MB at 4,096 x 2,048, against the
        # attention's output product and the feed-forward's down
        # projection, which the norms' backward would otherwise have the
        # recomputed forward make again: the last
        # ``kept_sublayer_out_layers`` layers keep both. What a sequence
        # mixer's input projections made, as the mixer's element-wise pass
        # reads it and that pass's backward reads it again: 169 MB a layer
        # at Mamba-2's 10,304 rows on 8,192 tokens against an ``in_proj`` of
        # 0.45 TFLOP, 206 MB at KDA's q, k and v of 4,096 against three such
        # products, 134 MB at a gated convolution's 6,144 and its gated
        # product's 2,048: the last ``kept_mixer_in_layers`` layers that have
        # such a mixer keep it. A gated attention's gate projection's
        # product, 134 MB a layer at 32 heads of 128 on 16,384 tokens
        # against a projection of 0.27 TFLOP: the last
        # ``kept_attn_gate_layers`` gated layers keep it)
        from autodist_tpu.ops.dsa import KEPT as DSA_CHOICE_KEPT
        from autodist_tpu.ops.flash_attention import KEPT as FLASH_CORE_KEPT
        from autodist_tpu.ops.ssd import KEPT as SSD_CORE_KEPT
        from autodist_tpu.parallel.expert import KEPT as HELD_EXPERTS_KEPT
        kept = (KDA_CORE_OUT, FLASH_CORE_KEPT, DSA_CHOICE_KEPT, SSD_CORE_KEPT)
        # (the last k routed layers are the last k layers that ARE routed)
        routed = routed_layer_indices(cfg)
        last = lambda k: routed[max(0, len(routed) - k):]  # noqa: E731
        if i in last(self.kept_expert_layers):
            kept += (HELD_EXPERTS_KEPT,)
        dense = num_dense_layers(cfg)
        if (dense - self.kept_dense_layers <= i < dense
                or i in last(self.kept_shared_layers)):
            kept += (DENSE_FFN_KEPT,)
        if i >= cfg.num_layers - self.kept_sublayer_out_layers:
            kept += (SUBLAYER_OUT_KEPT,)
        mixers = mixer_in_layer_indices(cfg)
        if i in mixers[max(0, len(mixers) - self.kept_mixer_in_layers):]:
            kept += (MIXER_IN_KEPT,)
        gated = gated_attention_layer_indices(cfg)
        if i in gated:
            kw["gated_attention"] = True
        if i in gated[max(0, len(gated) - self.kept_attn_gate_layers):]:
            kept += (ATTN_GATE_KEPT,)
        block = nn.remat(
            TransformerBlock,
            policy=jax.checkpoint_policies.save_only_these_names(*kept)
        ) if self.remat_blocks else TransformerBlock
        return block(
            cfg.num_heads, cfg.head_dim or cfg.d_model // cfg.num_heads,
            cfg.mlp_dim,
            dtype=cfg.dtype, norm=cfg.norm, norm_eps=cfg.norm_eps,
            attention_bias=cfg.attention_bias, qk_norm=cfg.qk_norm,
            rope_theta=layer_rope_theta(cfg, i),
            num_experts=cfg.num_experts,
            experts_per_token=cfg.experts_per_token,
            sandwich_norm=cfg.sandwich_norm, name="layer_%d" % i, **kw)

    def _final_norm(self, x):
        cfg = self.config
        return make_norm(cfg.norm, cfg.norm_eps, cfg.dtype, "final_ln")(x)

    def _head(self, x):
        cfg = self.config
        if cfg.tie_embedding:
            # (``_embed`` ran before on every path: the table is there)
            table = self.get_variable("params", "embed")["embedding"]
            return jnp.dot(x.astype(jnp.float32),
                           table.astype(jnp.float32).T)
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        use_bias=cfg.head_bias, name="lm_head")(x)

    def _looped(self, x, mask, positions):
        """A looped model's passes: all the blocks and the final norm as
        ONE body scanned ``loop_steps`` times with the parameters
        broadcast, so that the trace holds each block once whatever the
        passes; the compiler sees them unrolled, as straight-line code
        (one loop read 1.3 % slower a step on the v5e with 5.2 GB more
        scratch, PERF.md section 6, PR 44). Carries the normed state and
        returns every pass's, [T, B, S, d]. Each
        block stays recomputed (with its kept names) where
        ``remat_blocks`` says so: the scan then saves a block's input and
        what it keeps, once a pass."""
        cfg = self.config

        def one_pass(model, x, _):
            for i in range(cfg.num_layers):
                x = model._block(i, attn_fn=model.attn_fn)(
                    x, mask, positions=positions)
            x = model._final_norm(x)
            return x, x

        with scopes.scope(scopes.LOOP):
            _, states = nn.scan(
                one_pass, variable_broadcast="params",
                split_rngs={"params": False}, length=cfg.loop_steps,
                unroll=cfg.loop_steps)(self, x, None)
        return states

    def _refuse_a_looped_model(self, what):
        if self.config.loop_steps > 1:
            raise NotImplementedError(
                "%s keeps one K/V cache a layer: loop_steps = %d passes "
                "need a cache per (pass, layer) and an exit rule, which "
                "serving does not have yet" % (what, self.config.loop_steps))

    @nn.compact
    def hidden(self, input_ids):
        """Final-layer-norm hidden states [B, S, d] — the lean-head loss
        applies the lm_head itself through ``ops.xent`` so the [N, vocab]
        logits tensor never materializes. A looped model (``loop_steps``
        > 1) returns the normed state after EVERY pass, [T, B, S, d]."""
        seq_len = input_ids.shape[-1]  # LOCAL length under seq sharding
        positions = jnp.arange(seq_len)
        if self.seq_parallel:
            from autodist_tpu.parallel import sequence
            positions = positions + sequence.position_offset(seq_len)
        x = self._embed(input_ids, positions[None])
        # with an injected SP attention the causal structure is handled
        # inside the op; the local mask would be wrong and is skipped
        mask = None if self.attn_fn is not None else causal_mask(seq_len)
        with scopes.scope(scopes.BLOCKS):
            if self.config.loop_steps > 1:
                states = self._looped(x, mask, positions)
                if self.is_initializing():
                    self._exit_gate(states[0])
                return states
            for i in range(self.config.num_layers):
                x = self._block(i, attn_fn=self.attn_fn)(
                    x, mask, positions=positions)
        return self._final_norm(x)

    def _exit_gate(self, x):
        """A looped model's exit gate on one pass's normed state: the
        logit of ``lambda = sigmoid(w x + b)``, float32 (the loss applies
        it to all the passes at once from the parameters themselves, as
        the lean head takes ``lm_head``'s)."""
        return nn.Dense(1, dtype=jnp.float32, name="exit_gate")(x)[..., 0]

    @nn.compact
    def __call__(self, input_ids):
        """Logits [B, S, vocab]; a looped model's are the LAST pass's."""
        h = self.hidden(input_ids)
        with scopes.scope(scopes.PLAIN_HEAD):
            return self._head(h[-1] if self.config.loop_steps > 1 else h)

    @nn.compact
    def prefill(self, input_ids, length):
        """Prompt pass seeding a decode KV cache (continuous batching,
        ``serving/decode.py``): ``input_ids`` [B, P] right-padded
        prompts, ``length`` [B] real prompt lengths. Returns the
        last-real-position logits [B, vocab] plus per-layer K/V caches
        [B, layers, max_seq_len, heads, head_dim]. Causality makes the
        padding harmless: position ``length-1`` attends only real
        tokens, and the garbage rows past ``length`` sit above the
        decode cursor, so :func:`ops.attention.cached_attention` never
        reads them before a decode step overwrites them. Submodules are
        created in exactly :meth:`hidden`'s order so the training
        parameters resolve unchanged."""
        self._refuse_a_looped_model("prefill")
        cfg = self.config
        seq_len = input_ids.shape[-1]
        positions = jnp.arange(seq_len)
        x = self._embed(input_ids, positions[None])
        mask = None if self.attn_fn is not None else causal_mask(seq_len)
        ks, vs = [], []
        with scopes.scope(scopes.BLOCKS):
            for i in range(cfg.num_layers):
                x, (k, v) = self._block(
                    i, attn_fn=self.attn_fn, decode_attn=self.decode_attn)(
                    x, mask, return_kv=True, positions=positions)
                pad = [(0, 0), (0, cfg.max_seq_len - seq_len), (0, 0),
                       (0, 0)]
                ks.append(jnp.pad(k, pad))
                vs.append(jnp.pad(v, pad))
        x = self._final_norm(x)
        idx = jnp.clip(length - 1, 0, seq_len - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return self._head(last), jnp.stack(ks, axis=1), jnp.stack(vs, axis=1)

    @nn.compact
    def decode_step(self, token_ids, k_cache, v_cache, cursor, alive=None):
        """One cached decode step: ``token_ids`` [B] current tokens,
        caches [B, layers, max_seq_len, heads, head_dim], ``cursor`` [B]
        the row each token writes (== tokens already cached), ``alive``
        [B] bool gating cache writes for dead slots. Returns next-token
        logits [B, vocab] and the updated caches. Fixed shapes for any
        slot occupancy — the zero-recompile decode contract."""
        self._refuse_a_looped_model("decode_step")
        cfg = self.config
        positions = jnp.clip(cursor, 0, cfg.max_seq_len - 1)[:, None]
        x = self._embed(token_ids[:, None], positions)
        new_ks, new_vs = [], []
        with scopes.scope(scopes.BLOCKS):
            for i in range(cfg.num_layers):
                x, (k, v) = self._block(
                    i, attn_fn=None, decode_attn=self.decode_attn)(
                    x, cache=(k_cache[:, i], v_cache[:, i]),
                    cursor=cursor, alive=alive, positions=positions)
                new_ks.append(k)
                new_vs.append(v)
        x = self._final_norm(x)
        return (self._head(x[:, 0]), jnp.stack(new_ks, axis=1),
                jnp.stack(new_vs, axis=1))


def auto_flash_attention(seq_len: int, head_dim: int, backend: str) -> bool:
    """The ``attention="auto"`` rule: does the causal self-attention of a
    training step lower through ``ops/flash_attention.py``? Decided from
    the shapes and the backend alone, from readings of the whole train
    step on a TPU v5e (PERF.md section 6, PR 28;
    ``benchmark/records/pr28_runs.jsonl``), forced ``"flash"`` against
    ``"default"``:

    - heads of 128 (OLMoE's block, 8,192 tokens a step), sequences of
      whole 512-row tiles: the kernel wins by +1.3 % of the step's
      tokens/s at seq 512, +2.9 % at 1024, +6.5 % at 2048 (XLA's softmax
      passes the [B, H, S, S] scores through HBM: 19.4 ms of attention a
      step against 11.5). Shorter sequences at this width, and lengths
      that leave the kernel a smaller tile (1000 tiles by 8 rows; alone,
      128-row tiles lost to XLA, 20.5 against 15.5 ms), are not measured
      and stay on XLA.
    - heads of 64 at seq 256 (``lm1b_train_1chip``): the kernel LOSES 4.5 %
      (scores of 134 MB a layer, one 256-row tile a head). Narrow heads at
      long sequences are measured by no cell (alone, the kernel wins there
      from seq 1024: 5.1 against 10.6 ms, not a step reading).
    - from seq 8192 every width and length, as before this rule: XLA's
      scores stop fitting in HBM there, so this is memory and not speed.
      Heads of 64 there ARE read in a step since PR 40
      (``lfm2_24b_a2b_train_1chip``, [1, 8192, 32, 64] over 8 K/V heads,
      one layer; ``benchmark/records/pr40_runs.jsonl``): ``flash_fwd``
      4.56 ms and ``flash_bwd`` 9.60 ms a launch, 15.95 ms under the
      core's scope with the layout passes = 26 % of the bf16 peak by the
      model's causal FLOPs, what heads of 128 cost a head (Keye-VL-2.0's
      32 heads of 128 with a selection: 5.21 + 9.69): a live tile's
      contraction over 64 features fills half the MXU's depth, so half
      the FLOPs buy no time. 5.5 % of that step; no rule or tile changed.
    - any backend but a TPU: XLA (the kernel would run interpreted)."""
    if backend != "tpu":
        return False
    if seq_len >= 8192:
        return True
    from autodist_tpu.ops.flash_attention import full_tiles
    return head_dim >= 128 and full_tiles(seq_len)


# logits of [tokens, vocab] float32 from which the loss goes through the
# chunked head whatever the vocabulary: half a GiB, and the log-softmax
# and its gradient are as large again
LEAN_HEAD_LOGIT_BYTES = 1 << 29


def auto_remat_blocks(param_count: int, num_layers: int,
                      hbm_bytes: Optional[float],
                      loop_steps: int = 1) -> bool:
    """Is each block recomputed in the backward pass (``nn.remat`` around
    the block; ``strategy/remat.py`` checkpoints the whole loss, which
    does not lower the peak of a deep model)? Where the training state
    alone, at this repo's 16 B a parameter (float32 master weight, Adam's
    two moments, the gradient), takes over half the chip's memory and
    there is more than one block APPLICATION to keep activations of. The
    line says: a state under half the chip leaves the blocks' activations
    the other half. A looped model applies every block ``loop_steps``
    times, so the same parameters make that many times the activations,
    and it is held to the state plus as much again for EACH pass: ``16 B
    x parameters x (1 + loop_steps)`` over the chip (one pass: twice the
    state, the line above). ``hbm_bytes`` None (no TPU): never."""
    return (hbm_bytes is not None and num_layers * loop_steps > 1
            and 16.0 * param_count * (1 + loop_steps) > hbm_bytes)


# of the chip's memory by the chip table, what the training state at 12 B a
# parameter (float32 master weight, Adam's two moments: all that is at rest
# since set-up lets go of the caller's initial parameters,
# ``model_item.py:ModelItem.release_params``) and the kept values together
# leave to the step's other scratch. Drawn where the v5e has LOADED a step,
# cold and from the compile cache (PERF.md section 6, PR 41), WITH that dead
# copy of the parameters still on the chip and so booked at 16 B a parameter:
# DeepSeek-V2-Lite's cell with all five layers' held products kept was 0.751
# of 16e9 by that count and 15.35 GB on the chip, at rest + scratch, the
# fullest of the five cells that recompute (Ouro's 15.20, Kimi-Linear's
# 14.98, Keye-VL-2.0's 14.32, LFM2's 13.20:
# benchmark/records/pr45_aot_memory.json). Nothing has been seen to fail, so
# the line says what has been shown, not what is possible, and it stays where
# it was drawn: the room grows by exactly the 4 B a parameter that left the
# chip, so the rule books at most that much more than went, and no cell
# stands above what it stood at with the copy (the fullest is now
# DeepSeek-V2-Lite's 13.63 GB device-less, Ouro's 13.34:
# benchmark/records/pr46_aot_memory.json). ``auto_remat_blocks``' line keeps
# its 16 B: at 12 DeepSeek-V2-Lite's cell would stop recomputing, which does
# not fit.
KEPT_EXPERTS_HBM_LEFT = 0.24


def layer_rope_theta(cfg: LMConfig, i: int) -> Optional[float]:
    """Layer i's rotation: the model's, where ``rope_layers`` names no
    layers or names this one."""
    return (cfg.rope_theta if cfg.rope_layers is None or cfg.rope_layers[i]
            else None)


def num_dense_layers(cfg: LMConfig) -> int:
    """The leading layers whose feed-forward is the dense SwiGLU."""
    return min(cfg.first_k_dense_replace, cfg.num_layers) if cfg.dense_dim \
        else 0


def routed_layer_indices(cfg: LMConfig) -> Tuple[int, ...]:
    """The layers whose feed-forward is routed, by index: every layer after
    the leading dense ones, or under ``single_sublayer`` those
    ``layer_types`` names "moe" (they lie BETWEEN the mixers there)."""
    if not cfg.num_experts:
        return ()
    if cfg.single_sublayer:
        return tuple(i for i, t in enumerate(cfg.layer_types) if t == "moe")
    return tuple(range(min(cfg.first_k_dense_replace, cfg.num_layers),
                       cfg.num_layers))


# the token mixers whose input projections' outputs carry
# ``models/layers.py:MIXER_IN_KEPT``, by their name in ``layer_types``
SEQUENCE_MIXERS = ("mamba2", "kda", "conv")


def mixer_in_layer_indices(cfg: LMConfig) -> Tuple[int, ...]:
    """The layers whose token mixer is one of :data:`SEQUENCE_MIXERS`, by
    index."""
    return tuple(i for i, t in enumerate(cfg.layer_types or ())
                 if t in SEQUENCE_MIXERS)


def gated_attention_layer_indices(cfg: LMConfig) -> Tuple[int, ...]:
    """The softmax-attention layers whose output is gated (their ``gate``
    projection's product carries ``models/layers.py:ATTN_GATE_KEPT``), by
    index."""
    if not cfg.gated_attention:
        return ()
    types = cfg.layer_types or ("attention",) * cfg.num_layers
    return tuple(i for i, t in enumerate(types) if t == "attention")


def mixer_in_width(cfg: LMConfig) -> int:
    """The features a token that ONE such layer keeps under
    :data:`models.layers.MIXER_IN_KEPT`, from the widths: Mamba-2's
    ``in_proj`` output ``[z | xBC | dt]`` (2 H P + 2 G N + H); KDA's q, k
    and v (H d each), the narrow halves of its two low-rank pairs (d each)
    and b's H; the gated convolution's ``[B | C | u]`` and its gated product
    (3 d_model + d_model). 0 without such a layer. No model of the zoo has
    two kinds; one that had would be booked by the wider."""
    widths = {
        "mamba2": (2 * cfg.mamba_num_heads * cfg.mamba_head_dim
                   + 2 * cfg.mamba_n_groups * cfg.ssm_state_size
                   + cfg.mamba_num_heads),
        "kda": (3 * cfg.kda_num_heads * cfg.kda_head_dim
                + 2 * cfg.kda_head_dim + cfg.kda_num_heads),
        "conv": 4 * cfg.d_model,
    }
    return max((widths[t] for t in SEQUENCE_MIXERS
                if t in (cfg.layer_types or ())), default=0)


class KeptLayers(NamedTuple):
    """:func:`auto_kept_layers`' counts, the LAST so many layers of each
    kind, in the order they are booked."""
    experts: int = 0        # routed layers, their held experts' products
    dense: int = 0          # dense layers, their SwiGLU's products
    sublayer_outs: int = 0  # sandwich-normed layers, the output norms' inputs
    shared: int = 0         # routed layers, their shared experts' products
    mixer_in: int = 0       # sequence-mixer layers, the input projections'
    attn_gate: int = 0      # gated attention layers, the gate projection's


def auto_kept_layers(remat_blocks: bool, param_count: int,
                     hbm_bytes: Optional[float], tokens: int,
                     itemsize: int = 2, *, routed_layers: int = 0,
                     held_stack: Optional[Tuple[int, int, int]] = None,
                     dense_layers: int = 0, dense_width: int = 0,
                     sandwich_layers: int = 0, d_model: int = 0,
                     shared_width: int = 0, loop_steps: int = 1,
                     core_bytes: int = 0, expert_products: int = 2,
                     mixer_layers: int = 0, mixer_width: int = 0,
                     gate_layers: int = 0,
                     gate_width: int = 0) -> KeptLayers:
    """Of a recomputed model's layers, how many keep by name what the
    recomputed forward would otherwise make a second time only for the
    backward to read (``TransformerLM._block`` saves the names in the LAST
    so many layers of each kind). ONE booking of ONE room, what the state at
    12 B a parameter leaves under ``KEPT_EXPERTS_HBM_LEFT`` of the chip's
    memory free; whole layers, ``loop_steps`` applications each, as many as
    fit, in this order:

    - the held experts' gate and up products (``parallel/expert.py:KEPT``):
      two ``[tokens, E, f]`` arrays of ``itemsize`` bytes a routed layer
      (``expert_products``: ONE where the experts have no gate, and so for
      the shared experts below), ``held_stack`` being the held up stack's
      ``[E, d, f]``. None where
      no share is held (``held_stack`` None: the sorted form's grouped
      matmuls carry no name);
    - from what they leave, less ``core_bytes`` (what the flash cores and
      the state-space scans keep by name in any case,
      :func:`flash_kept_bytes` and :func:`ssd_kept_bytes` an application), the
      dense feed-forwards' (``models/layers.py:DENSE_FFN_KEPT``): two
      ``[tokens, dense_width]`` arrays an application of a dense layer;
    - what a sandwich-normed block's two output norms read
      (``models/layers.py:SUBLAYER_OUT_KEPT``): two ``[tokens, d_model]``
      arrays an application, for the attention's output product and the
      feed-forward's down projection;
    - the shared experts' SwiGLU (the dense name in a routed block): two
      ``[tokens, shared_width]`` arrays a routed layer;
    - what a sequence mixer's input projections made
      (``models/layers.py:MIXER_IN_KEPT``): ``[tokens, mixer_width]``
      (:func:`mixer_in_width`) an application of each of the
      ``mixer_layers`` layers that have such a mixer;
    - a gated attention's gate projection's product
      (``models/layers.py:ATTN_GATE_KEPT``): ``[tokens, gate_width]`` (heads
      x head_dim) an application of each of the ``gate_layers`` gated
      layers.

    All 0 where blocks are not recomputed (nothing is made twice) and off a
    TPU."""
    if not remat_blocks or hbm_bytes is None:
        return KeptLayers()
    room = max(0.0, (1.0 - KEPT_EXPERTS_HBM_LEFT) * hbm_bytes
               - 12.0 * param_count)
    a_layer = kept_layer_bytes(tokens, itemsize, held_stack, dense_width,
                               d_model, shared_width, loop_steps,
                               expert_products, mixer_width, gate_width)

    def book(layers, nbytes):
        nonlocal room
        kept = int(min(layers, room // nbytes)) if layers and nbytes else 0
        room -= kept * nbytes
        return kept

    experts = book(routed_layers, a_layer.experts)
    room = max(0.0, room - core_bytes)
    return KeptLayers(experts, book(dense_layers, a_layer.dense),
                      book(sandwich_layers, a_layer.sublayer_outs),
                      book(routed_layers, a_layer.shared),
                      book(mixer_layers, a_layer.mixer_in),
                      book(gate_layers, a_layer.attn_gate))


def kept_layer_bytes(tokens: int, itemsize: int,
                     held_stack: Optional[Tuple[int, int, int]],
                     dense_width: int, d_model: int, shared_width: int,
                     loop_steps: int = 1, expert_products: int = 2,
                     mixer_width: int = 0,
                     gate_width: int = 0) -> KeptLayers:
    """What ONE layer of each kind keeps by name over all its
    ``loop_steps`` applications (0 for a kind the model has none of)."""
    return KeptLayers(
        held_expert_kept_bytes(tokens, held_stack, itemsize, expert_products)
        if held_stack is not None else 0,
        loop_steps * dense_kept_bytes(tokens, dense_width, itemsize),
        loop_steps * sublayer_out_kept_bytes(tokens, d_model, itemsize),
        loop_steps * dense_kept_bytes(tokens, shared_width, itemsize,
                                      expert_products),
        loop_steps * itemsize * tokens * mixer_width,
        loop_steps * itemsize * tokens * gate_width)


def flash_kept_bytes(tokens: int, num_heads: int, qk_dim: int, v_dim: int,
                     itemsize: int = 2) -> int:
    """What ONE application of a block on the flash kernels keeps under
    :data:`ops.flash_attention.KEPT`: q ``[tokens, heads, qk_dim]`` and the
    output ``[tokens, heads, v_dim]`` of ``itemsize`` bytes, the
    log-sum-exp ``[heads, tokens]`` in float32."""
    return tokens * num_heads * (itemsize * (qk_dim + v_dim) + 4)


def ssd_kept_bytes(batch_size: int, seq_len: int, num_heads: int,
                   head_dim: int, state_size: int, chunk: int,
                   itemsize: int = 2) -> int:
    """What ONE Mamba-2 layer on the scan kernels keeps under
    :data:`ops.ssd.KEPT`: y ``[B, S, heads, head_dim]`` of ``itemsize``
    bytes and the float32 state ``[heads, head_dim, state_size]`` that
    enters each of the padded sequence's chunks."""
    padded = seq_len + -seq_len % chunk
    return batch_size * num_heads * head_dim * (
        padded * itemsize + padded // chunk * state_size * 4)


def held_expert_kept_bytes(tokens: int, held_stack: Tuple[int, int, int],
                           itemsize: int = 2, products: int = 2) -> int:
    """What one routed layer keeps under :data:`parallel.expert.KEPT`:
    ``products`` hidden arrays (gate and up; up alone without a gate)."""
    n_held, _, width = held_stack
    return products * itemsize * tokens * n_held * width


def dense_kept_bytes(tokens: int, width: int, itemsize: int = 2,
                     products: int = 2) -> int:
    """What ONE application of a feed-forward of this width (a dense
    layer's SwiGLU, a routed layer's shared experts') keeps under
    :data:`models.layers.DENSE_FFN_KEPT`: ``products`` hidden arrays."""
    return products * itemsize * tokens * width


def sublayer_out_kept_bytes(tokens: int, d_model: int,
                            itemsize: int = 2) -> int:
    """What ONE application of a sandwich-normed block keeps under
    :data:`models.layers.SUBLAYER_OUT_KEPT`: both output norms' inputs."""
    return 2 * itemsize * tokens * d_model


def exit_log_distribution(gate_logits):
    """``log p^t`` [T, N] of a looped model's exit distribution from the
    exit gate's logits after passes 1 .. T-1, [T-1, N] float32 (the last
    pass exits whatever its gate says): with ``lambda^t = sigmoid(g^t)``
    and ``S^t = prod_{s <= t} (1 - lambda^s)`` the mass still in the loop
    after pass t, ``p^t = lambda^t S^{t-1}`` for t < T and ``p^T =
    S^{T-1}``, taken in logs (``log lambda = log_sigmoid(g)``, ``log (1 -
    lambda) = log_sigmoid(-g)``), so a saturated gate gives a finite
    entropy. The T masses sum to 1."""
    log_left = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0)
    before = jnp.concatenate([jnp.zeros_like(log_left[:1]), log_left[:-1]])
    return jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits) + before, log_left[-1:]])


def _chip_hbm_bytes() -> Optional[float]:
    """The attached chip's memory by the chip table, None off a TPU."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    from autodist_tpu.resource_spec import CHIP_TABLE, chip_kind_of
    return CHIP_TABLE[chip_kind_of(device.device_kind)].hbm_bytes


def make_train_setup(config: Optional[LMConfig] = None, seq_len: int = 128,
                     batch_size: int = 32, seed: int = 0,
                     attention: str = "auto", lean_head="auto"):
    """``attention``: "auto" (:func:`auto_flash_attention` decides from
    ``seq_len``, the head width and the backend: on a TPU the pallas
    flash kernel where the chip showed it faster IN THE STEP, XLA's
    softmax attention elsewhere and on every other backend), "flash"
    (force the kernel; interpreted on the CPU backend), or "default"
    (XLA always).

    ``lean_head``: True routes the loss through the chunked cross-entropy
    (``ops.xent.chunked_softmax_xent``) — the [tokens, vocab] fp32 logits
    tensor (3.25 GB for lm1b at batch 32) never materializes, which is
    what lets lm1b train at batch 64 on a 16 GB chip. "auto" (default)
    engages it at vocab >= 32768, or where the batch's logits alone
    would be ``LEAN_HEAD_LOGIT_BYTES``, with or without a head bias. Same
    math to float tolerance.

    ``config`` decides the model, not this function: ``LMConfig.lm1b()``
    (GPT-2 style blocks) and ``LMConfig.olmoe_1b_7b()`` (RMSNorm, QK-norm,
    RoPE, dropless top-8-of-64 SwiGLU experts) go through the same
    ``TransformerLM``, the same loss and the same lean-head rule. A
    softmax-routed config adds its router losses to the mean NLL, with
    all its experts or a share of them held:
    ``router_aux_loss_coef * L_lb + router_z_loss_coef * L_z``, each taken
    per layer over the batch this loss sees and averaged over layers
    (OLMoE), or under ``seq_aux`` ``router_aux_loss_coef * sum L_l``, each
    routed layer's balance loss taken per sequence and the layers SUMMED
    (``LMConfig.deepseek_v2_lite()``: latent attention with YaRN-scaled
    rotary keys in every layer, a leading dense layer, ``experts_held`` of
    64 softmax-routed experts beside two shared ones; the sum also leaves
    the step as the device counter ``moe.aux_loss``).
    ``LMConfig.kimi_linear_48b_a3b()`` (KDA and latent-attention layers
    by ``layer_types``, a leading dense layer, sigmoid-routed experts of
    which ``experts_held`` are here, a shared expert) takes the same
    path; its loss is the NLL alone, and so is
    ``LMConfig.lfm2_24b_a2b()``'s (gated short convolutions beside
    grouped-query attention by ``layer_types``, two leading dense layers,
    sigmoid-routed experts of which ``experts_held`` are here, a tied
    head: either head takes the embedding's transpose). Whether each block
    is recomputed in the backward pass is :func:`auto_remat_blocks`'s to
    say, from the parameters it counts and the chip's memory."""
    cfg = config or LMConfig()
    if lean_head == "auto":
        lean_head = (cfg.vocab_size >= 32768
                     or 4 * batch_size * seq_len * cfg.vocab_size
                     >= LEAN_HEAD_LOGIT_BYTES)
    elif not isinstance(lean_head, bool):
        raise ValueError("lean_head must be True, False or 'auto', got %r"
                         % (lean_head,))
    if seq_len > cfg.max_seq_len:
        # out-of-range position lookups would silently NaN (jnp.take fills)
        raise ValueError("seq_len %d exceeds config.max_seq_len %d"
                         % (seq_len, cfg.max_seq_len))
    attn_fn = None
    if attention not in ("auto", "flash", "default"):
        raise ValueError("attention must be auto|flash|default, got %r"
                         % attention)
    types = cfg.layer_types or ("attention",) * cfg.num_layers
    # the widest scores a softmax layer of this model contracts over
    head_dim = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                if "mla" in types
                else cfg.head_dim or cfg.d_model // cfg.num_heads)
    if attention == "flash" or (attention == "auto" and auto_flash_attention(
            seq_len, head_dim, jax.default_backend())):
        from autodist_tpu.ops.flash_attention import make_flash_attn_fn
        attn_fn = make_flash_attn_fn(causal=True)
    flash_layers = (sum(t in ("attention", "mla") for t in types)
                    if attn_fn is not None else 0)
    # block applications whose softmax attention runs ``attn_pre`` between
    # its projections and its core: the layer's own rule, asked here
    from autodist_tpu.ops import attn_pre
    fused_pre_layers = cfg.loop_steps * sum(
        kind == "attention" and attn_pre.runs_fused(
            attn_fn, seq_len, cfg.head_dim or cfg.d_model // cfg.num_heads,
            cfg.qk_head_norm, layer_rope_theta(cfg, i) is not None,
            cfg.qk_norm)
        for i, kind in enumerate(types))
    kda_kernel_layers = 0
    if "kda" in types:
        from autodist_tpu.ops.kda import runs_as_kernels
        if runs_as_kernels(cfg.kda_head_dim, cfg.kda_head_dim):
            kda_kernel_layers = types.count("kda")
    mamba_layers = types.count("mamba2")
    ssd_kernel_layers = mamba_fused_mixer_layers = 0
    if mamba_layers:
        from autodist_tpu.ops import ssd
        shape = (cfg.mamba_head_dim, cfg.ssm_state_size,
                 cfg.mamba_num_heads // cfg.mamba_n_groups)
        if ssd.runs_as_kernels(*shape, cfg.mamba_chunk):
            ssd_kernel_layers = mamba_layers
        if ssd.mixer_runs_fused(*shape, cfg.mamba_n_groups, cfg.mamba_chunk,
                                cfg.mamba_conv_size):
            mamba_fused_mixer_layers = mamba_layers
    rng = jax.random.PRNGKey(seed)
    # only the parameters leave the jit, so the forward pass the init
    # traces (XLA's attention whatever ``attn_fn`` is, and what a routed
    # model's layers sow) is dead code and never runs
    variables = {"params": jax.jit(
        lambda key, ids: TransformerLM(cfg).init(key, ids)["params"])(
        rng, jnp.zeros((1, seq_len), jnp.int32))}
    param_count = sum(a.size for a in jax.tree_util.tree_leaves(variables))
    hbm_bytes = _chip_hbm_bytes()
    remat_blocks = auto_remat_blocks(param_count, cfg.num_layers, hbm_bytes,
                                     cfg.loop_steps)
    # what the recomputed blocks on the flash kernels and on the scan
    # kernels keep by name, once an APPLICATION (a looped model's passes
    # each keep their own)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    core_bytes = flash_layers * flash_kept_bytes(
        batch_size * seq_len, cfg.num_heads, head_dim,
        cfg.v_head_dim if "mla" in types else head_dim, itemsize)
    if ssd_kernel_layers:
        core_bytes += ssd_kernel_layers * ssd_kept_bytes(
            batch_size, seq_len, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.ssm_state_size, cfg.mamba_chunk, itemsize)
    kept_core_bytes = cfg.loop_steps * core_bytes if remat_blocks else 0
    # (leading dense layers route nothing; single sub-layers by kind)
    n_routed = len(routed_layer_indices(cfg))
    routed = n_routed > 0
    held_stack = (None if cfg.experts_held is None else
                  (len(cfg.experts_held), cfg.d_model, cfg.mlp_dim))
    # (a replica sees no more tokens a step than the whole batch)
    tokens = batch_size * seq_len
    shared_width = (cfg.shared_expert_dim
                    or cfg.num_shared_experts * cfg.mlp_dim) if routed else 0
    expert_products = 2 if cfg.expert_gated else 1
    mixer_width = mixer_in_width(cfg)
    gated_layers = len(gated_attention_layer_indices(cfg))
    gate_width = (cfg.num_heads * (cfg.head_dim
                                   or cfg.d_model // cfg.num_heads)
                  if gated_layers else 0)
    kept = auto_kept_layers(
        remat_blocks, param_count, hbm_bytes, tokens, itemsize,
        routed_layers=n_routed, held_stack=held_stack,
        dense_layers=num_dense_layers(cfg), dense_width=cfg.dense_dim,
        sandwich_layers=cfg.num_layers if cfg.sandwich_norm else 0,
        d_model=cfg.d_model, shared_width=shared_width,
        loop_steps=cfg.loop_steps, core_bytes=kept_core_bytes,
        expert_products=expert_products,
        mixer_layers=len(mixer_in_layer_indices(cfg)),
        mixer_width=mixer_width, gate_layers=gated_layers,
        gate_width=gate_width)
    # (applications counted)
    kept_bytes = [n * nbytes for n, nbytes in zip(kept, kept_layer_bytes(
        tokens, itemsize, held_stack, cfg.dense_dim, cfg.d_model,
        shared_width, cfg.loop_steps, expert_products, mixer_width,
        gate_width))]
    model = TransformerLM(cfg, attn_fn=attn_fn, remat_blocks=remat_blocks,
                          kept_expert_layers=kept.experts,
                          kept_dense_layers=kept.dense,
                          kept_sublayer_out_layers=kept.sublayer_outs,
                          kept_shared_layers=kept.shared,
                          kept_mixer_in_layers=kept.mixer_in,
                          kept_attn_gate_layers=kept.attn_gate)
    router_load = SHARE_LOAD if cfg.experts_held is not None else ROUTER_LOAD
    router_losses = cfg.router_activation == "softmax"
    indexed = bool(cfg.indexer_num_heads)

    def forward(params, ids, method):
        """(the method's output, the router losses' weighted sum). The
        layers' load and an indexer's choice go to the step's device
        counters from HERE, the loss's own trace
        (``telemetry/device_counters.py``)."""
        if not (routed or indexed or mamba_layers):
            return model.apply(params, ids, method=method), None
        out, sown = model.apply(params, ids, method=method,
                                mutable=["losses", "counters"])
        for layer in sown["counters"].values():
            if "moe" in layer:
                for name in router_load:
                    device_counters.add("moe." + name, layer["moe"][name][0])
            if "mamba" in layer:    # the mean over the Mamba layers too
                device_counters.add(
                    "mamba.chunk_carry",
                    layer["mamba"]["chunk_carry"][0] / mamba_layers)
            for mixer in layer.values():
                if "indexer" in mixer:
                    for name in INDEXER_CHOICE:
                        device_counters.add("dsa." + name,
                                            mixer["indexer"][name][0])
        if not (routed and router_losses and (
                cfg.router_aux_loss_coef or cfg.router_z_loss_coef)):
            return out, None
        per_layer = sown["losses"].values()
        lb = sum(layer["moe"]["router_lb"][0] for layer in per_layer)
        if cfg.seq_aux:
            device_counters.add("moe.aux_loss", lb)
            return out, cfg.router_aux_loss_coef * lb
        z = sum(layer["moe"]["router_z"][0] for layer in per_layer)
        return out, (cfg.router_aux_loss_coef * lb
                     + cfg.router_z_loss_coef * z) / cfg.num_layers

    def mean_loss(nll, router_loss):
        loss = jnp.mean(nll)
        return loss if router_loss is None else loss + router_loss

    def head_weights(p):
        """The head's kernel [d, vocab] as stored (the embedding's
        transpose where tied) and its float32 bias (zeros without one)."""
        kernel = (p["embed"]["embedding"].T if cfg.tie_embedding
                  else p["lm_head"]["kernel"])
        bias = (p["lm_head"]["bias"].astype(jnp.float32) if cfg.head_bias
                else jnp.zeros((cfg.vocab_size,), jnp.float32))
        return kernel, bias

    def looped_loss(params, ids, targets):
        """A looped model's loss: the head on the normed state after EVERY
        pass against the one kernel (the lean head in ONE call on the
        [T N, d] stack, so that each vocabulary chunk of the kernel is
        streamed once a step and the kernel's gradient is the sum over the
        passes), the exit gate on the same stack, and
        ``mean_i [sum_t p_i^t nll_i^t - exit_entropy_coef H_i]`` with p
        the exit distribution (:func:`exit_log_distribution`) and H its
        entropy, in float32. The batch means of p and H leave the step as
        the device counters ``loop.exit_mass_<t>`` and
        ``loop.exit_entropy``."""
        T = cfg.loop_steps
        states, _ = forward(params, ids, TransformerLM.hidden)
        stack = states.reshape(-1, cfg.d_model)             # [T N, d]
        kernel, bias = head_weights(params["params"])
        kernel = kernel.astype(jnp.float32)
        picked = jnp.tile(targets.reshape(-1), T)
        if lean_head:
            from autodist_tpu.ops.xent import chunked_softmax_xent
            nll = chunked_softmax_xent(stack, kernel, bias, picked)
        else:
            with scopes.scope(scopes.PLAIN_HEAD):
                logp = jax.nn.log_softmax(
                    jnp.dot(stack.astype(jnp.float32), kernel) + bias)
                nll = -jnp.take_along_axis(
                    logp, picked[:, None], axis=-1)[:, 0]
        nll = nll.reshape(T, -1)
        with scopes.scope(scopes.EXIT_GATE):
            gate = params["params"]["exit_gate"]
            logits = jnp.dot(states[:-1].astype(jnp.float32),
                             gate["kernel"][:, 0].astype(jnp.float32)) \
                + gate["bias"].astype(jnp.float32)
            log_p = exit_log_distribution(logits.reshape(T - 1, -1))
            mass = jnp.exp(log_p)
            entropy = -jnp.sum(mass * log_p, axis=0)
            loss = jnp.mean(jnp.sum(mass * nll, axis=0)
                            - cfg.exit_entropy_coef * entropy)
            for t in range(T):
                device_counters.add("loop.exit_mass_%d" % (t + 1),
                                    jnp.mean(mass[t]))
            device_counters.add("loop.exit_entropy", jnp.mean(entropy))
        return loss

    def loss_fn(params, batch):
        # what the rule decided, once per trace, host side
        tel.gauge_set("attention.flash_layers", flash_layers)
        tel.gauge_set("attention.fused_pre_layers", fused_pre_layers)
        tel.gauge_set("attention.kda_kernel_layers", kda_kernel_layers)
        tel.gauge_set("model.remat_blocks",
                      cfg.num_layers if remat_blocks else 0)
        for what, layers, nbytes in zip(("expert", "dense", "sublayer_out",
                                         "shared", "mixer_in", "attn_gate"),
                                        kept, kept_bytes):
            tel.gauge_set("model.kept_%s_layers" % what, layers)
            tel.gauge_set("model.kept_%s_bytes" % what, nbytes)
        tel.gauge_set("model.loop_steps", cfg.loop_steps)
        tel.gauge_set("model.block_applications",
                      cfg.num_layers * cfg.loop_steps)
        tel.gauge_set("model.kept_core_bytes", kept_core_bytes)
        tel.gauge_set("model.mamba_layers", mamba_layers)
        tel.gauge_set("model.ssd_kernel_layers", ssd_kernel_layers)
        tel.gauge_set("model.mamba_fused_mixer_layers",
                      mamba_fused_mixer_layers)
        tel.gauge_set("model.single_sublayer_blocks",
                      cfg.num_layers if cfg.single_sublayer else 0)
        tokens = batch["tokens"]
        targets = tokens[:, 1:]
        if cfg.loop_steps > 1:
            return looped_loss(params, tokens[:, :-1], targets)
        if lean_head:
            from autodist_tpu.ops.xent import chunked_softmax_xent
            h, router_loss = forward(params, tokens[:, :-1],
                                     TransformerLM.hidden)
            kernel, bias = head_weights(params["params"])
            nll = chunked_softmax_xent(
                h.reshape(-1, cfg.d_model), kernel.astype(jnp.float32),
                bias, targets.reshape(-1))
            return mean_loss(nll, router_loss)
        logits, router_loss = forward(params, tokens[:, :-1], None)
        with scopes.scope(scopes.PLAIN_HEAD):
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
        return mean_loss(nll, router_loss)

    declared = []
    if routed:
        declared += ["moe." + n for n in router_load]
        declared += ["moe.aux_loss"] if cfg.seq_aux else []
    if indexed:
        declared += ["dsa." + n for n in INDEXER_CHOICE]
    if mamba_layers:
        declared += ["mamba.chunk_carry"]
    if cfg.loop_steps > 1:
        declared += ["loop.exit_mass_%d" % (t + 1)
                     for t in range(cfg.loop_steps)] + ["loop.exit_entropy"]
    if declared:
        loss_fn.device_counters = tuple(declared)

    npr = np.random.RandomState(seed)
    example_batch = {"tokens": npr.randint(
        0, cfg.vocab_size, (batch_size, seq_len + 1)).astype(np.int32)}
    apply_fn = lambda p, ids: model.apply(p, ids)  # noqa: E731
    return loss_fn, dict(variables), example_batch, apply_fn


def make_decode_setup(config: Optional[LMConfig] = None,
                      decode_attn: str = "reference",
                      return_logits: bool = False):
    """Continuous-batching decode functions over a trained TransformerLM
    (``serving/decode.py`` DecodeEngine). Returns a
    :class:`~autodist_tpu.serving.decode.DecodeSetup` whose parameters
    resolve against the same variables :func:`make_train_setup` trains.

    ``decode_attn="flash"`` routes the decode inner loop through the
    pallas flash kernel (``ops.attention.flash_cached_attention``);
    greedy argmax sampling runs in-graph so the per-step D2H is one
    int32 per slot. ``return_logits`` adds the full [slots, vocab]
    logits to the step fetches (parity tests; costs a vocab-sized D2H
    per step, keep it off in production)."""
    from autodist_tpu.serving.decode import DecodeSetup

    cfg = config or LMConfig()
    model = TransformerLM(cfg, decode_attn=decode_attn)
    head_dim = cfg.d_model // cfg.num_heads

    def prefill_fn(params, batch):
        logits, k, v = model.apply(params, batch["tokens"], batch["length"],
                                   method=TransformerLM.prefill)
        return {"next_token": jnp.argmax(logits, axis=-1).astype(jnp.int32),
                "k": k, "v": v}

    def decode_fn(params, dstate):
        logits, k, v = model.apply(
            params, dstate["token"], dstate["k"], dstate["v"],
            dstate["cursor"], dstate["alive"],
            method=TransformerLM.decode_step)
        out = {"k": k, "v": v,
               "next_token": jnp.argmax(logits, axis=-1).astype(jnp.int32)}
        if return_logits:
            out["logits"] = logits
        return out

    def init_dstate(slots: int):
        cache_shape = (slots, cfg.num_layers, cfg.max_seq_len,
                       cfg.num_heads, head_dim)
        cache_dtype = np.dtype(jnp.dtype(cfg.dtype).name)
        return {"k": np.zeros(cache_shape, cache_dtype),
                "v": np.zeros(cache_shape, cache_dtype),
                "token": np.zeros((slots,), np.int32),
                "cursor": np.zeros((slots,), np.int32),
                "alive": np.zeros((slots,), np.bool_)}

    return DecodeSetup(prefill_fn=prefill_fn, decode_fn=decode_fn,
                       init_dstate=init_dstate, max_len=cfg.max_seq_len,
                       vocab_size=cfg.vocab_size)


def make_sp_train_setup(config: Optional[LMConfig] = None, seq_len: int = 128,
                        batch_size: int = 32, seed: int = 0,
                        attention: str = "ring"):
    """Sequence-parallel train setup: tokens arrive [B, S] with S sharded
    over the ``seq`` mesh axis; attention runs ring/Ulysses; next-token
    targets cross shard boundaries via ``sequence.shift_left``; the final
    global position is masked out with an SP-exact weighted mean."""
    from autodist_tpu import const
    from autodist_tpu.ops.attention import make_attn_fn
    from autodist_tpu.parallel import sequence

    cfg = config or LMConfig()
    if seq_len > cfg.max_seq_len:
        raise ValueError("seq_len %d exceeds config.max_seq_len %d"
                         % (seq_len, cfg.max_seq_len))
    attn_fn = make_attn_fn(attention, const.SEQUENCE_AXIS, causal=True)
    model = TransformerLM(cfg, attn_fn=None, seq_parallel=True)  # init w/o axis
    rng = jax.random.PRNGKey(seed)
    variables = jax.jit(model.init)(rng, jnp.zeros((1, seq_len), jnp.int32))
    sp_model = TransformerLM(cfg, attn_fn=attn_fn, seq_parallel=True)

    def loss_fn(params, batch):
        tokens = batch["tokens"]          # local chunk [B, C]
        local_len = tokens.shape[1]
        logits = sp_model.apply(params, tokens)
        targets = sequence.shift_left(tokens, const.SEQUENCE_AXIS, axis=1)
        with scopes.scope(scopes.PLAIN_HEAD):
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
        # mask the final GLOBAL position (its target wrapped around)
        pos = jnp.arange(local_len) + sequence.position_offset(
            local_len, const.SEQUENCE_AXIS)
        total_len = local_len * sequence.axis_size(const.SEQUENCE_AXIS)
        weights = (pos < total_len - 1).astype(nll.dtype)[None, :]
        weights = jnp.broadcast_to(weights, nll.shape)
        return sequence.global_weighted_mean(nll, weights, const.SEQUENCE_AXIS)

    npr = np.random.RandomState(seed)
    example_batch = {"tokens": npr.randint(
        0, cfg.vocab_size, (batch_size, seq_len)).astype(np.int32)}
    apply_fn = lambda p, ids: model.apply(p, ids)  # noqa: E731
    return loss_fn, dict(variables), example_batch, apply_fn
