"""kimi_linear family: Kimi-Linear's layers (KDA and NoPE latent attention
by a per-layer pattern, a leading dense SwiGLU layer, sigmoid-routed
experts of which this chip holds a share, a shared expert) as a
configuration of the ONE decoder-only model of ``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.kimi_linear_48b_a3b`` with the
file's sizes), the seeded host batches (ids drawn from the file's slice of
the vocabulary), and the closed-form FLOPs and bytes the per-layer metrics
divide by. The closed forms are the yardstick and live here, not in the
program.

In the file ``num_experts`` is what is HELD here (``experts_held`` names
them) and ``router_num_experts`` the router's published width.
"""
import dataclasses

from benchmark.families.lm import host_batches, tokens_per_row  # noqa: F401
from benchmark.reference import kimi_linear as reference  # noqa: F401  (run.py reads it)

# tokens per chunk of the chunked delta rule the closed form counts (the
# size at which the least work below is stated; the program's own is
# autodist_tpu/ops/kda.py:CHUNK)
KDA_CHUNK = 64


def layer_types(config):
    """("kda" | "mla") for each layer kept, by the published indices
    (``linear_attn_config`` numbers layers from 1)."""
    full = set(config["linear_attn_config"]["full_attn_layers"])
    return tuple("mla" if i + 1 in full else "kda"
                 for i in range(config["num_hidden_layers"]))


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    kda = config["linear_attn_config"]
    return dataclasses.replace(
        LMConfig.kimi_linear_48b_a3b(
            num_layers=config["num_hidden_layers"],
            layer_types=layer_types(config),
            dtype=jnp.dtype(config["dtype"]),
            max_seq_len=max(seq, config["model_max_length"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        norm_eps=config["rms_norm_eps"],
        kda_num_heads=kda["num_heads"], kda_head_dim=kda["head_dim"],
        kda_conv_size=kda["short_conv_kernel_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        first_k_dense_replace=config["first_k_dense_replace"],
        dense_dim=config["intermediate_size"],
        mlp_dim=config["moe_intermediate_size"],
        num_experts=config["router_num_experts"],
        experts_per_token=config["num_experts_per_token"],
        moe_renormalize=config["moe_renormalize"],
        routed_scaling_factor=config["routed_scaling_factor"],
        num_shared_experts=config["num_shared_experts"],
        experts_held=tuple(config["experts_held"]))


def train_setup(config, traffic, global_batch, seed):
    """(loss_fn, params on the device, example batch) through the program's
    ``make_train_setup``: weights come from one jitted init of ``seed``."""
    from autodist_tpu.models import lm
    seq = traffic["seq"]
    loss_fn, params, example, _ = lm.make_train_setup(
        model_config(config, seq), seq_len=seq, batch_size=global_batch,
        seed=seed)
    return loss_fn, params, example


def _layers(config):
    types = layer_types(config)
    routed = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return types.count("kda"), types.count("mla"), routed


def active_matmul_params(config):
    """Matmul parameters ONE token passes through. A KDA mixer: q, k, v
    and the output projection, the two low-rank gates, beta. A latent
    mixer: q, the latent's down- and up-projection, the output. The dense
    layer's SwiGLU; per routed layer the router over ALL its outputs, the
    shared experts, and of the k chosen experts the share an even router
    sends here (k x held / all: 0.25 of an expert); the untied head over
    the slice."""
    d = config["hidden_size"]
    kda = config["linear_attn_config"]
    hd = kda["num_heads"] * kda["head_dim"]
    kda_params = (4 * d * hd + 2 * (d + hd) * kda["head_dim"]
                  + d * kda["num_heads"])
    h = config["num_attention_heads"]
    nope, pe, v, rank = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                         config["v_head_dim"], config["kv_lora_rank"])
    mla_params = (d * h * (nope + pe) + d * (rank + pe)
                  + rank * h * (nope + v) + h * v * d)
    f = config["moe_intermediate_size"]
    here = (config["num_experts_per_token"] * config["num_experts"]
            / config["router_num_experts"])
    moe_params = (d * config["router_num_experts"]
                  + 3 * d * f * (config["num_shared_experts"] + here))
    n_kda, n_mla, n_routed = _layers(config)
    return (n_kda * kda_params + n_mla * mla_params + n_routed * moe_params
            + config["first_k_dense_replace"] * 3 * d
            * config["intermediate_size"] + d * config["vocab_size"])


def kda_scan_flops_per_step(config, tokens):
    """The least FLOPs of the chunked delta rule for ``tokens`` tokens of
    ONE sequence batch, forward + backward (3 x forward: each matmul once
    forward and twice backward), every KDA layer. Per chunk of C tokens
    and head, forward, with only the causal half of a [C, C] product
    counted: M and P (2 C^2 d_k), the unit-triangular solve of d_k + d_v
    right-hand sides (C^2 (d_k + d_v)), P W (C^2 d_v), and the three
    [C, d_k] x [d_k, d_v] products with the state (6 C d_k d_v)."""
    kda = config["linear_attn_config"]
    c, dk = KDA_CHUNK, kda["head_dim"]
    dv = dk
    per_chunk = (2 * c * c * dk + c * c * (dk + dv) + c * c * dv
                 + 6 * c * dk * dv)
    return (3.0 * per_chunk * (tokens / c) * kda["num_heads"]
            * _layers(config)[0])


def kda_scan_bytes_per_step(config, tokens):
    """The least bytes the delta rule's core moves for ``tokens`` tokens,
    forward + backward, every KDA layer: forward reads q, k, v (2 B), the
    log-decay (4 B a channel) and beta (4 B a head) and writes o; backward
    reads them and o's gradient and writes their five gradients."""
    kda = config["linear_attn_config"]
    dk = dv = kda["head_dim"]
    inputs = 2 * (2 * dk + dv) + 4 * dk + 4
    out = 2 * dv
    return (float(3 * inputs + 2 * out) * tokens * kda["num_heads"]
            * _layers(config)[0])


def mla_attn_flops_per_step(config, batch, seq):
    """Model FLOPs of the latent layers' attention cores, CAUSAL (half of
    the S x S square), forward + backward without the kernel's
    recomputation: Q K^T over nope + pe features and P V over v, 2 FLOPs a
    multiply-add, once forward and twice backward."""
    width = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
             + config["v_head_dim"])
    return (3.0 * 2 * width * config["num_attention_heads"] * batch
            * seq * (seq + 1) / 2 * _layers(config)[1])


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter, plus the latent layers' causal scores
    and the KDA cores at their least. Recomputation (each block is
    recomputed in the backward pass; the lean head recomputes each chunk's
    logits) is NOT counted."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + mla_attn_flops_per_step(config, 1, seq) / seq
            + kda_scan_flops_per_step(config, seq) / seq)


def expert_flops_per_step(config, tokens):
    """FLOPs the program spends in the held experts for ``tokens`` tokens,
    forward + backward, the routed layers together: it runs EVERY held
    expert on EVERY token under its gate (``parallel/expert.py:
    _held_experts``), three [d, f] projections, 2 FLOPs a weight, once
    forward and twice backward. The model's work is the pairs that CHOSE
    a held expert (k x held / all of these rows under an even router: 1 in
    32 here), which is what ``train_flops_per_token`` counts."""
    return (18.0 * config["hidden_size"] * config["moe_intermediate_size"]
            * tokens * config["num_experts"] * _layers(config)[2])
