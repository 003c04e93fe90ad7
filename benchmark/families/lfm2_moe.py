"""lfm2_moe family: LFM2-24B-A2B's layers (gated short convolutions and
grouped-query attention by the published ``layer_types``, leading dense
SwiGLU layers, sigmoid-routed experts of which this chip holds a share, a
tied head) as a configuration of the ONE decoder-only model of
``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.lfm2_24b_a2b`` with the file's
sizes), the seeded host batches (ids drawn from the file's slice of the
vocabulary), and the closed-form FLOPs and bytes the per-layer metrics
divide by. The closed forms are the yardstick and live here, not in the
program.

In the file ``num_experts`` is what is HELD here (``experts_held`` names
them) and ``router_num_experts`` the router's published width;
``layer_types`` is the published list of all 40, of which the first
``num_hidden_layers`` are built.
"""
import dataclasses

# (the pool's SECOND batch is its first once more, so that the driver's
# second loss is read on the sequence step 0 trained on, where it shows the
# step: ``families/deepseek_v2.py:host_batches`` and its reason)
from benchmark.families.deepseek_v2 import host_batches  # noqa: F401
from benchmark.families.lm import tokens_per_row  # noqa: F401
from benchmark.reference import lfm2_moe as reference  # noqa: F401  (run.py reads it)

# the source's names for a layer's token mixer -> LMConfig's
MIXERS = {"conv": "conv", "full_attention": "attention"}


def layer_types(config):
    """LMConfig's name of each layer kept: the published list, cut from
    its start."""
    return tuple(MIXERS[t] for t in
                 config["layer_types"][:config["num_hidden_layers"]])


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    return dataclasses.replace(
        LMConfig.lfm2_24b_a2b(
            num_layers=config["num_hidden_layers"],
            layer_types=layer_types(config),
            dtype=jnp.dtype(config["dtype"]),
            max_seq_len=max(seq, config["max_position_embeddings"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        norm_eps=config["norm_eps"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        conv_size=config["conv_L_cache"],
        first_k_dense_replace=config["num_dense_layers"],
        dense_dim=config["intermediate_size"],
        mlp_dim=config["moe_intermediate_size"],
        num_experts=config["router_num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_renormalize=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        experts_held=tuple(config["experts_held"]))


def held_to_the_reference(config):
    """``drivers/train_fit.py`` calls ``reference.nll_sum`` with the
    numbers ``reference/lfm2_moe.py`` states as constants (and with the
    equations it writes out: no bias, a choice-only expert bias, gates
    renormalised and not scaled) and hands it no configuration, so a file
    that states others would be compared with another model: refuse it
    here, by name."""
    stated = {"num_experts_per_tok": reference.TOP_K,
              "norm_eps": reference.RMS_EPS,
              "rope_theta": reference.ROPE_THETA,
              "norm_topk_prob": True, "routed_scaling_factor": 1,
              "use_expert_bias": True, "conv_bias": False}
    given = dict(config, rope_theta=config["rope_parameters"]["rope_theta"])
    differs = sorted(k for k, v in stated.items() if given[k] != v)
    if differs:
        raise ValueError(
            "benchmark/reference/lfm2_moe.py states %s, the configuration "
            "%s" % ({k: stated[k] for k in differs},
                    {k: given[k] for k in differs}))


def train_setup(config, traffic, global_batch, seed):
    """(loss_fn, params on the device, example batch) through the program's
    ``make_train_setup``: weights come from one jitted init of ``seed``."""
    from autodist_tpu.models import lm
    held_to_the_reference(config)
    seq = traffic["seq"]
    loss_fn, params, example, _ = lm.make_train_setup(
        model_config(config, seq), seq_len=seq, batch_size=global_batch,
        seed=seed)
    return loss_fn, params, example


def _layers(config):
    """(conv layers, attention layers, dense layers, routed layers)."""
    types = layer_types(config)
    dense = min(config["num_dense_layers"], len(types))
    return (types.count("conv"), types.count("attention"), dense,
            len(types) - dense)


def active_matmul_params(config):
    """Matmul parameters ONE token passes through forward AND backward. A
    conv mixer: its two projections (d -> 3 d, d -> d; the 3-tap filter is
    no matmul). An attention mixer: q and the output over ``heads x
    head_dim``, k and v over the K/V heads. A leading dense layer's
    SwiGLU; per routed layer the router over ALL its outputs and of the k
    chosen experts the share an even router sends here (k x held / all:
    half an expert); the tied head over the slice (the table is one
    parameter with two uses: the lookup is no matmul, the logits are)."""
    d = config["hidden_size"]
    hd = d // config["num_attention_heads"]
    conv = 4 * d * d
    attn = d * hd * 2 * (config["num_attention_heads"]
                         + config["num_key_value_heads"])
    here = (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_num_experts"])
    moe = d * config["router_num_experts"] \
        + 3 * d * config["moe_intermediate_size"] * here
    n_conv, n_attn, n_dense, n_routed = _layers(config)
    return (n_conv * conv + n_attn * attn
            + n_dense * 3 * d * config["intermediate_size"]
            + n_routed * moe + d * config["vocab_size"])


def dsa_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the attention cores, forward + backward without the
    kernel's recomputation, under the name ``dsa_core_roofline_pct`` asks
    a family for (the cores run under the program's ``dsa_core`` scope,
    its name for the attention function's call on grouped K/V heads):
    with no indexer the chosen pairs are ALL the causal pairs, S (S + 1)
    / 2. Q K^T and P V over ``head_dim`` features, 2 FLOPs a
    multiply-add, every QUERY head (a K/V head shared by a group saves
    bytes, no product), once forward and twice backward; every attention
    layer. What caps the share well under 100 at heads of 64: every
    product of a live tile has either a contraction over 64 features
    (Q K^T, dO V^T: half the MXU's 128-deep pass on a v5e) or an output
    64 wide (P V, P^T dO, dS^T Q, dS K: half its columns), the kernels
    make the scores again in their backward pass (7 products run where 6
    are counted), and the 16 tiles the diagonal crosses compute pairs no
    query sees: about 40 % is the ceiling, where heads of 192 / 128 have
    83 %."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return (3.0 * 2 * 2 * hd * config["num_attention_heads"] * batch
            * seq * (seq + 1) / 2 * _layers(config)[1])


def conv_mix_flops_per_step(config, tokens):
    """Model FLOPs of the conv mixers' two projections (d -> 3 d and
    d -> d: 4 d^2 weights a layer) for ``tokens`` tokens, every conv
    layer: 2 a weight and token, once forward and twice backward; the
    blocks' recomputed forward is not counted (as in
    ``expert_flops_per_step``). What lies between the projections (the
    split in thirds, the two gates, the 3-tap filter) is a dozen FLOPs a
    channel and no product: bytes, which at this width are a ninth of the
    products' time even if every one passed HBM alone (forward and the
    recomputed forward each read [S, 3 d] and write [S, d], backward
    reads both and writes [S, 3 d]: 15 d x 2 B a token and layer, 2.52
    GB = 3.07 ms at 819 GB/s beside the products' 20.9 ms at peak), so
    FLOPs bound the mixer."""
    d = config["hidden_size"]
    return 3 * 2.0 * 4 * d * d * tokens * _layers(config)[0]


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter plus the attention cores over the causal
    pairs. The conv cores' element-wise work (a dozen FLOPs a channel) is
    left out: it is bytes, not FLOPs. Recomputation (each block is
    recomputed in the backward pass, the flash kernels recompute the
    scores) is NOT counted, nor the held experts a token did not choose."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + dsa_core_flops_per_step(config, 1, seq) / seq)


def expert_flops_per_step(config, tokens):
    """FLOPs the program RUNS in the held experts for ``tokens`` tokens,
    forward + backward, the routed layers together: EVERY held expert on
    EVERY token under its gate (``parallel/expert.py:_held_experts``),
    three [d, f] projections, 2 FLOPs a weight, once forward and twice
    backward; the blocks' recomputed forward is not counted. The model's
    work is the pairs that CHOSE a held expert (k / all of these rows
    under an even router: 4 in 64), which is what
    ``train_flops_per_token`` counts."""
    return (18.0 * config["hidden_size"] * config["moe_intermediate_size"]
            * tokens * config["num_experts"] * _layers(config)[3])
