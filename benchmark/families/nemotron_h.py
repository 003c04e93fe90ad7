"""nemotron_h family: the Nemotron-H tower of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 (Mamba-2 state-space layers, NoPE
grouped-query attention and sigmoid-routed ``relu(up x)^2`` experts beside
a shared one, EVERY layer ONE sub-layer by ``hybrid_override_pattern``; an
untied head) as a configuration of the ONE decoder-only model of
``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.nemotron_twotower_30b_a3b`` with the
file's sizes), the seeded host batches (ids drawn from the file's slice of
the vocabulary), and the closed-form FLOPs and bytes the per-layer metrics
divide by. The closed forms are the yardstick and live here, not in the
program.

In the file ``n_routed_experts`` is what is HELD here (``experts_held``
names them) and ``router_num_experts`` the router's published width;
``hybrid_override_pattern`` is the published string of all 52 letters, of
which the first ``num_hidden_layers`` are built.
"""
import dataclasses

# (the pool's SECOND batch is its first once more, so that the driver's
# second loss is read on the sequence step 0 trained on, where it shows the
# step: ``families/deepseek_v2.py:host_batches`` and its reason)
from benchmark.families.deepseek_v2 import host_batches  # noqa: F401
from benchmark.families.lm import tokens_per_row  # noqa: F401
from benchmark.reference import nemotron_h as reference  # noqa: F401  (run.py reads it)

def layer_types(config):
    """LMConfig's name of each layer kept: the published pattern, cut from
    its start, by the program's own letters (``lm.NEMOTRON_H_LAYERS``; "-",
    a dense feed-forward alone, is not built, and this model's string has
    none)."""
    from autodist_tpu.models.lm import NEMOTRON_H_LAYERS
    letters = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    if set(letters) - set(NEMOTRON_H_LAYERS):
        raise ValueError(
            "hybrid_override_pattern %r names a layer that is not built "
            "(built: %s)" % (letters, sorted(NEMOTRON_H_LAYERS)))
    return tuple(NEMOTRON_H_LAYERS[c] for c in letters)


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    return dataclasses.replace(
        LMConfig.nemotron_twotower_30b_a3b(
            num_layers=config["num_hidden_layers"],
            layer_types=layer_types(config),
            dtype=jnp.dtype(config["dtype"]),
            max_seq_len=max(seq, config["max_position_embeddings"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        norm_eps=config["layer_norm_epsilon"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_n_groups=config["n_groups"],
        ssm_state_size=config["ssm_state_size"],
        mamba_conv_size=config["conv_kernel"],
        mamba_chunk=config["chunk_size"],
        mlp_dim=config["moe_intermediate_size"],
        shared_expert_dim=config["moe_shared_expert_intermediate_size"],
        num_shared_experts=config["n_shared_experts"],
        num_experts=config["router_num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_renormalize=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        experts_held=tuple(config["experts_held"]))


def held_to_the_reference(config):
    """``drivers/train_fit.py`` calls ``reference.nll_sum`` with the
    numbers ``reference/nemotron_h.py`` states as constants (and with the
    equations it writes out: no bias but the filter's, ``relu2`` experts, a
    choice-only expert bias in one group, no clamp on the time step) and
    hands it no configuration, so a file that states others would be
    compared with another model: refuse it here, by name."""
    stated = {"num_experts_per_tok": reference.TOP_K,
              "layer_norm_epsilon": reference.RMS_EPS,
              "norm_eps": reference.RMS_EPS,
              "routed_scaling_factor": reference.SCALING,
              "n_groups": reference.N_GROUPS,
              "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
              "n_shared_experts": 1, "mlp_hidden_act": "relu2",
              "mamba_hidden_act": "silu", "use_conv_bias": True,
              "mamba_proj_bias": False, "attention_bias": False,
              "mlp_bias": False, "use_bias": False,
              "tie_word_embeddings": False, "time_step_limit": [0, None]}
    differs = sorted(k for k, v in stated.items() if config[k] != v)
    if differs:
        raise ValueError(
            "benchmark/reference/nemotron_h.py states %s, the configuration "
            "%s" % ({k: stated[k] for k in differs},
                    {k: config[k] for k in differs}))


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
    """(Mamba layers, attention layers, routed layers)."""
    types = layer_types(config)
    return tuple(types.count(k) for k in ("mamba2", "attention", "moe"))


def _mamba_proj_params(config):
    """A Mamba mixer's two projections: d -> [z | xBC | dt] and inner -> d
    (the 4-tap filter is no matmul)."""
    d = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    return d * (2 * inner + 2 * config["n_groups"] * config["ssm_state_size"]
                + config["mamba_num_heads"]) + inner * d


def active_matmul_params(config):
    """Matmul parameters ONE token passes through forward AND backward. A
    Mamba mixer: its two projections. An attention mixer: q and the output
    over ``heads x head_dim``, k and v over the K/V heads. Per routed layer
    the router over ALL its outputs, the shared expert's two matrices, and
    of the k chosen experts the share an even router sends here (k x held
    / all: 0.375 of an expert, two matrices each); the untied head over
    the slice."""
    d, hd = config["hidden_size"], config["head_dim"]
    attn = d * hd * 2 * (config["num_attention_heads"]
                         + config["num_key_value_heads"])
    here = (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_num_experts"])
    moe = (d * config["router_num_experts"]
           + 2 * d * config["moe_shared_expert_intermediate_size"]
           + 2 * d * config["moe_intermediate_size"] * here)
    n_mamba, n_attn, n_routed = _layers(config)
    return (n_mamba * _mamba_proj_params(config) + n_attn * attn
            + n_routed * moe + d * config["vocab_size"])


def dsa_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the attention cores, forward + backward without the
    kernel's recomputation, under the name ``dsa_core_roofline_pct`` asks a
    family for (the cores run under the program's ``dsa_core`` scope, its
    name for the attention function's call on grouped K/V heads): with no
    indexer the chosen pairs are ALL the causal pairs, S (S + 1) / 2. Q K^T
    and P V over ``head_dim`` features, 2 FLOPs a multiply-add, every
    QUERY head (two K/V heads shared by groups of 16 save bytes, no
    product), once forward and twice backward; every attention layer."""
    return (3.0 * 2 * 2 * config["head_dim"] * config["num_attention_heads"]
            * batch * seq * (seq + 1) / 2 * _layers(config)[1])


def mamba_proj_flops_per_step(config, tokens):
    """Model FLOPs of the Mamba mixers' two projections for ``tokens``
    tokens, every Mamba layer: 2 a weight and token, once forward and
    twice backward; the blocks' recomputed forward is not counted."""
    return 3 * 2.0 * _mamba_proj_params(config) * tokens * _layers(config)[0]


def ssd_scan_flops_per_step(config, tokens):
    """Model FLOPs of the state-space recurrence in its chunked dual form
    at the configuration's ``chunk_size`` L, for ``tokens`` tokens, every
    Mamba layer, forward + backward (x 3), the blocks' recomputed forward
    not counted; 2 FLOPs a multiply-add. A token and layer: ``C B^T`` over
    the keys of its chunk it sees, (L + 1) / 2 of them, N features, once a
    GROUP; ``(L o C B^T)(dt x)`` over the same keys, P features a head; its
    part of the chunk's state, ``dt x B^T``, P x N a head; and ``C S_prev``,
    N x P a head. The same work whatever implements the core (a kernel
    that makes the whole L x L square runs more and is credited this)."""
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, L = config["n_groups"], config["ssm_state_size"], config["chunk_size"]
    a_token = 2.0 * ((L + 1) / 2 * (N * G + P * H) + 2 * P * N * H)
    return 3 * a_token * tokens * _layers(config)[0]


def ssd_scan_bytes_per_step(config, tokens):
    """The least HBM traffic of the recurrence for ``tokens`` tokens,
    every Mamba layer, forward + backward, at the configuration's bfloat16
    (dt in float32): forward reads x [inner], B and C [G N] and dt [H] a
    token and writes y [inner]; backward reads them and dy again and
    writes the gradients of x, B, C and dt. States and decays stay on the
    chip in this count (64 chunk states of 2 MB a layer would add a tenth).
    What caps the share far under 100 for a ``jnp`` core: the decay
    ``[H, L, L]`` a chunk, float32, is made, masked, multiplied and
    rounded as passes of their own: 268 MB a layer each."""
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    io = 2 * (H * P + 2 * config["n_groups"] * config["ssm_state_size"]) \
        + 4 * H                                          # x, B, C; dt
    out = 2 * H * P                                      # y or dy
    return (io + out + io + out + io) * tokens * _layers(config)[0]


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter plus the attention cores over the causal
    pairs plus the recurrence's products. The element-wise work (filter,
    gates, norms, decays) is left out: it is bytes, not FLOPs.
    Recomputation (each block is recomputed in the backward pass, the flash
    kernels recompute the scores) is NOT counted, nor the held experts a
    token did not choose."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + dsa_core_flops_per_step(config, 1, seq) / seq
            + ssd_scan_flops_per_step(config, 1))


def expert_flops_per_step(config, tokens):
    """FLOPs the program RUNS in the held experts for ``tokens`` tokens,
    forward + backward, the routed layers together: EVERY held expert on
    EVERY token under its gate (``parallel/expert.py:_held_experts``), TWO
    [d, f] projections (no gate), 2 FLOPs a weight, once forward and twice
    backward: 12 d f a row; the blocks' recomputed forward is not counted.
    The model's work is the pairs that CHOSE a held expert (k / all of
    these rows under an even router: 6 in 128), which is what
    ``train_flops_per_token`` counts."""
    return (12.0 * config["hidden_size"] * config["moe_intermediate_size"]
            * tokens * config["n_routed_experts"] * _layers(config)[2])


def chosen_pairs_per_step(config, tokens):
    """What the routed layers add to ``moe.chosen_pairs`` a step: every
    token's k pairs, every routed layer (how ``mamba_chunk_carry`` counts
    the steps that were read back)."""
    return tokens * config["num_experts_per_tok"] * _layers(config)[2]
