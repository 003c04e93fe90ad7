"""afmoe family: Trinity-Mini (arcee-ai, 26B-A3B: GATED softmax attention
over grouped K/V heads with a per-head QK-norm, sliding-window rotary and
global NoPE layers 3 : 1 by ``layer_types``, four norms a block, a leading
dense SwiGLU layer, sigmoid-routed experts of which this chip holds a share
beside one shared expert, the embedding scaled; an untied head) as a
configuration of the ONE decoder-only model of
``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.trinity_mini_26b_a3b`` with the
file's sizes), the seeded host batches (ids drawn from the file's slice of
the vocabulary), and the closed-form FLOPs the per-layer metrics divide by.
The closed forms are the yardstick and live here, not in the program.

In the file ``num_experts`` is what is HELD here (``experts_held`` names
them) and ``router_num_experts`` the router's published width;
``layer_types`` is the published list of all 32 kinds, of which the
``num_hidden_layers`` from ``first_layer_built`` on are built, the first
``num_dense_layers`` of them dense.
"""
import dataclasses
import sys

# (the pool's SECOND batch is its first once more, so that the driver's
# second loss is read on the sequence step 0 trained on, where it shows the
# step: ``families/deepseek_v2.py:host_batches`` and its reason)
from benchmark.families.deepseek_v2 import host_batches  # noqa: F401
from benchmark.families.lm import tokens_per_row  # noqa: F401
from benchmark.families.smallthinker import (_core_flops, causal_pairs,
                                             window_pairs)
from benchmark.reference import afmoe as reference  # noqa: F401  (run.py reads it)


def layer_kinds(config):
    """The published kinds of the layers built, in order."""
    first = config["first_layer_built"]
    return tuple(config["layer_types"][first:first
                                       + config["num_hidden_layers"]])


def layouts(config):
    """(window flags, rotation flags) of the layers built: a
    ``sliding_attention`` layer has a window AND rotates, a
    ``full_attention`` layer neither."""
    local = tuple(int(kind == "sliding_attention")
                  for kind in layer_kinds(config))
    return local, local


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    preset = getattr(LMConfig, "trinity_mini_26b_a3b", None)
    if preset is None:
        # a checkout from before the preset cannot run this configuration:
        # it says so at once and by name
        sys.exit("benchmark: this checkout's autodist_tpu.models.lm.LMConfig "
                 "has no trinity_mini_26b_a3b (no gated attention): the "
                 "afmoe family cannot run on it")
    window_layers, rope_layers = layouts(config)
    return dataclasses.replace(
        preset(num_layers=config["num_hidden_layers"],
               window_layers=window_layers, rope_layers=rope_layers,
               dtype=jnp.dtype(config["dtype"]),
               max_seq_len=max(seq, config["max_position_embeddings"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        sliding_window=config["sliding_window"],
        first_k_dense_replace=config["num_dense_layers"],
        dense_dim=config["intermediate_size"],
        mlp_dim=config["moe_intermediate_size"],
        num_experts=config["router_num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_renormalize=config["route_norm"],
        routed_scaling_factor=float(config["route_scale"]),
        num_shared_experts=config["num_shared_experts"],
        embed_scale=config["mup_enabled"],
        experts_held=tuple(config["experts_held"]))


def held_to_the_reference(config):
    """``drivers/train_fit.py`` calls ``reference.nll_sum`` with the
    numbers ``reference/afmoe.py`` states as constants (and with the
    equations it writes out: a sigmoid router renormalised over the chosen
    and scaled, a scaled embedding, an untied head, SiLU) and hands it no
    configuration, so a file that states others would be compared with
    another model: refuse it here, by name."""
    kinds = [("sliding_attention" if reference.layout(i)[1]
              else "full_attention")
             for i in range(len(config["layer_types"]))]
    stated = {"num_experts_per_tok": reference.TOP_K,
              "rms_norm_eps": reference.RMS_EPS,
              "rope_theta": reference.ROPE_THETA,
              "sliding_window": reference.WINDOW,
              "route_scale": reference.ROUTE_SCALE,
              "global_attn_every_n_layers": reference.GLOBAL_EVERY,
              "first_layer_built": reference.FIRST_LAYER,
              "layer_types": kinds, "score_func": "sigmoid",
              "route_norm": True, "mup_enabled": True, "rope_scaling": None,
              "tie_word_embeddings": False, "hidden_act": "silu",
              "num_shared_experts": 1}
    differs = sorted(k for k, v in stated.items() if config[k] != v)
    if differs:
        raise ValueError(
            "benchmark/reference/afmoe.py states %s, the configuration %s"
            % ({k: stated[k] for k in differs},
               {k: config[k] for k in differs}))


def train_setup(config, traffic, global_batch, seed):
    """(loss_fn, params on the device, example batch) through the program's
    ``make_train_setup``: weights come from one jitted init of ``seed``."""
    from autodist_tpu.models import lm
    seq = traffic["seq"]
    cfg = model_config(config, seq)     # (a checkout without the preset
    held_to_the_reference(config)       # stops at the first of the two)
    loss_fn, params, example, _ = lm.make_train_setup(
        cfg, seq_len=seq, batch_size=global_batch, seed=seed)
    return loss_fn, params, example


def _layers(config):
    """(global layers, window layers) of those built."""
    window_layers, _ = layouts(config)
    return window_layers.count(0), window_layers.count(1)


def routed_layers(config):
    return config["num_hidden_layers"] - config["num_dense_layers"]


def active_matmul_params(config):
    """Matmul parameters ONE token passes through forward AND backward. A
    layer's attention: q, THE GATE and the output over ``heads x
    head_dim``, k and v over the K/V heads. The dense layers' SwiGLU; per
    routed layer the router over ALL its outputs, the shared expert, and of
    the k chosen experts the share an even router sends here (k x held /
    all: 0.5 of an expert, three matrices each); the untied head over the
    slice."""
    d, hd = config["hidden_size"], config["head_dim"]
    attn = d * hd * (3 * config["num_attention_heads"]
                     + 2 * config["num_key_value_heads"])
    f = config["moe_intermediate_size"]
    here = (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_num_experts"])
    moe = (d * config["router_num_experts"]
           + 3 * d * f * (config["num_shared_experts"] + here))
    return (config["num_hidden_layers"] * attn
            + routed_layers(config) * moe
            + config["num_dense_layers"] * 3 * d * config["intermediate_size"]
            + d * config["vocab_size"])


def dsa_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the GLOBAL layers' attention cores over all the
    causal pairs (``families/smallthinker.py:_core_flops``: Q K^T and P V
    over ``head_dim`` features for every QUERY head, once forward and twice
    backward, the kernel's recomputed scores not counted), under the name ``dsa_core_roofline_pct`` asks a family
    for (the global cores run under the program's ``dsa_core`` scope, its
    name for the attention function's call on grouped K/V heads; the window
    layers' run under ``swa_core``)."""
    return _core_flops(config, batch, causal_pairs(seq), _layers(config)[0])


def swa_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the WINDOW layers' attention cores over the pairs
    INSIDE the window alone (31,458,304 of the 134,225,920 causal pairs at
    16,384 positions and a window of 2,048): a kernel that walks tiles
    behind the window's far edge runs more and is credited this."""
    return _core_flops(config, batch,
                       window_pairs(seq, config["sliding_window"]),
                       _layers(config)[1])


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter (the gate's projection among them) plus
    the attention cores, the global layers' over the causal pairs and the
    window layers' over the pairs inside the window. Recomputation (a block
    recomputed in the backward pass, the flash kernels' recomputed scores)
    is NOT counted, nor the held experts a token did not choose, nor the
    gate's sigmoid and product (element-wise)."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + (dsa_core_flops_per_step(config, 1, seq)
               + swa_core_flops_per_step(config, 1, seq)) / seq)


def expert_flops_per_step(config, tokens):
    """FLOPs the program RUNS in the held experts for ``tokens`` tokens,
    forward + backward, every routed layer: EVERY held expert on EVERY
    token under its gate (``parallel/expert.py:_held_experts``), three
    [d, f] projections, 2 FLOPs a weight, once forward and twice backward;
    a recomputed forward is not counted. The model's work is the pairs that
    CHOSE a held expert (k / all of these rows under an even router: 8 in
    128), which is what ``train_flops_per_token`` counts."""
    return (18.0 * config["hidden_size"] * config["moe_intermediate_size"]
            * tokens * config["num_experts"] * routed_layers(config))
