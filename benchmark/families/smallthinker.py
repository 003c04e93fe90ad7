"""smallthinker family: SmallThinker-21BA3B-Instruct (sliding-window rotary
and global NoPE grouped-query attention layers 3 : 1 by the two published
layouts, a softmax router that reads the ATTENTION's normed input, ReGLU
experts of which this chip holds a share, no shared expert; an untied head)
as a configuration of the ONE decoder-only model of
``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.smallthinker_21b_a3b`` with the
file's sizes), the seeded host batches (ids drawn from the file's slice of
the vocabulary), and the closed-form FLOPs the per-layer metrics divide by.
The closed forms are the yardstick and live here, not in the program.

In the file ``moe_num_primary_experts`` is what is HELD here
(``experts_held`` names them) and ``router_num_experts`` the router's
published width; ``sliding_window_layout`` and ``rope_layout`` are the
published lists of all 52 flags, of which the first ``num_hidden_layers``
are built.
"""
import dataclasses
import sys

# (the pool's SECOND batch is its first once more, so that the driver's
# second loss is read on the sequence step 0 trained on, where it shows the
# step: ``families/deepseek_v2.py:host_batches`` and its reason)
from benchmark.families.deepseek_v2 import host_batches  # noqa: F401
from benchmark.families.lm import tokens_per_row  # noqa: F401
from benchmark.reference import smallthinker as reference  # noqa: F401  (run.py reads it)


def layouts(config):
    """(window flags, rotation flags) of the layers kept: the two published
    lists, cut from their start."""
    n = config["num_hidden_layers"]
    return (tuple(config["sliding_window_layout"][:n]),
            tuple(config["rope_layout"][:n]))


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    preset = getattr(LMConfig, "smallthinker_21b_a3b", None)
    if preset is None:
        # a checkout from before the preset cannot run this configuration:
        # it says so at once and by name
        sys.exit("benchmark: this checkout's autodist_tpu.models.lm.LMConfig "
                 "has no smallthinker_21b_a3b (no sliding window, no router "
                 "on the mixer's input, no ReGLU expert): the smallthinker "
                 "family cannot run on it")
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
        sliding_window=config["sliding_window_size"],
        mlp_dim=config["moe_ffn_hidden_size"],
        num_experts=config["router_num_experts"],
        experts_per_token=config["moe_num_active_primary_experts"],
        moe_renormalize=config["norm_topk_prob"],
        experts_held=tuple(config["experts_held"]))


def held_to_the_reference(config):
    """``drivers/train_fit.py`` calls ``reference.nll_sum`` with the
    numbers ``reference/smallthinker.py`` states as constants (and with the
    equations it writes out: a softmax router renormalised over the chosen,
    no bias, an untied head) and hands it no configuration, so a file that
    states others would be compared with another model: refuse it here, by
    name."""
    n, period = config["num_hidden_layers"], reference.PERIOD
    flags = [period[i % len(period)] for i in range(n)]
    stated = {"moe_num_active_primary_experts": reference.TOP_K,
              "rms_norm_eps": reference.RMS_EPS,
              "rope_theta": reference.ROPE_THETA,
              "sliding_window_size": reference.WINDOW,
              "moe_primary_router_apply_softmax": True,
              "norm_topk_prob": True, "rope_scaling": None,
              "tie_word_embeddings": False,
              "sliding_window_layout": flags, "rope_layout": flags}
    given = dict(config, sliding_window_layout=config[
        "sliding_window_layout"][:n], rope_layout=config["rope_layout"][:n])
    differs = sorted(k for k, v in stated.items() if given[k] != v)
    if differs:
        raise ValueError(
            "benchmark/reference/smallthinker.py states %s, the "
            "configuration %s" % ({k: stated[k] for k in differs},
                                  {k: given[k] for k in differs}))


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


def active_matmul_params(config):
    """Matmul parameters ONE token passes through forward AND backward. A
    layer's attention: q and the output over ``heads x head_dim``, k and v
    over the K/V heads; the router over ALL its outputs; of the k chosen
    experts the share an even router sends here (k x held / all: 0.75 of an
    expert, three matrices each); the untied head over the slice."""
    d, hd = config["hidden_size"], config["head_dim"]
    attn = d * hd * 2 * (config["num_attention_heads"]
                         + config["num_key_value_heads"])
    here = (config["moe_num_active_primary_experts"]
            * config["moe_num_primary_experts"]
            / config["router_num_experts"])
    moe = (d * config["router_num_experts"]
           + 3 * d * config["moe_ffn_hidden_size"] * here)
    return (config["num_hidden_layers"] * (attn + moe)
            + d * config["vocab_size"])


def causal_pairs(seq):
    """(query, key) pairs with ``j <= i``."""
    return seq * (seq + 1) // 2


def window_pairs(seq, window):
    """(query, key) pairs with ``j <= i`` and ``i - j < window``: query i
    sees min(i + 1, window) keys."""
    w = min(seq, window)
    return w * (w + 1) // 2 + (seq - w) * w


def _core_flops(config, batch, pairs, layers):
    """Q K^T and P V over ``head_dim`` features, 2 FLOPs a multiply-add,
    every QUERY head (four K/V heads shared by groups of 7 save bytes, no
    product), once forward and twice backward, the kernel's recomputed
    scores not counted."""
    return (3.0 * 2 * 2 * config["head_dim"] * config["num_attention_heads"]
            * batch * pairs * layers)


def dsa_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the GLOBAL layers' attention cores over all the
    causal pairs, under the name ``dsa_core_roofline_pct`` asks a family
    for (the global cores run under the program's ``dsa_core`` scope, its
    name for the attention function's call on grouped K/V heads; the window
    layers' run under ``swa_core``)."""
    return _core_flops(config, batch, causal_pairs(seq), _layers(config)[0])


def swa_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the WINDOW layers' attention cores over the pairs
    INSIDE the window alone (58,722,304 of the 134,225,920 causal pairs at
    16,384 positions and a window of 4,096): a kernel that walks tiles
    behind the window's far edge runs more and is credited this."""
    return _core_flops(
        config, batch, window_pairs(seq, config["sliding_window_size"]),
        _layers(config)[1])


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter plus the attention cores, the global
    layers' over the causal pairs and the window layers' over the pairs
    inside the window. Recomputation (a block recomputed in the backward
    pass, the flash kernels' recomputed scores) is NOT counted, nor the
    held experts a token did not choose."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + (dsa_core_flops_per_step(config, 1, seq)
               + swa_core_flops_per_step(config, 1, seq)) / seq)


def expert_flops_per_step(config, tokens):
    """FLOPs the program RUNS in the held experts for ``tokens`` tokens,
    forward + backward, every layer: EVERY held expert on EVERY token under
    its gate (``parallel/expert.py:_held_experts``), three [d, f]
    projections, 2 FLOPs a weight, once forward and twice backward; a
    recomputed forward is not counted. The model's work is the pairs that
    CHOSE a held expert (k / all of these rows under an even router: 6 in
    64), which is what ``train_flops_per_token`` counts."""
    return (18.0 * config["hidden_size"] * config["moe_ffn_hidden_size"]
            * tokens * config["moe_num_primary_experts"]
            * config["num_hidden_layers"])
