"""keye_vl2 family: Keye-VL-2.0's language model, text only (32 query heads
over 4 K/V heads with a per-head norm and RoPE, a sparse-attention indexer
that chooses ``sa_config.topk`` keys for every query, softmax-routed
experts with renormalised gates of which this chip holds a share, no
shared expert) as a configuration of the ONE decoder-only model of
``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.keye_vl2_30b_a3b`` with the file's
sizes), the seeded host batches (ids drawn from the file's slice of the
vocabulary), and the closed-form FLOPs the per-layer metrics divide by.
The closed forms are the yardstick and live here, not in the program.

In the file ``num_experts`` is what is HELD here (``experts_held`` names
them) and ``router_num_experts`` the router's published width.
"""
import dataclasses

from benchmark.families import lm as lm_family
from benchmark.families.lm import tokens_per_row  # noqa: F401
from benchmark.reference import keye_vl2 as reference  # noqa: F401  (run.py reads it)


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer's heads read ONE key head, the "
                         "configuration states %d"
                         % sa["indexer_num_kv_heads"])
    return dataclasses.replace(
        LMConfig.keye_vl2_30b_a3b(
            num_layers=config["num_hidden_layers"],
            dtype=jnp.dtype(config["dtype"]),
            max_seq_len=max(seq, config["max_position_embeddings"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], indexer_topk=sa["topk"],
        indexer_q_chunk=sa["q_chunk_size"],
        indexer_rope_dim=config["assumed"]["indexer_rope_dim"],
        mlp_dim=config["moe_intermediate_size"],
        num_experts=config["router_num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_renormalize=config["norm_topk_prob"],
        experts_held=tuple(config["experts_held"]))


def held_to_the_reference(config):
    """``drivers/train_fit.py`` calls ``reference.nll_sum`` with the
    numbers ``reference/keye_vl2.py`` states as constants and hands it no
    configuration, so a file that states others would be compared with
    another model: refuse it here, by name."""
    stated = {"num_experts_per_tok": reference.TOP_K,
              "rms_norm_eps": reference.RMS_EPS,
              "rope_theta": reference.ROPE_THETA,
              "topk": reference.INDEX_TOPK,
              "indexer_rope_dim": reference.INDEX_ROPE_DIM,
              "norm_topk_prob": True}
    given = dict(config, topk=config["sa_config"]["topk"],
                 indexer_rope_dim=config["assumed"]["indexer_rope_dim"])
    differs = sorted(k for k, v in stated.items() if given[k] != v)
    if differs:
        raise ValueError(
            "benchmark/reference/keye_vl2.py states %s, the configuration "
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


def host_batches(config, traffic, global_batch, seed, count):
    """``families/lm.py``'s ``count`` seeded host batches, the SECOND of
    them the first once more, as ``families/deepseek_v2.py:host_batches``
    makes them and for its reason: the driver's second loss is then read
    on the sequence step 0 trained on, where it shows the step."""
    pool = lm_family.host_batches(config, traffic, global_batch, seed, count)
    if count > 1:
        pool[1] = pool[0]
    return pool


def chosen_pairs(config, seq):
    """(query, key) pairs a sequence's queries keep: every key a query sees
    while there are no more than ``topk``, then ``topk``."""
    k = min(config["sa_config"]["topk"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def indexer_params(config):
    """Matmul parameters of one layer's indexer (its three projections)."""
    sa = config["sa_config"]
    return config["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def active_matmul_params(config):
    """Matmul parameters ONE token passes through forward AND backward:
    per layer q and the output over ``heads x head_dim``, k and v over the
    K/V heads, the router over ALL its outputs, and of the k chosen experts
    the share an even router sends here (k x held / all: 1 expert); the
    untied head over the slice. The indexer's projections are forward only
    and counted in :func:`dsa_index_flops_per_step`."""
    d, hd = config["hidden_size"], config["head_dim"]
    attn = d * hd * 2 * (config["num_attention_heads"]
                         + config["num_key_value_heads"])
    here = (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_num_experts"])
    moe = d * config["router_num_experts"] \
        + 3 * d * config["moe_intermediate_size"] * here
    return config["num_hidden_layers"] * (attn + moe) \
        + d * config["vocab_size"]


def dsa_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the attention cores over the CHOSEN pairs only,
    forward + backward without the kernel's recomputation: Q K^T and P V
    over ``head_dim`` features, 2 FLOPs a multiply-add, every query head,
    once forward and twice backward; every layer. A core that computes
    every causal pair and masks does 1 / 0.4375 of this at seq 8,192."""
    return (3.0 * 2 * 2 * config["head_dim"] * config["num_attention_heads"]
            * batch * chosen_pairs(config, seq) * config["num_hidden_layers"])


def dsa_index_flops_per_step(config, batch, seq):
    """Model FLOPs of the indexers: the three projections and the CAUSAL
    half of the index scores (``heads x head_dim`` multiply-adds a pair),
    forward only (no gradient passes a choice, and a recomputed block
    keeps the choice); every layer. They run as float32 products, several
    bfloat16 passes each: the peak they are held to is the bfloat16 one."""
    sa = config["sa_config"]
    scores = 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        * seq * (seq + 1) / 2
    return (batch * (scores + 2.0 * indexer_params(config) * seq)
            * config["num_hidden_layers"])


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter, the cores over the chosen pairs and the
    indexers' forward. Recomputation (each block is recomputed in the
    backward pass, the flash kernels recompute the scores, the lean head
    its logits) is NOT counted, nor the pairs a masked core computes and
    throws away."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + (dsa_core_flops_per_step(config, 1, seq)
               + dsa_index_flops_per_step(config, 1, seq)) / seq)


def expert_flops_per_step(config, tokens):
    """FLOPs the program RUNS in the held experts for ``tokens`` tokens,
    forward + backward, the layers together: EVERY held expert on EVERY
    token under its gate (``parallel/expert.py:_held_experts``), three
    [d, f] projections, 2 FLOPs a weight, once forward and twice backward;
    the blocks' recomputed forward is not counted. The model's work is the
    pairs that CHOSE a held expert (k / all of these rows under an even
    router: 8 in 128), which is what ``train_flops_per_token`` counts."""
    return (18.0 * config["hidden_size"] * config["moe_intermediate_size"]
            * tokens * config["num_experts"] * config["num_hidden_layers"])
