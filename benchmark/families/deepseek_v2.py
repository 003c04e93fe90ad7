"""deepseek_v2 family: DeepSeek-V2's layers (latent attention with
decoupled, YaRN-scaled rotary keys in every layer, a leading dense SwiGLU
layer, softmax-routed experts of which this chip holds a share beside the
shared experts, the sequence-wise balance loss) as a configuration of the
ONE decoder-only model of ``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.deepseek_v2_lite`` with the file's
sizes), the seeded host batches (ids drawn from the file's slice of the
vocabulary), and the closed-form FLOPs the per-layer metrics divide by.
The closed forms are the yardstick and live here, not in the program.

In the file ``n_routed_experts`` is what is HELD here (``experts_held``
names them) and ``router_num_experts`` the router's published width.
"""
import dataclasses

from benchmark.families import lm as lm_family
from benchmark.families.lm import tokens_per_row  # noqa: F401
from benchmark.reference import deepseek_v2 as reference  # noqa: F401  (run.py reads it)


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    return dataclasses.replace(
        LMConfig.deepseek_v2_lite(
            num_layers=config["num_hidden_layers"],
            dtype=jnp.dtype(config["dtype"]),
            max_seq_len=max(seq, config["max_position_embeddings"]),
            rope_scaling=dict(config["rope_scaling"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        first_k_dense_replace=config["first_k_dense_replace"],
        dense_dim=config["intermediate_size"],
        mlp_dim=config["moe_intermediate_size"],
        num_experts=config["router_num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_renormalize=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        num_shared_experts=config["n_shared_experts"],
        experts_held=tuple(config["experts_held"]),
        seq_aux=config["seq_aux"],
        router_aux_loss_coef=config["assumed"]["aux_loss_alpha"])


def held_to_the_reference(config):
    """``drivers/train_fit.py`` calls ``reference.nll_sum`` with the
    numbers ``reference/deepseek_v2.py`` states as constants and hands it
    no configuration, so a file that states others would be compared with
    another model: refuse it here, by name."""
    stated = {"num_experts_per_tok": reference.TOP_K,
              "rms_norm_eps": reference.RMS_EPS,
              "rope_theta": reference.ROPE_THETA,
              "rope_scaling": dict(reference.YARN, type="yarn"),
              "aux_loss_alpha": reference.ALPHA}
    given = dict(config, aux_loss_alpha=config["assumed"]["aux_loss_alpha"])
    differs = sorted(k for k, v in stated.items() if given[k] != v)
    if differs:
        raise ValueError(
            "benchmark/reference/deepseek_v2.py states %s, the configuration "
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
    them the first once more. ``drivers/train_fit.py`` compares two losses
    with the reference's, step 0's on ``pool[0]`` and step 1's on
    ``pool[1]``: on a fresh batch of uniform ids the loss of step 1 hardly
    shows what step 0 did (at the published widths a step left out, or
    computed in float8, read within the bfloat16 program's own distance
    there: ``records/pr31_loss_limit.jsonl``, round 1); on the sequence
    step 0 trained on it is the step's own effect, which every gradient
    leaf's sign and the learning rate decide. The window cycles through
    the pool as it does in every cell."""
    pool = lm_family.host_batches(config, traffic, global_batch, seed, count)
    if count > 1:
        pool[1] = pool[0]
    return pool


def routed_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def active_matmul_params(config):
    """Matmul parameters ONE token passes through. A latent mixer: q (no
    low-rank q), the latent's down- and up-projection, the output. The
    dense layer's SwiGLU; per routed layer the router over ALL its outputs,
    the shared experts, and of the k chosen experts the share an even
    router sends here (k x held / all: 0.75 of an expert); the untied head
    over the slice."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    nope, pe, v, rank = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                         config["v_head_dim"], config["kv_lora_rank"])
    mla_params = (d * h * (nope + pe) + d * (rank + pe)
                  + rank * h * (nope + v) + h * v * d)
    f = config["moe_intermediate_size"]
    here = (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_num_experts"])
    moe_params = (d * config["router_num_experts"]
                  + 3 * d * f * (config["n_shared_experts"] + here))
    return (config["num_hidden_layers"] * mla_params
            + routed_layers(config) * moe_params
            + config["first_k_dense_replace"] * 3 * d
            * config["intermediate_size"] + d * config["vocab_size"])


def mla_attn_flops_per_step(config, batch, seq):
    """Model FLOPs of the latent layers' attention cores, CAUSAL (half of
    the S x S square), forward + backward without the kernel's
    recomputation: Q K^T over nope + pe features and P V over v, 2 FLOPs a
    multiply-add, once forward and twice backward; every layer."""
    width = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
             + config["v_head_dim"])
    return (3.0 * 2 * width * config["num_attention_heads"] * batch
            * seq * (seq + 1) / 2 * config["num_hidden_layers"])


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter plus the latent layers' causal scores.
    Recomputation (each block is recomputed in the backward pass, the
    flash kernels recompute the scores) is NOT counted."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + mla_attn_flops_per_step(config, 1, seq) / seq)


def expert_flops_per_step(config, tokens):
    """FLOPs the program RUNS in the held experts for ``tokens`` tokens,
    forward + backward, the routed layers together: EVERY held expert on
    EVERY token under its gate (``parallel/expert.py:_held_experts``),
    three [d, f] projections, 2 FLOPs a weight, once forward and twice
    backward; the blocks' recomputed forward is not counted. The model's
    work is the pairs that CHOSE a held expert (k / all of these rows
    under an even router: 6 in 64), which is what
    ``train_flops_per_token`` counts."""
    return (18.0 * config["hidden_size"] * config["moe_intermediate_size"]
            * tokens * config["n_routed_experts"] * routed_layers(config))
