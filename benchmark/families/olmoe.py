"""olmoe family: OLMoE's block (RMSNorm, QK-norm, RoPE, a dropless top-k
SwiGLU feed-forward) as a configuration of the ONE decoder-only model of
``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.olmoe_1b_7b`` with the file's
sizes), the seeded host batches, and the closed-form FLOPs the per-layer
metrics divide by. The closed forms are the yardstick and live here, not
in the program.
"""
import dataclasses

from benchmark.families.lm import host_batches, tokens_per_row  # noqa: F401
from benchmark.reference import olmoe as reference  # noqa: F401  (run.py reads it)


def model_config(config, seq):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    return dataclasses.replace(
        LMConfig.olmoe_1b_7b(dtype=jnp.dtype(config["dtype"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        mlp_dim=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        max_seq_len=max(seq, config["max_position_embeddings"]),
        router_aux_loss_coef=config["assumed"]["router_aux_loss_coef"],
        router_z_loss_coef=config["assumed"]["router_z_loss_coef"])


def train_setup(config, traffic, global_batch, seed):
    """(loss_fn, params on the device, example batch) through the program's
    ``make_train_setup``: weights come from one jitted init of ``seed``."""
    from autodist_tpu.models import lm
    seq = traffic["seq"]
    loss_fn, params, example, _ = lm.make_train_setup(
        model_config(config, seq), seq_len=seq, batch_size=global_batch,
        seed=seed)
    return loss_fn, params, example


def active_matmul_params(config):
    """Matmul parameters ONE token passes through: attention's four
    projections, its k chosen experts' three, the router, per layer, and
    the untied head."""
    d, f = config["hidden_size"], config["intermediate_size"]
    per_layer = (4 * d * d + config["num_experts_per_tok"] * 3 * d * f
                 + d * config["num_experts"])
    return config["num_hidden_layers"] * per_layer + d * config["vocab_size"]


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter plus attention's 12 x seq x d per layer
    (QK^T and PV over the full S x S square, as ``families/lm.py`` counts
    it). Recomputation (the lean head recomputes each chunk's logits in
    its backward pass) is NOT counted."""
    return (6.0 * active_matmul_params(config)
            + 12.0 * config["num_hidden_layers"] * traffic["seq"]
            * config["hidden_size"])


def expert_flops_per_step(config, tokens):
    """FLOPs of the grouped expert matmuls for ``tokens`` tokens on one
    chip, forward + backward: each of the T x k routed pairs passes three
    [d, f] projections, 2 FLOPs a weight, once forward and twice backward
    (the input's and the weight's gradient): 18 d f T k per layer."""
    return (18.0 * config["hidden_size"] * config["intermediate_size"]
            * tokens * config["num_experts_per_tok"]
            * config["num_hidden_layers"])
