"""ouro family: Ouro-2.6B's looped stack (every layer and the final norm
run ``total_ut_steps`` times over the same weights, a norm on each
sub-layer's output, the head read after every pass, an exit gate that
weighs the passes' losses) as a configuration of the ONE decoder-only
model of ``autodist_tpu/models/lm.py``.

Builds, from a configuration file that keeps the keys of the model's
public ``config.json``, what the program's own entry point takes
(``lm.make_train_setup`` on ``LMConfig.ouro_2_6b`` with the file's
sizes), the seeded host batches (uniform ids over the whole vocabulary),
and the closed-form FLOPs the per-layer metrics divide by. The closed
forms are the yardstick and live here, not in the program: they count all
the passes, or ``mfu_pct`` would read a quarter.
"""
import dataclasses

# (the pool's SECOND batch is its first once more, so that the driver's
# second loss is read on the sequence step 0 trained on, where it shows the
# step: ``families/deepseek_v2.py:host_batches`` and its reason)
from benchmark.families.deepseek_v2 import host_batches  # noqa: F401
from benchmark.families.lm import tokens_per_row  # noqa: F401
from benchmark.reference import ouro as reference  # noqa: F401  (run.py reads it)


def model_config(config, seq):
    """The file's sizes on ``LMConfig.ouro_2_6b``. (A program from before
    the looped model has no such preset: the cell fails here, at once.)"""
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    return dataclasses.replace(
        LMConfig.ouro_2_6b(
            num_layers=config["num_hidden_layers"],
            dtype=jnp.dtype(config["dtype"]),
            max_seq_len=max(seq, config["max_position_embeddings"])),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        mlp_dim=config["intermediate_size"],
        dense_dim=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        loop_steps=config["total_ut_steps"],
        exit_entropy_coef=config["assumed"]["exit_entropy_coef"])


def held_to_the_reference(config):
    """``drivers/train_fit.py`` calls ``reference.nll_sum`` with the
    numbers ``reference/ouro.py`` states as constants (and with the
    equations it writes out: plain multi-head attention, an untied head,
    every pass run) and hands it no configuration, so a file that states
    others would be compared with another model: refuse it here, by
    name."""
    stated = {"total_ut_steps": reference.T,
              "exit_entropy_coef": reference.BETA,
              "rms_norm_eps": reference.RMS_EPS,
              "rope_theta": reference.ROPE_THETA,
              "num_key_value_heads": config["num_attention_heads"],
              "tie_word_embeddings": False, "early_exit_threshold": 1,
              "rope_scaling": None}
    given = dict(config,
                 exit_entropy_coef=config["assumed"]["exit_entropy_coef"])
    differs = sorted(k for k, v in stated.items() if given[k] != v)
    if differs:
        raise ValueError(
            "benchmark/reference/ouro.py states %s, the configuration %s"
            % ({k: stated[k] for k in differs},
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


def block_applications(config):
    """Blocks a token passes: every layer once a pass."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def active_matmul_params(config):
    """Matmul parameters ONE token passes through, each counted as often
    as it is used: every pass runs every layer (q, k, v, o over ``heads x
    head_dim`` and the SwiGLU's three projections) and reads the untied
    head; the exit gate's one row after every pass but the last. The
    embedding lookup is no matmul."""
    d, T = config["hidden_size"], config["total_ut_steps"]
    attn = 4 * d * config["num_attention_heads"] * config["head_dim"]
    layer = attn + 3 * d * config["intermediate_size"]
    return (T * (config["num_hidden_layers"] * layer
                 + d * config["vocab_size"]) + (T - 1) * d)


def attn_core_flops_per_step(config, batch, seq):
    """Model FLOPs of the attention cores over the causal pairs, S (S + 1)
    / 2, forward + backward without the kernel's recomputation: Q K^T and
    P V over ``head_dim`` features, 2 FLOPs a multiply-add, once forward
    and twice backward; every block APPLICATION."""
    return (3.0 * 2 * 2 * config["head_dim"] * config["num_attention_heads"]
            * batch * seq * (seq + 1) / 2 * block_applications(config))


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per ACTIVE matmul parameter, a parameter used in four passes counted
    four times, plus the attention cores of all the passes over the causal
    pairs. Recomputation (each block is recomputed in the backward pass,
    the flash kernels recompute the scores, the lean head its chunks'
    logits) is NOT counted."""
    seq = traffic["seq"]
    return (6.0 * active_matmul_params(config)
            + attn_core_flops_per_step(config, 1, seq) / seq)
