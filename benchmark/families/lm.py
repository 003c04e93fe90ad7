"""lm family: the decoder-only transformer of ``autodist_tpu/models/lm.py``.

Builds, from a configuration file, what the program's own entry points
take (``lm.make_train_setup`` / ``lm.make_decode_setup``), the seeded host
batches, and the closed-form FLOPs and bytes the per-layer metrics divide
by. The closed forms are the yardstick and live here, not in the program.
"""
import numpy as np

from benchmark.reference import lm as reference  # noqa: F401  (run.py reads it)

SIZE_KEYS = ("vocab_size", "d_model", "num_layers", "num_heads", "mlp_dim")


def model_config(config, max_seq_len):
    import jax.numpy as jnp
    from autodist_tpu.models.lm import LMConfig
    return LMConfig(max_seq_len=max_seq_len, dtype=jnp.dtype(config["dtype"]),
                    **{k: config[k] for k in SIZE_KEYS})


def train_setup(config, traffic, global_batch, seed):
    """(loss_fn, params on the device, example batch) through the program's
    ``make_train_setup``: weights come from one jitted init of ``seed``."""
    from autodist_tpu.models import lm
    seq = traffic["seq"]
    cfg = model_config(config, max(seq, config["max_seq_len"]))
    loss_fn, params, example, _ = lm.make_train_setup(
        cfg, seq_len=seq, batch_size=global_batch, seed=seed)
    return loss_fn, params, example


def host_batches(config, traffic, global_batch, seed, count):
    """``count`` distinct seeded host batches: uniform token ids, seq + 1
    wide (inputs and shifted targets)."""
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(
        0, config["vocab_size"],
        (global_batch, traffic["seq"] + 1)).astype(np.int32)}
        for _ in range(count)]


def tokens_per_row(traffic):
    return traffic["seq"]


def matmul_params(config):
    d = config["d_model"]
    return (config["num_layers"] * (4 * d * d + 2 * d * config["mlp_dim"])
            + d * config["vocab_size"])


def train_flops_per_token(config, traffic):
    """Model FLOPs of forward + backward per trained token, closed form:
    6 per matmul parameter (blocks and the untied head; the embedding
    lookups are not matmuls) plus attention's 12 x seq x d per layer
    (QK^T and PV over the full S x S square, as the program computes it).
    Recomputation (the lean head recomputes each chunk's logits in its
    backward pass) is NOT counted. From ``chip_smoke.py:lm_train_flops``."""
    return (6.0 * matmul_params(config)
            + 12.0 * config["num_layers"] * traffic["seq"] * config["d_model"])


def decode_setup(config):
    """(LMConfig, DecodeSetup) at the decode position-table length."""
    from autodist_tpu.models import lm
    cfg = model_config(config, config["assumed"]["max_seq_len_decode"])
    return cfg, lm.make_decode_setup(cfg)


def decode_train_stub(config, seed, replicas):
    """The smallest train set-up that yields the decode cell's weights: the
    runner is built and initialised from it, never stepped."""
    from autodist_tpu.models import lm
    cfg = model_config(config, config["assumed"]["max_seq_len_decode"])
    loss_fn, params, example, _ = lm.make_train_setup(
        cfg, seq_len=8, batch_size=replicas, seed=seed)
    return loss_fn, params, example


def decode_bytes_per_step(config, live_rows):
    """Bytes one decode step has to read, closed form: every matmul weight
    once in the served type (bf16) plus the K and V rows of the live
    sequences (``live_rows`` = sum over live slots of cached positions).
    Writes, the embedding rows and the logits are left out: a floor."""
    item = np.dtype(config["dtype"]).itemsize
    row = 2 * config["num_layers"] * config["d_model"] * item
    return matmul_params(config) * item + live_rows * row


def decode_flops_per_step(config, live, live_rows):
    return (2.0 * matmul_params(config) * live
            + 4.0 * config["d_model"] * config["num_layers"] * live_rows)
