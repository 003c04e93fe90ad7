"""autodist_tpu — a TPU-native distributed training strategy compiler.

A from-scratch JAX/XLA framework with the capabilities of the AutoDist
strategy compiler (reference ``autodist/__init__.py``): single-device user
code + a cluster description in, a compiled serializable per-variable
distribution strategy out, lowered to SPMD programs over a TPU device mesh.

Import-time behavior mirrors the reference (``__init__.py:35-50``): a
backend version gate and optimizer-capture patching.
"""
__version__ = "0.1.0"

import jax as _jax

# version gate (reference enforces TF in [1.15, 2.2], __init__.py:35-43):
# one installation is supported — the jax minor this tree is built and
# run against (jax.shard_map, pallas tpu.CompilerParams, jit._cache_size)
_JAX_MINOR = (0, 9)
if tuple(int(x) for x in _jax.__version__.split(".")[:2]) < _JAX_MINOR:
    raise RuntimeError("autodist_tpu requires jax >= %d.%d, found %s"
                       % (_JAX_MINOR + (_jax.__version__,)))

from autodist_tpu import const  # noqa: E402
from autodist_tpu import patch as _patch  # noqa: E402

if const.ENV.ADT_PATCH_OPTAX.val:
    _patch.patch_optax()  # reference patches optimizers at import (__init__.py:50)

from autodist_tpu.autodist import AutoDist, get_default_autodist, reset  # noqa: E402
from autodist_tpu.model_item import ModelItem  # noqa: E402
from autodist_tpu.resource_spec import ResourceSpec  # noqa: E402
from autodist_tpu.runtime.sentinel import (SentinelPolicy,  # noqa: E402
                                           TrainingDiverged)
from autodist_tpu.train_state import TrainState  # noqa: E402
from autodist_tpu import strategy  # noqa: E402

ENV = const.ENV

__all__ = ["AutoDist", "ModelItem", "ResourceSpec", "TrainState", "strategy",
           "SentinelPolicy", "TrainingDiverged",
           "ENV", "get_default_autodist", "reset", "__version__"]
