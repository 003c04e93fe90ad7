"""Compiled or interpreted: the one probe every pallas kernel of the tree
asks (``ops/flash_attention.py``, ``parallel/expert.py:grouped_matmul``).

Compiled (Mosaic) on a TPU, interpreted on the CPU test backend, and
nothing else: a machine that came up on some other backend must not
quietly run a kernel interpreted. A device-less compile FOR a described
TPU (``jax.experimental.topologies``) runs where JAX's default backend is
the CPU and still needs the compiled kernels: it traces under
:func:`compiling_for_tpu`.
"""
import contextlib

import jax

_FOR_TPU = [False]


def interpret() -> bool:
    if _FOR_TPU[0]:
        return False
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "pallas kernels compile for tpu and interpret on cpu; the active "
        "jax backend is %r" % (backend,))


@contextlib.contextmanager
def compiling_for_tpu():
    """Kernels traced inside lower as ``tpu_custom_call`` whatever the
    default backend: for ``.lower(lowering_platforms=("tpu",))``."""
    prev, _FOR_TPU[0] = _FOR_TPU[0], True
    try:
        yield
    finally:
        _FOR_TPU[0] = prev
