"""Device time per traced step of the recomputed forward: ops under
``transpose(jvp(loss))`` whose path holds ``rematted_computation``, the
part of ``bwd_ms_per_step`` that a block recomputed in the backward pass
(``models/lm.py:auto_remat_blocks``) runs a second time. What a block
keeps by name across its recomputation (the delta rule's output and
states, the flash kernel's output, log-sum-exp and q) is not in it."""
from benchmark.phases import phase_ms


def read(rec, ctx):
    return phase_ms(rec, "remat")
