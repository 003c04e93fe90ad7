"""Bytes in use on the fullest chip at the end of ``setup.first_step``
(``memory_stats()["bytes_in_use"]``, an arg of the span), in GiB: the
training state plus whatever set-up left alive; a step program's scratch
is not in it. None where the program keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    return sa.memory_gib("setup.first_step", "hbm_in_use")
