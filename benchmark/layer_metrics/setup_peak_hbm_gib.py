"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip at the end
of ``setup.init`` (an arg of the span), in GiB: where it equals
``peak_hbm_gib`` the run's peak was set-up's, not the step's. None where
the program keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    return sa.memory_gib("setup.init", "hbm_peak")
