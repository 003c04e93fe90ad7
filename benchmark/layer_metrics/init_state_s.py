"""The ``setup.init`` span of the program's set-up account: ``Runner.init``,
the optimizer state built and placed (or a checkpoint restored). None
where the program keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    return sa.phase_s("setup.init")
