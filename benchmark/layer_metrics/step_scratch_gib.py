"""The compiled step's scratch on one device in GiB: ``temp_bytes`` of the
program's step account (``Compiled.memory_analysis().temp_size_in_bytes``
of the compile the scope map pays), the temporaries that
``memory_stats()`` and with it ``peak_hbm_gib`` / ``hbm_at_rest_gib``
leave out. The whole account goes to the run's diagnostics
(``step_account``). None from an untraced run or a program without one."""
from benchmark import step_account as sa


def read(rec, ctx):
    return sa.memory_field(rec, "temp_bytes", sa.GIB)
