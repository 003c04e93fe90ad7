"""The compiled step's generated code on one device in MiB:
``code_bytes`` of the program's step account
(``generated_code_size_in_bytes``): what a straight-line step of many
block applications costs the chip and the compile cache. None from an
untraced run or a program without an account."""
from benchmark import step_account as sa


def read(rec, ctx):
    return sa.memory_field(rec, "code_bytes", sa.MIB)
