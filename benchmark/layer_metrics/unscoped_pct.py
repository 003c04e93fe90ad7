"""Share of the step module's device time whose instruction maps to no
phase (compiler-made copies, ops traced outside every scope, or a map
that does not know the instruction): the instrument's own health."""
from benchmark.phases import step_phases


def read(rec, ctx):
    phases = step_phases(rec)
    if phases is None or not phases["total"]:
        return None
    return 100.0 * phases["unscoped"] / phases["total"]
