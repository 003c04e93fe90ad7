"""The entropy of a looped model's exit distribution, mean over tokens, in
nats, over the steps of the window that were read back: the device
counter ``loop.exit_entropy`` (each step's batch mean, summed over those
steps) over the number of those steps, which the counters give themselves
(the T masses ``loop.exit_mass_<t>`` of one step sum to 1). ``ln T`` (1.386
at four passes) is a uniform gate; 0 a gate that sends every token out
after the same pass. None where the program counts no such thing.

A diagnostic of the training dynamics, not of the step's speed: training
runs all the passes whatever the gate says (``early_exit_threshold`` is
1), so this moves no throughput until early exit or the paper's stage-II
gate training exists, and on the cell's uniform random ids the gate
collapses and the number is seed noise (0.02-0.14 over PR 44's runs). The
``better`` and ``moves`` that ``BENCHMARK.json`` has to give every metric
say nothing here: read no direction into it from one PR to the next."""


def steps_read(counters):
    """How many steps' counters were read back: the sum of their masses."""
    return sum(v for k, v in counters.items()
               if k.startswith("loop.exit_mass_"))


def read(rec, ctx):
    counters = rec.get("counters") or {}
    steps = steps_read(counters)
    if not steps or "loop.exit_entropy" not in counters:
        return None
    return counters["loop.exit_entropy"] / steps
