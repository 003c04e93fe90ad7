"""What of a chunk's incoming state survives the chunk, over the window:
the device counter ``mamba.chunk_carry`` (each step's mean over heads,
chunks and Mamba layers of ``exp(sum_chunk dt A)``, summed over the steps
that were read back) over the number of those steps, which the routed
layers' ``moe.chosen_pairs`` gives (every step adds the family's
``chosen_pairs_per_step``). Near 0 the states carried from chunk to chunk
do nothing and the recurrence is its chunks' own products; near 1 it is
all memory. None where the program counts no such thing or the family
cannot say how many steps were read.

A diagnostic of what the recurrence computes, not of the step's speed: the
dual form runs the same products whatever the decays are. The ``better``
and ``moves`` that ``BENCHMARK.json`` has to give every metric say nothing
here: read no direction into it from one PR to the next."""


def read(rec, ctx):
    counters = rec.get("counters") or {}
    carry, chosen = (counters.get("mamba.chunk_carry"),
                     counters.get("moe.chosen_pairs"))
    per_step = getattr(ctx.family, "chosen_pairs_per_step", None)
    if carry is None or not chosen or per_step is None:
        return None
    tokens = rec["tokens_per_step"] / rec["chips"]
    return carry / (chosen / per_step(ctx.config, tokens))
