"""The routed layers' balance loss, per layer and step, over the window:
the device counter ``moe.aux_loss`` (each step's ``sum_l L_l``, unweighted,
summed over the steps read back) over routed layers x steps. Both come
from the counters the compiled step fills: ``moe.chosen_pairs`` counts
T x k a routed layer and step, so layers x steps = chosen pairs / (tokens
a chip and step x k). 1 = an even router over ALL its outputs, per
sequence; E / k (10.7 at 64 and 6) = every token of a sequence on the same
k experts: a router collapsing under the optimizer shows here at the
published width, where ``moe_held_pairs_share`` sees the held experts
only. None where the program counts no such loss."""


def read(rec, ctx):
    counters = rec.get("counters") or {}
    aux, chosen = counters.get("moe.aux_loss"), counters.get("moe.chosen_pairs")
    if not aux or not chosen:
        return None
    pairs = (rec["tokens_per_step"] / rec["chips"]
             * ctx.config["num_experts_per_tok"])
    return aux / (chosen / pairs)
