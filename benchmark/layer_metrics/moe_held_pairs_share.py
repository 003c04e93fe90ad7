"""Of the routed (token, expert) pairs a layer's router chose, the share
that chose an expert HELD here, over the window: the counters the compiled
step fills, ``moe.routed_pairs`` (pairs routed to held experts) over
``moe.chosen_pairs`` (all T x k), summed over layers and steps. An even
router gives held / all (8 / 256 = 0.031); a reading far from it says the
seeded router favours or starves the held experts (0 where no pair of the
window chose one: a router that sends every token to the same experts
leaves them idle at most seeds). None where the program counts no chosen
pairs (every expert held)."""


def read(rec, ctx):
    counters = rec.get("counters") or {}
    chosen = counters.get("moe.chosen_pairs")
    if not chosen:
        return None
    # (a counter that stayed at zero is not in the record: no held pair)
    return counters.get("moe.routed_pairs", 0.0) / chosen
