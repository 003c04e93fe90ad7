"""How full the fullest expert HELD here is, over the window: its routed
(token, expert) pairs over what an even router gives every expert, per
layer and step, from the counters the compiled step fills under a share
(``moe.max_expert_pairs`` over the held experts, summed over layers and
steps, over ``moe.chosen_pairs`` / the router's width; the width is the
configuration's ``router_num_experts``). 1 = the router is even over ALL
its outputs; width / k (10.7 at 64 and 6) = every token of the step chose
this expert; 0 = no pair of the window chose a held expert. In a
deployment this chip's experts receive the pairs of all the chips that
share the layer, and the fullest of them paces the layer: an
expert-parallel exchange would follow this number, the dense products of
``parallel/expert.py:_held_experts`` do not (their time is the same
whatever the router does). ``expert_load_max_over_mean`` divides by the
held experts' own mean and an ``num_experts`` key: it has no number where
no pair chose a held expert and none for a configuration that names its
router's width otherwise. None where the program counts no chosen pairs
(every expert held) or the configuration names no router width."""


def read(rec, ctx):
    counters = rec.get("counters") or {}
    chosen = counters.get("moe.chosen_pairs")
    width = ctx.config.get("router_num_experts")
    if not chosen or not width:
        return None
    # (a counter that stayed at zero is not in the record: no held pair)
    return counters.get("moe.max_expert_pairs", 0.0) / (chosen / width)
