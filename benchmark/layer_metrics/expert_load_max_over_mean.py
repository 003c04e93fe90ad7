"""Router load imbalance over the window: routed (token, expert) pairs of
the fullest expert over the mean per expert, per layer and step, from the
counters the compiled step fills (``moe.max_expert_pairs`` summed over
layers and steps, over ``moe.routed_pairs`` / E). 1 = perfectly even; a
grouped matmul's time follows the total, a capacity-bound or
expert-parallel path would follow the fullest expert."""


def read(rec, ctx):
    counters = rec.get("counters") or {}
    fullest = counters.get("moe.max_expert_pairs")
    pairs = counters.get("moe.routed_pairs")
    if not fullest or not pairs:
        return None
    return fullest / (pairs / ctx.config["num_experts"])
