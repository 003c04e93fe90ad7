"""Of the (query, key) pairs a causal attention sees, the share a sparse
attention's indexer chose, over the window: the counters the compiled step
fills, ``dsa.selected_pairs`` over ``dsa.causal_pairs``, summed over layers
and steps. At seq 8,192 and topk 2,048 it is 14,681,088 / 33,558,528 =
0.4375 whatever the weights (every query keeps min(its keys, topk)); 1.0
would say the choice is off, anything else that a query kept another
number of keys. None where the program counts no such pairs."""


def read(rec, ctx):
    counters = rec.get("counters") or {}
    seen = counters.get("dsa.causal_pairs")
    if not seen:
        return None
    return counters.get("dsa.selected_pairs", 0.0) / seen
