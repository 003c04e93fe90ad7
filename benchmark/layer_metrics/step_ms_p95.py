"""95th percentile of the wall time between the ends of consecutive steps
as the host saw them (``readers.step_intervals_ms``): under
``metrics_every=16`` one interval in 16 holds the host's visit, so this
reads a visit, and a host that sees ends late shows here first."""
from benchmark.readers import percentile, step_intervals_ms


def read(rec, ctx):
    if rec["kind"] != "train_fit":
        return None
    return percentile(step_intervals_ms(rec), 95)
