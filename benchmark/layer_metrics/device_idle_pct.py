"""1 - (union of the op intervals on a chip) / traced window, mean over
chips, in percent."""
from benchmark.readers import device_busy


def read(rec, ctx):
    got = device_busy(rec)
    if got is None:
        return None
    busy_s, window_s = got
    return 100.0 * (1.0 - busy_s / window_s)
