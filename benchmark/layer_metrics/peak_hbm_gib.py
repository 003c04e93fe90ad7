"""max over chips of ``memory_stats()["peak_bytes_in_use"]`` after the
window, in GiB."""


def read(rec, ctx):
    peak = rec.get("memory_peak_bytes")
    return peak / 2.0 ** 30 if peak else None
