"""The host's own time per step: self time of the fit loop's spans
(``runner.*``, ``dstep.*``, ``prefetch.*``) inside the traced window,
without ``runner.wait_device`` (the wait for the device), over the traced
steps. None where the program has no ``runner.wait_device`` span: its
``runner.readback`` then holds the wait, and the sum would be the step."""
from benchmark.phases import DEVICE_WAIT, host_self_ms


def read(rec, ctx):
    tracer, spans = rec.get("tracer"), rec.get("spans")
    if (tracer is None or tracer.window_ns is None or not spans
            or not rec.get("traced_steps")
            or not any(s[0] == DEVICE_WAIT for s in spans)):
        return None
    own = host_self_ms(spans, tracer.window_ns)
    rec["host_self_ms_per_step"] = {
        name: ms / rec["traced_steps"] for name, ms in sorted(own.items())}
    return sum(own.values()) / rec["traced_steps"]
