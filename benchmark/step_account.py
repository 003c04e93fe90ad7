"""What the step-account readers share: the memory half of the program's
step account (``autodist_tpu.telemetry.step_account("jit_local_step")``:
what the compiler says the compiled step holds on a device, read off the
compile the scope map of a traced run already pays). The driver hands a
reader no runner, so the account is reached through the program's
accessor."""
from benchmark import phases

GIB = 2.0 ** 30
MIB = 2.0 ** 20


def memory(rec):
    """``{"temp_bytes", "argument_bytes", "output_bytes", "alias_bytes",
    "code_bytes", "peak_bytes"}`` of the step program, per device, kept in
    the run's diagnostics as ``step_account``: ``hbm_at_rest_gib`` plus
    ``temp_bytes`` is what the step needs of a chip. None from an untraced
    run (which must compile nothing more), a program that keeps no account
    (a checkout from before it) or a backend without an analysis."""
    key = "step_account"
    if key in rec:
        return rec[key]
    out = None
    if rec.get("kind") == "train_fit" and rec.get("tracer") is not None:
        try:
            from autodist_tpu import telemetry
        except ImportError:
            telemetry = None
        get = getattr(telemetry, "step_account", None)
        acc = get(phases.STEP_MODULE) if get is not None else None
        out = acc["memory"] if acc is not None else None
    rec[key] = out
    return out


def memory_field(rec, field, unit):
    got = memory(rec)
    return None if got is None or got.get(field) is None \
        else got[field] / unit
