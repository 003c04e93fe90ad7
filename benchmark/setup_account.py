"""What the set-up readers share: the program's set-up account
(``autodist_tpu.telemetry.setup_account()``: the ``setup.*`` phases of
build, init and the first step, each with the device's memory at its end
and the seconds JAX traced and lowered, compiled or loaded beneath it as
args; the counters and gauges of set-up), which outlives the recorder's
``clear()`` before the window. The driver hands a reader no runner, so the
account is reached through the program's accessor."""

ROOTS = ("setup.build", "setup.init", "setup.first_step")
GIB = 2.0 ** 30


def account():
    """The account, or None where the program keeps none (a checkout from
    before it) or recorded none (tracing off, no build)."""
    try:
        from autodist_tpu import telemetry
    except ImportError:
        return None
    get = getattr(telemetry, "setup_account", None)
    acc = get() if get is not None else None
    return acc if acc and phase(acc, ROOTS[0]) is not None else None


def phase(acc, name):
    """The last phase of that name, or None."""
    named = [p for p in acc["phases"] if p["name"] == name]
    return named[-1] if named else None


def seconds(p):
    return (p["end_ns"] - p["start_ns"]) / 1e9


def phase_s(name):
    """Seconds of the named phase (0.0 where it did not run); None where
    there is no account."""
    acc = account()
    if acc is None:
        return None
    p = phase(acc, name)
    return seconds(p) if p is not None else 0.0


def jax_s(key):
    """``key`` (``trace_lower_s`` / ``backend_compile_s`` /
    ``cache_load_s``: what JAX did beneath a phase and not beneath one
    inside it) summed over the phases; None where there is no account."""
    acc = account()
    if acc is None:
        return None
    return sum(p["args"].get(key, 0.0) for p in acc["phases"])


def memory_gib(name, key):
    """``key`` (``hbm_in_use`` / ``hbm_peak``) at the named phase's end in
    GiB; None where there is no account or no such phase."""
    acc = account()
    p = phase(acc, name) if acc is not None else None
    return p["args"].get(key, 0) / GIB if p is not None else None


def diagnostics(acc, t_start, peak_before_build=0):
    """The whole account for a run's diagnostics: every phase with its
    offset from the process's start, its own time (less the phases inside
    it), what JAX did beneath it and the two memory readings; the phase at
    whose end the peak first stood at its final value; counters; gauges."""
    rows = []
    for p in acc["phases"]:
        inside = [c for c in acc["phases"] if c["parent"] == p["id"]]
        rows.append(dict(
            p["args"], name=p["name"],
            offset_s=p["start_ns"] / 1e9 - t_start, s=seconds(p),
            self_s=seconds(p) - sum(seconds(c) for c in inside)))
    by_end = sorted(rows, key=lambda r: r["offset_s"] + r["s"])
    rows.sort(key=lambda r: r["offset_s"])
    final = by_end[-1].get("hbm_peak", 0) if by_end else 0
    set_in = None
    if final:  # 0: a backend that reports no memory
        set_in = ("before " + rows[0]["name"] if peak_before_build >= final
                  else next(r["name"] for r in by_end
                            if r.get("hbm_peak", 0) >= final))
    return {"phases": rows, "hbm_peak_bytes": final,
            "hbm_peak_set_in": set_in,
            "counters": acc["counters"], "gauges": acc["gauges"]}
