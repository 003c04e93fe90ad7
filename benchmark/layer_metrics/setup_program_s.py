"""The program's part of ``setup_s``: the spans ``setup.build`` +
``setup.init`` + ``setup.first_step`` of the program's set-up account
(``benchmark/setup_account.py``). ``setup_s`` less it is the machine's and
the benchmark's: imports, TPU start, weights, the reference, warm-up. Also
writes the whole account into the run's diagnostics (``setup_account``).
None where the program keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    acc = sa.account()
    if acc is None:
        return None
    rec["setup_account"] = sa.diagnostics(
        acc, ctx.t_start, rec.get("peak_bytes_after_reference", 0))
    return sum(sa.seconds(p) for p in (sa.phase(acc, n) for n in sa.ROOTS)
               if p is not None)
