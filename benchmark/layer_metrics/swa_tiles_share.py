"""Of the tiles a causal flash launch would walk, the share the windowed
launches of this program's step walk: ``attention.window_tiles`` over
``attention.window_tiles_causal``, the two counters
``ops/flash_attention.py`` adds from the shapes as it traces a windowed
launch (forward and backward alike), read from the set-up account, which
keeps the counters as they stood when the step had been traced (the
recorder is cleared before the window). At 16,384 positions, 512-row tiles
and a window of 4,096: 252 of 528 = 0.477, for 43.75 % of the causal
pairs; 1.0 would say the window only masks and every causal tile is still
walked. None where the program counts no windowed launch (no window layer,
a checkout from before the counters) or keeps no account."""
from benchmark import setup_account as sa


def read(rec, ctx):
    acc = sa.account()
    counters = (acc or {}).get("counters") or {}
    causal = counters.get("attention.window_tiles_causal")
    if not causal:
        return None
    return counters.get("attention.window_tiles", 0.0) / causal
