"""Output tokens of the requests that completed INSIDE the window (whenever
they were due: the ramp's count too), over the window. Nothing completed
after the window's end counts, so this is what the engine sustained and
never more than it: the tokens of requests due in the window and finished
in a grace after it are the offered load under another name (PR 22)."""


def read(rec, ctx):
    if rec["kind"] != "decode_open_loop":
        return None
    w0, w1 = rec["w0"], rec["w0"] + rec["window_s"]
    return sum(r["cap"] for r in rec["requests"]
               if r["ok"] and w0 < r["done"] <= w1) / rec["window_s"]
