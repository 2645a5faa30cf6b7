"""admission: decode slots in use, averaged over samples of ``engine.num_active`` taken every
100 ms of the traced part of the window.  (The engine has no per-tick row counter: PERF.md, Open questions.)"""


def read(ctx):
    s = ctx["samples"]["rows_active"]
    return sum(s) / len(s) if s else None
