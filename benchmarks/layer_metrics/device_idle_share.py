"""device: share of the traced span in which no operation ran on the device (%), whole span,
not only while requests were in flight."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None
