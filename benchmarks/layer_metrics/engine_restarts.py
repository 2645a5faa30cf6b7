"""engine: crash-only restarts of the engine loop inside the window (``supervision_stats()``)."""


def read(ctx):
    return float(ctx["c1"]["engine_restarts"] - ctx["c0"]["engine_restarts"])
