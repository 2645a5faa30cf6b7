"""engine: mean over finished turns of time per output token (ms): beside the end-to-end median,
the statistic one stall moves (PR 22 was refused on its spread)."""


def read(ctx):
    return ctx["e2e"].get("tpot_mean_ms")
