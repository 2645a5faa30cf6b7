"""engine: host time to issue one decode tick (ms), ``tick_stats()`` totals at the window's
edges, divided by the ticks issued between them."""


def read(ctx):
    n = ctx["c1"]["ticks"] - ctx["c0"]["ticks"]
    return (ctx["c1"]["tick_issue_total_ms"] - ctx["c0"]["tick_issue_total_ms"]) / n if n else None
