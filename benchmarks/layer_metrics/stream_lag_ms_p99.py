"""entry: 99th percentile over the window's finished requests of ``usage.timings.stream_lag_max_s`` (ms): the
longest, in one response, from the engine's stamp of a token (the tick that carried it) to its delta being
handed to the socket: the engine-thread -> asyncio hop, the detokenizer, the event loop's backlog.  ``None``
when no finished request carries it (an older program)."""
from benchmarks.metrics import percentile


def read(ctx):
    vals = [(e.get("usage") or {}).get("timings", {}).get("stream_lag_max_s") for e in ctx["events"]
            if e["measured"] and not e.get("error") and "done" in e]
    vals = [v * 1e3 for v in vals if v is not None]
    return percentile(vals, 99) if vals else None
