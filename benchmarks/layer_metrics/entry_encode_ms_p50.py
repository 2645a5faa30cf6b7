"""entry: median over the window's finished requests of ``usage.timings.encode_s`` (ms): receipt at the
``/dialog/`` handler's first line to the engine's ``submit`` (body parse, chat format, tokenizer), stamped by
the program on its own clock.  ``None`` when no finished request carries ``timings`` (an older program)."""
import statistics


def read(ctx):
    vals = [(e.get("usage") or {}).get("timings", {}).get("encode_s") for e in ctx["events"]
            if e["measured"] and not e.get("error") and "done" in e]
    vals = [v * 1e3 for v in vals if v is not None]
    return statistics.median(vals) if vals else None
