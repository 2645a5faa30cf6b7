"""model step: device time of the prefill programs per 1000 prompt tokens submitted (ms): traced
time of every program with ``prefill`` in its name, over the prompt tokens of the turns submitted
inside the traced span.  Reused prefixes count as submitted, so prefix reuse lowers this number."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    t = sum(s for name, s in tr["program_s"].items() if "prefill" in name)
    a, b = ctx["trace_span"]
    toks = sum(e["prompt_len"] for e in ctx["events"]
               if a <= e.get("submit", -1.0) < b)
    return t * 1e3 / toks * 1000.0 if toks and t else None
