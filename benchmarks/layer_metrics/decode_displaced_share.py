"""engine: share (%) of the finished requests' decode time (first to last token on the engine's host) in
which the device was not running a decode step they rode: ``100 * (1 - sum(decode_steps) * decode_step_dev_ms
/ sum(decode_s))`` over ``usage.timings`` of the whole window.  What is left is other requests' prefill
programs between ticks and host gaps.  ``None`` without a trace (the step's device time comes from it) or
when no finished request carries ``timings`` (an older program)."""


def read(ctx):
    step_ms = ctx["read"]("decode_step_dev_ms")
    tms = [(e.get("usage") or {}).get("timings") for e in ctx["events"]
           if e["measured"] and not e.get("error") and "done" in e]
    tms = [t for t in tms if t and t.get("decode_s")]
    if not step_ms or not tms:
        return None
    ridden_ms = sum(t["decode_steps"] for t in tms) * step_ms
    return 100.0 * (1.0 - ridden_ms / (sum(t["decode_s"] for t in tms) * 1e3))
