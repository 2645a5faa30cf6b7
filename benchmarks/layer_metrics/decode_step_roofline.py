"""kernels: the decode step's share of its HBM roofline (%): the bytes a step must read (all
weights once, the live keys and values once; ``benchmarks/roofline.py``) over the chip's
published bandwidth, divided by ``decode_step_dev_ms``.  Memory-bound: at 8 rows the step's
matmuls need well under 1% of the chip's int8 peak."""


def read(ctx):
    step_ms = ctx["read"]("decode_step_dev_ms") if ctx["trace"] else None
    if not step_ms:
        return None
    a, b = ctx["trace_span"]
    live = []
    for i in range(50):
        t = a + (b - a) * (i + 0.5) / 50
        live.append(sum(e["prompt_len"] + sum(1 for x in e["times"] if x <= t)
                        for e in ctx["events"] if e.get("times") and e["times"][0] <= t <= e["times"][-1]))
    peaks = ctx["roofline"].peaks(ctx["device"]["kind"])
    least_s = ctx["roofline"].decode_step_bytes(ctx["conf"], sum(live) / len(live)) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (step_ms * 1e-3)
