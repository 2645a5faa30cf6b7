"""admission: how long a prefill program waits in the device's queue (ms): over every prefill dispatch of the
window, the start of the segment that held it (the previous result ready) less the moment the host enqueued it,
from the device-queue ledger: what the lookahead ticks queued ahead put in front of an admission (ROADMAP S7).
``None`` where the window dispatched no prefill, or on a program without the ledger."""


def read(ctx):
    w = ctx["read"]("device_queue_window")
    if not w:
        return None
    n = sum(t["lag_n"] for t in w["segs"].values())
    return sum(t["lag_s"] for t in w["segs"].values()) * 1e3 / n if n else None
