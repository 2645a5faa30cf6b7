"""model step: time of one decode step over the WHOLE window, no trace (ms): the device-queue ledger's seconds in
``tick`` segments (one tick alone between two results the host waited for: the previous result ready -> this one
ready) over their count and the steps a tick fuses.  Stands beside the traced ``decode_step_dev_ms`` (3 s of the
window).  ``None`` under 10 such segments, or on a program without the ledger."""


def read(ctx):
    w = ctx["read"]("device_queue_window")
    if not w or not w["tick_windowed"]:
        return None
    return w["tick_s"] * 1e3 / w["steps"]
