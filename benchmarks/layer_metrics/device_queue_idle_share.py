"""device: share (%) of the window in which the device's queue stood empty as the engine's thread saw it: a
program was enqueued after the result of the last one before it had been waited for and read, from the
device-queue ledger (``idle.s`` over all segments' seconds + idle; ``tick_stats()["device_queue"]["idle"]
["by_phase"]`` says which loop phase the engine thread spent it in).  Over the whole window, where
``device_idle_share`` reads 3 s of trace; it cannot see a gap inside a segment.  ``None`` on a program without
the ledger."""


def read(ctx):
    w = ctx["read"]("device_queue_window")
    if not w or not w["total_s"]:
        return None
    return 100.0 * w["idle_s"] / w["total_s"]
