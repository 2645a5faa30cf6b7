"""engine: share (%) of the results the engine's thread read in the window that it had to wait for (longer
than the ledger's threshold): only such a result's arrival is the moment its program ended, so only it closes a
segment; the others' dispatches ride into the next one (``mixed``).  How far the other device-queue readers can
be believed.  ``None`` where no result was read, or on a program without the ledger."""


def read(ctx):
    w = ctx["read"]("device_queue_window")
    if not w or not w["waited"] + w["not_waited"]:
        return None
    return 100.0 * w["waited"] / (w["waited"] + w["not_waited"])
