"""engine: share (%) of the window's device time that went to prefill programs, from the device-queue ledger
alone: seconds of the ``prefill:*``, ``suffix:*`` and ``chunk`` segments, plus the ``chunk+tick``, ``piggyback``
and ``mixed`` ones less their ticks at the window's mean tick (under 10 ``tick`` segments: at the traced
``decode_step_dev_ms``), over all segments' seconds + the seconds the queue stood empty.  What a request's time
per token pays for other requests' admissions.  ``None`` on a program without the ledger, or where ticks have
to come off and no tick time is known (an untraced run of a window with fewer than 10 ``tick`` segments)."""


def read(ctx):
    w = ctx["read"]("device_queue_window")
    if not w or not w["total_s"] or w["prefill_s"] is None:
        return None
    return 100.0 * w["prefill_s"] / w["total_s"]
