"""model step: device time of one prefill chunk program over the WHOLE window (ms): the device-queue ledger's
seconds in ``chunk``, ``chunk+tick`` and ``mixed`` segments (a cell whose prompts are all longer than a chunk
dispatches no other prefill kind; with no row active a chunk has no tick behind it) less their ticks at the
window's mean tick (under 10 ``tick`` segments: at the traced ``decode_step_dev_ms``), over the chunks they held.
The time follows the context: ``start_tokens / groups`` of the same segments is the window's mean chunk start.
Stands beside the traced ``prefill_chunk_dev_ms``.  ``None`` where no chunk ran, where ticks have to come off
and no tick time is known, or on a program without the ledger."""


def read(ctx):
    w = ctx["read"]("device_queue_window")
    if not w or not w["chunk_groups"] or w["chunk_s"] is None:
        return None
    return w["chunk_s"] * 1e3 / w["chunk_groups"]
