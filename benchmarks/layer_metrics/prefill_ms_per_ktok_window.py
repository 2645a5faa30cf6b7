"""model step: device time of the one-shot prefill programs per 1000 prompt tokens they ran, over the WHOLE
window, no trace (ms): the device-queue ledger's seconds in ``prefill:*`` and ``suffix:*`` segments (one group
alone: the program, its insert, its activation) over their real prompt tokens.  Stands beside the traced
``prefill_dev_ms_per_ktok`` (the ~8 programs 3 s happen to hold, over tokens *submitted*).  ``None`` where the
window ran no such program, or on a program without the ledger."""


def read(ctx):
    w = ctx["read"]("device_queue_window")
    if not w:
        return None
    groups = [t for key, t in w["segs"].items() if key.startswith(("prefill:", "suffix:"))]
    tokens = sum(t["tokens"] for t in groups)
    return sum(t["s"] for t in groups) * 1e6 / tokens if tokens else None
