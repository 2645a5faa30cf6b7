"""device: ``memory_stats()["peak_bytes_in_use"]`` after the window, before the reference runs (GB)."""


def read(ctx):
    return ctx["hbm_peak_bytes"] / 1e9 if ctx["hbm_peak_bytes"] else None
