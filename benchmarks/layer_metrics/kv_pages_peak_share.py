"""KV memory: most pages of the pool in use at once (%), over samples of ``kv_stats()`` taken
every 100 ms of the traced part of the window."""


def read(ctx):
    s = ctx["samples"]["kv_pages_used"]
    total = ctx["c1"].get("kv_pages_total")
    return 100.0 * max(s) / total if s and total else None
