"""kernels: device time of the selection per 1,000 queries (ms): time under ``attn/select`` in the chunk
programs and the decode tick (all layers) over the queries they ran: the window's mean chunk program's
tokens times the chunk programs traced, plus the mean decode step's rows times the steps traced.  A top-k
has no roofline; its time is what a later change moves."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "chunk_mean"):
        return None
    t = [f.scope_seconds(ctx, p, ("attn/select",)) for p in (f.CHUNK_SCOPE, f.TICK_SCOPE)]
    if None in t or not sum(t):
        return None
    chunk, runs = f.chunk_mean(ctx), f.chunk_runs(ctx)
    decode, steps = f.decode_mean(ctx), f.traced_decode_steps(ctx)
    queries = (chunk["queries"] * runs if chunk and runs else 0.0) + (decode["queries"] * steps if decode and steps else 0.0)
    return sum(t) * 1e3 / queries * 1000.0 if queries else None
