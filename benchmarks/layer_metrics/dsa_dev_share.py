"""model step: the learned sparse attention's share of the device time of the chunk programs and the decode
tick (%): time of their operations traced under ``attn/index_q``, ``attn/index_k``, ``attn/index_score``,
``attn/select`` and ``attn/sparse_core`` over all of theirs (``trace.scope_s``).  None where the program
traces no indexer."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "scope_seconds"):
        return None
    programs = (f.CHUNK_SCOPE, f.TICK_SCOPE)
    part = [f.scope_seconds(ctx, p, f.DSA_SCOPES) for p in programs]
    whole = [f.scope_seconds(ctx, p, ("",)) for p in programs]
    if None in part or not sum(part) or not sum(whole):
        return None
    return 100.0 * sum(part) / sum(whole)
