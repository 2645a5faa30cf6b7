"""model step: the routed and shared experts' share of the decode tick's device time (%): time of
the tick's operations traced under ``moe/*`` (router, dispatch, experts, combine, shared) over all
of the tick's (``trace.scope_s``).  None where the program has no such scopes."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "tick_scope_seconds"):
        return None
    part, whole = f.tick_scope_seconds(ctx, "/moe/"), f.tick_scope_seconds(ctx, "")
    return 100.0 * part / whole if part and whole else None
