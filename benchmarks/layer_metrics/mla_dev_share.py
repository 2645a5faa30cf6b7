"""model step: latent attention's share of the decode tick's device time (%): time of the tick's
operations traced under ``attn/*`` (q_down, q_up, kv_down, absorb, kv_read: the kernel, out) over
all of the tick's (``trace.scope_s``).  None where the program traces no latent attention."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "tick_scope_seconds") or not f.tick_scope_seconds(ctx, "/attn/absorb"):
        return None
    part, whole = f.tick_scope_seconds(ctx, "/attn/"), f.tick_scope_seconds(ctx, "")
    return 100.0 * part / whole if part and whole else None
