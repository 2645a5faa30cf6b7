"""model step: routed picks that landed on experts held by this rank, of all picks in the window
(%), decode steps and prefill programs together (``tick_stats()["moe"]``).  Even routing gives
held / routed (6.25% at 12 of 192)."""


def read(ctx):
    f = ctx["family"]
    w = f.moe_window(ctx) if hasattr(f, "moe_window") else None
    return 100.0 * w["picks_local"] / w["picks"] if w and w["picks"] else None
