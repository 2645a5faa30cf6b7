"""model step: routed picks that fell on identity (zero-compute) experts, of all picks in the window
(%), decode steps and prefill programs together (``tick_stats()["moe"]``: ``picks_zero`` / ``picks``).
Even routing gives zero experts / router width (33.3% at 256 of 768); such a pick costs nothing, so
the share sets how much of the routed work a step has at all."""


def read(ctx):
    f = ctx["family"]
    w = f.moe_window(ctx) if hasattr(f, "moe_window") else None
    return 100.0 * w["picks_zero"] / w["picks"] if w and w["picks"] and "picks_zero" in w else None
