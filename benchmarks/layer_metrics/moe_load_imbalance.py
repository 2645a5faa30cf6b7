"""model step: the busiest held expert's tokens over the mean of the held experts' in the window
(1.0: even), decode steps and prefill programs together (``tick_stats()["moe"]``)."""


def read(ctx):
    f = ctx["family"]
    w = f.moe_window(ctx) if hasattr(f, "moe_window") else None
    if not w or not sum(w["tokens_per_expert"]):
        return None
    t = w["tokens_per_expert"]
    return max(t) * len(t) / sum(t)
