"""model step: the 90th percentile of a token's number of picks on REAL experts in the window (of
top-k: the others fell on identity experts), decode steps and prefill programs together, from the
program's histogram (``tick_stats()["moe"]``: ``real_picks_hist``).  The spread of compute a token:
its mean is printed as ``real_picks_mean`` on every run's diagnostics line (8 of 12 under even routing)."""


def read(ctx):
    f = ctx["family"]
    w = f.moe_window(ctx) if hasattr(f, "real_picks_quantile") else None
    return f.real_picks_quantile(w["real_picks_hist"], 0.9) if w and "real_picks_hist" in w else None
