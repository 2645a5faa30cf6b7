"""model step: the (query, key) pairs attended over the causal pairs a dense attention would attend (%),
over the window, decode steps and every prefill program together (the program's counters, a layer's worth
each): about ``index_topk`` over the mean context."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "dsa_window"):
        return None
    kinds = [w for w in (f.dsa_window(ctx, k) for k in ("decode", "chunk", "prefill")) if w]
    causal = sum(w["pairs_causal"] for w in kinds)
    return 100.0 * sum(w["pairs_selected"] for w in kinds) / causal if causal else None
