"""kernels: the index scoring's share of the bfloat16 peak in the chunk programs (%): 2 x index heads x
index width operations for every causal (query, key) pair the counter says had to be scored (the window's
mean chunk program, every layer) times the chunk programs traced, over the device time under
``attn/index_score`` in them.  Scoring dead keys of a padded view reads lower, never over 100."""


def read(ctx):
    f = ctx["family"]
    return f.chunk_pairs_share(ctx, "attn/index_score", "index", "pairs_causal") if hasattr(f, "chunk_pairs_share") else None
