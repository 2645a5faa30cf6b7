"""kernels: the sparse attention's share of the bfloat16 peak in the chunk programs (%): 2 x heads x (key
width + value width) operations, the expanded form's count, for every SELECTED pair (the counter; the
window's mean chunk program, every layer) times the chunk programs traced, over the device time under
``attn/sparse_core`` in them.  A kernel that computes the pairs the selection dropped and masks them reads
lower by that much."""


def read(ctx):
    f = ctx["family"]
    return f.chunk_pairs_share(ctx, "attn/sparse_core", "attention", "pairs_selected") if hasattr(f, "chunk_pairs_share") else None
