"""kernels: the whole prefill chunk program against the chip's published bfloat16 peak (%): the operations
the WINDOW's mean chunk program had to cost (``family.prefill_chunk_flops``: projections and feed-forward
of its tokens, the held experts by the counters' picks, an index score for every causal pair, attention
over the SELECTED pairs in the expanded count) times the chunk programs traced, over their device time.
Work the program does beyond that (re-expanding the context's keys and values every chunk, attending pairs
the selection dropped under a mask) lowers the share; nothing counted can pass what was needed."""


def read(ctx):
    f, tr = ctx["family"], ctx.get("trace")
    if not tr or not hasattr(f, "chunk_mean"):
        return None
    mean, runs = f.chunk_mean(ctx), f.chunk_runs(ctx)
    t = tr["program_s"].get(f.CHUNK_PROGRAM)
    if not mean or not runs or not t:
        return None
    flops = f.prefill_chunk_flops(ctx["conf"], mean["queries"], mean["pairs_causal"], mean["pairs_selected"], mean["picks_local"])
    return 100.0 * flops * runs / t / ctx["roofline"].peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
