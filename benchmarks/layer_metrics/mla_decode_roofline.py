"""kernels: the latent decode kernel's share of the HBM roofline (%): the live latent rows of the
pages a step lists (context tokens of the requests decoding x 576 numbers x 2 bytes x layers, the
pool's pad lanes and the dead rows of a last page not counted) times the steps traced, over the
device time of the ``latent_decode*`` operations (``trace.op_s``) and the published bandwidth.
At 64 heads against one row the kernel is bound by its matmuls and its per-page latency, not by
these bytes: the share says how far."""


def read(ctx):
    f, tr = ctx["family"], ctx.get("trace")
    if not tr or not hasattr(f, "latent_row_bytes"):
        return None
    t = sum(s for name, s in tr["op_s"].items() if name.startswith("latent_decode"))
    live, steps = f.live_context_tokens(ctx), f.traced_decode_steps(ctx)
    if not t or not live or not steps:
        return None
    bw = ctx["roofline"].peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * live * f.latent_row_bytes(ctx["conf"]) * ctx["conf"]["hf"]["num_hidden_layers"] * steps / bw / t
