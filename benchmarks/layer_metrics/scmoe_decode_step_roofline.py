"""kernels: the ``scmoe`` family's decode step as a share of its HBM roofline (%): the bytes a step
must read (``family.decode_step_bytes``: every weight of both sublayers of every layer once, of the
held experts those HIT by the program's counter over the window, and the live latent rows once per
attention sublayer, twice a layer) over the published bandwidth, divided by ``decode_step_dev_ms``.
``mla_moe_decode_step_roofline`` reads the ``mla_moe`` family's counts the same way."""


def read(ctx):
    f = ctx["family"]
    step_ms = ctx["read"]("decode_step_dev_ms") if ctx.get("trace") else None
    if not step_ms or not hasattr(f, "attention_sublayers"):
        return None
    hit, live = f.experts_hit_per_layer_step(ctx), f.live_context_tokens(ctx)
    if not hit or live is None:
        return None
    bw = ctx["roofline"].peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * f.decode_step_bytes(ctx["conf"], live, hit) / bw / (step_ms * 1e-3)
