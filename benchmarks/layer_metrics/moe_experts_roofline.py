"""kernels: the held experts' share of the HBM roofline in the decode tick (%): bytes of the
experts HIT (the program's counter: distinct held experts hit per layer-step, times one expert's
three matrices, times the expert layer-steps traced) over the device time under ``moe/experts``
and the chip's published bandwidth.  A dense pass over all held experts reads the idle ones too and
so reads under what it streams at; a pass that skips them cannot read over 100%."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "experts_hit_per_layer_step"):
        return None
    hit, t, steps = f.experts_hit_per_layer_step(ctx), f.tick_scope_seconds(ctx, "/moe/experts"), f.traced_decode_steps(ctx)
    if not hit or not t or not steps:
        return None
    hf = ctx["conf"]["hf"]
    layers = hf["num_hidden_layers"] - hf["first_k_dense_replace"]
    bw = ctx["roofline"].peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * hit * f.expert_bytes(ctx["conf"]) * layers * steps / bw / t
