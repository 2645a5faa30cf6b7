"""kernels: the held experts' share of the HBM roofline in the ``scmoe`` family's decode tick (%):
bytes of the experts HIT (the program's counter over the window: distinct held experts hit per
layer-step, times one expert's three matrices, times the expert layer-steps traced: one expert layer
a double layer) over the device time under ``moe/experts`` and the chip's published bandwidth.
Picks on identity experts read nothing and are not in the count."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "attention_sublayers"):
        return None
    hit, t, steps = f.experts_hit_per_layer_step(ctx), f.tick_scope_seconds(ctx, "/moe/experts"), f.traced_decode_steps(ctx)
    if not hit or not t or not steps:
        return None
    bw = ctx["roofline"].peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * hit * f.expert_bytes(ctx["conf"]) * ctx["conf"]["hf"]["num_layers"] * steps / bw / t
