"""kernels: the sparse decode path's share of the HBM roofline in the decode tick (%): the index keys of
the live context and the latent rows selected for it (the counters' mean decode step: 256 B and 1,152 B a
token a layer at the published widths) times the steps traced, over the device time under
``attn/index_score``, ``attn/select`` and ``attn/sparse_core`` in the tick and the published bandwidth."""


def read(ctx):
    f = ctx["family"]
    if not hasattr(f, "decode_mean"):
        return None
    mean, steps = f.decode_mean(ctx), f.traced_decode_steps(ctx)
    t = f.scope_seconds(ctx, f.TICK_SCOPE, ("attn/index_score", "attn/select", "attn/sparse_core"))
    if not mean or not steps or not t:
        return None
    conf = ctx["conf"]
    per_step = conf["hf"]["num_hidden_layers"] * (
        f.index_key_bytes(conf) * mean["pairs_causal"] + f.latent_row_bytes(conf) * mean["pairs_selected"])
    return 100.0 * per_step * steps / ctx["roofline"].peaks(ctx["device"]["kind"])["hbm_bytes_per_s"] / t
