"""A second architecture family, for the tests: the ``llama`` block with one
step its reference does not have, a scale per KV head on the values (as
published blocks scale theirs by a constant).  ``test_rehearsal.py`` copies
this file into a data directory of its own as ``families/tiny_vscale.py``;
nothing in ``benchmarks/`` names it.

The program has no such step, and needs none: ``served_params`` folds the scale
into ``wv``'s per-channel scales and into ``bv`` (powers of two, so the fold is
exact in every type), and the program serves the tree unchanged.  The family's
own reference regenerates the unfolded weights and applies the scale where the
block has it, so only this reference agrees with what was served.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks import roofline, weights
from benchmarks.reference import decoder

CONTROLS = ("int4",)
# float32 rehearsal on the CPU: sound runs read under 0.01, int4 weights and a
# reference without the value scale over 0.3 (test_rehearsal.py asserts both)
LIMITS = {"logit_gap_max": 0.05}
V_SCALE = (2.0, 0.5)  # one per KV head of tiny-rehearsal


def _per_channel(hf):
    import jax.numpy as jnp

    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return jnp.repeat(jnp.asarray(V_SCALE, jnp.float32), D)  # [KH * D]


def served_params(conf, seed):
    w = weights.stacked(conf["hf"], seed, conf["weights"]["head_ids"])
    s = _per_channel(conf["hf"])
    layers = dict(w["layers"])
    q, scale = layers["wv"]
    layers["wv"] = (q, scale * s)
    layers["bv"] = (layers["bv"].astype(s.dtype) * s).astype(layers["bv"].dtype)
    return {"layers": layers, **w["top"]}


@functools.lru_cache(maxsize=None)
def _layer_fn(hf_items):
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)
    H, KH = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    eps = float(hf["rms_norm_eps"])

    def layer(x, p, cos, sin):
        B, T, _ = x.shape
        h = decoder._rms(x, p["attn_norm"], eps)
        q = decoder._rope((h @ p["wq"] + p["bq"]).reshape(B, T, H, D), cos, sin)
        k = decoder._rope((h @ p["wk"] + p["bk"]).reshape(B, T, KH, D), cos, sin)
        v = (h @ p["wv"] + p["bv"]).reshape(B, T, KH, D) * jnp.asarray(V_SCALE, jnp.float32)[None, None, :, None]
        k, v = jnp.repeat(k, H // KH, axis=2), jnp.repeat(v, H // KH, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D**-0.5)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v).reshape(B, T, H * D)
        x = x + o @ p["wo"]
        h = decoder._rms(x, p["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]

    return jax.jit(layer)


def reference_logits(conf, seed, sequences, first_positions, columns, control=None):
    import jax
    import jax.numpy as jnp

    if control not in (None, *CONTROLS):
        raise ValueError(f"no control {control!r}: {CONTROLS}")
    hf = conf["hf"]
    top = weights.dequantised_top(hf, seed, tuple(conf["weights"]["head_ids"]))
    T = max(len(s) for s in sequences)
    ids = np.zeros((len(sequences), T), np.int32)
    for i, s in enumerate(sequences):
        ids[i, : len(s)] = s
    layer = _layer_fn(weights.scalar_items(hf))
    with jax.default_matmul_precision("highest"):
        x = top["tok_embed"][jnp.asarray(ids)]
        cos, sin = decoder._rope_tables(hf, jnp.arange(T))
        for i in range(hf["num_hidden_layers"]):
            x = layer(x, weights.dequantised_layer(hf, seed, i, 64 if control == "int4" else 0), cos, sin)
        x = decoder._rms(x, top["final_norm"], float(hf["rms_norm_eps"]))
        cols = jnp.asarray(columns)
        return [np.asarray(x[i, f : len(s) - 1] @ top["lm_head"][:, cols])
                for i, (s, f) in enumerate(zip(sequences, first_positions))]


decode_step_bytes = roofline.decode_step_bytes
decode_step_flops = roofline.decode_step_flops
