"""Seeded decoder weights, made by the benchmark and not by the program.

The served weights and the reference's weights come from the same functions
here, so that the reference takes nothing the program has made.  A layer's
leaves depend only on (seed, layer index, leaf name): the program gets all
layers stacked from one jitted call, the reference regenerates one layer at a
time and dequantises it to float32.

Format served (what the configuration file calls ``int8-weight-only``): the
seven projections of each layer are int8 with one float32 scale per output
channel; embeddings, output head, norms and biases are bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np

PROJ = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# uniform int8 in [-128, 127] has this standard deviation
_U8_STD = (256.0**2 - 1.0) ** 0.5 / (12.0**0.5)


def key_words(seed: int, *path: int) -> np.ndarray:
    """Two uint32 words for a JAX threefry key, from any whole-number seed
    (the driver's are above 2**31, which ``jax.random.key`` refuses)."""
    ss = np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, *[int(p) for p in path]])
    return ss.generate_state(2, np.uint32)


def shapes(hf: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    E, F = hf["hidden_size"], hf["intermediate_size"]
    H, KH = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or E // H
    return {
        "wq": (E, H * D), "wk": (E, KH * D), "wv": (E, KH * D), "wo": (H * D, E),
        "w_gate": (E, F), "w_up": (E, F), "w_down": (F, E),
    }


def has_bias(hf: Dict[str, Any]) -> bool:
    return bool(hf.get("attention_bias", hf.get("model_type") == "qwen2"))


def layer_leaves(hf: Dict[str, Any], key) -> Dict[str, Any]:
    """One layer's leaves from its key: int8 payloads, per-channel scales that
    vary by +-25% (so a dropped or misplaced scale shows), norms near one and,
    where the family has them, small q/k/v biases."""
    import jax
    import jax.numpy as jnp

    E = hf["hidden_size"]
    out: Dict[str, Any] = {}
    names = list(PROJ) + ["attn_norm", "mlp_norm", "bq", "bk", "bv"]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    for name, shape in shapes(hf).items():
        kq, ks = jax.random.split(keys[name])
        q = jax.random.bits(kq, shape, jnp.uint8).astype(jnp.int8)
        std = shape[0] ** -0.5
        scale = (std / _U8_STD) * jax.random.uniform(
            ks, (1, shape[1]), jnp.float32, 0.75, 1.25
        )
        out[name] = (q, scale)
    for name in ("attn_norm", "mlp_norm"):
        out[name] = (1.0 + 0.1 * jax.random.normal(keys[name], (E,), jnp.float32)).astype(
            jnp.bfloat16
        )
    if has_bias(hf):
        sh = shapes(hf)
        for name, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
            out[name] = (0.1 * jax.random.normal(keys[name], (sh[w][1],), jnp.float32)).astype(
                jnp.bfloat16
            )
    return out


def top_leaves(hf: Dict[str, Any], key, head_ids: Tuple[int, int]) -> Dict[str, Any]:
    """Embedding, final norm and output head.  Only the head's columns for the
    ids ``head_ids[0] .. head_ids[1]`` (the printable ASCII bytes of the byte
    tokenizer) are drawn; every other column is zero.  Such a logit is 0 where
    the best of the drawn columns sits near +2.5, so greedy decoding never
    picks the end-of-sequence id (every run emits the same number of tokens)
    and every served token is one character that ``/dialog/`` streams to the
    client as text: the client reads the served token ids off the wire.  The
    head is still a full ``[hidden, vocab]`` matrix in every step."""
    import jax
    import jax.numpy as jnp

    E, V = hf["hidden_size"], hf["vocab_size"]
    ke, kn, kh = jax.random.split(key, 3)
    col = jnp.arange(V)
    drawn = (col >= head_ids[0]) & (col <= head_ids[1])
    head = (E**-0.5 * jax.random.normal(kh, (E, V), jnp.float32) * drawn[None, :]).astype(jnp.bfloat16)
    return {
        "tok_embed": jax.random.normal(ke, (V, E), jnp.float32).astype(jnp.bfloat16),
        "final_norm": (1.0 + 0.1 * jax.random.normal(kn, (E,), jnp.float32)).astype(jnp.bfloat16),
        "lm_head": head,
    }


def _root(seed: int):
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(jnp.asarray(key_words(seed, 0x77), jnp.uint32))


def layer_key(seed: int, layer):
    """The key of one layer (``layer`` may be an array of indices under vmap)."""
    import jax

    return jax.random.fold_in(_root(seed), layer)


def top_key(seed: int):
    import jax

    return jax.random.fold_in(_root(seed), 0xFFFF)


def all_keys(seed: int, n_layers: int):
    """(top key, stacked layer keys): the arguments of :func:`stacked_fn`."""
    import jax
    import jax.numpy as jnp

    return top_key(seed), jax.vmap(lambda i: layer_key(seed, i))(jnp.arange(n_layers))


def stacked_fn(hf: Dict[str, Any], head_ids: Tuple[int, int]):
    """The one jitted call that makes every weight: ``(top key, layer keys) ->
    {"top": ..., "layers": ...}`` with each layer leaf stacked on a leading axis."""
    import jax

    @jax.jit
    def make(top_k, layer_ks):
        return {
            "top": top_leaves(hf, top_k, head_ids),
            "layers": jax.vmap(functools.partial(layer_leaves, hf))(layer_ks),
        }

    return make


def stacked(hf: Dict[str, Any], seed: int, head_ids: Tuple[int, int]) -> Dict[str, Any]:
    """All weights of the served model, on the device, from ``seed``."""
    return stacked_fn(hf, tuple(head_ids))(*all_keys(seed, hf["num_hidden_layers"]))


@functools.lru_cache(maxsize=None)
def _dequantise_fn(hf_items, int4_group: int):
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)

    @jax.jit
    def make(key):
        out = {}
        for name, leaf in layer_leaves(hf, key).items():
            if isinstance(leaf, tuple):
                w = leaf[0].astype(jnp.float32) * leaf[1]
                if int4_group:
                    g = w.reshape(w.shape[0] // int4_group, int4_group, w.shape[1])
                    s = jnp.maximum(jnp.max(jnp.abs(g), axis=1, keepdims=True) / 7.0, 1e-12)
                    w = (jnp.clip(jnp.round(g / s), -8, 7) * s).reshape(w.shape)
                out[name] = w
            else:
                out[name] = leaf.astype(jnp.float32)
        return out

    return make


def scalar_items(hf: Dict[str, Any]):
    """The configuration's scalar keys as a hashable tuple (a cache key)."""
    return tuple(sorted((k, v) for k, v in hf.items() if isinstance(v, (int, float, str, bool, type(None)))))


def dequantised_layer(hf: Dict[str, Any], seed: int, layer: int, int4_group: int = 0) -> Dict[str, Any]:
    """Layer ``layer`` in float32 for the reference.  ``int4_group`` > 0 gives
    the control: every projection re-quantised to symmetric int4 in groups of
    that many rows along the contraction, the nearest precision below int8."""
    return _dequantise_fn(scalar_items(hf), int4_group)(layer_key(seed, layer))


def dequantised_top(hf: Dict[str, Any], seed: int, head_ids: Tuple[int, int]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: x.astype(jnp.float32), jax.jit(lambda k: top_leaves(hf, k, tuple(head_ids)))(top_key(seed))
    )
