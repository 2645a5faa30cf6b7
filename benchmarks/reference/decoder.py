"""The plain reference: a decoder-only transformer forward pass in float32.

Straight ``jax.numpy`` after the published descriptions of Mistral-7B
(arXiv:2310.06825) and Qwen2.5 (arXiv:2412.15115) and their Hugging Face
modelling code: RMSNorm before each block, rotary embeddings in the half-split
("rotate_half") layout, grouped-query causal attention with an optional sliding
window and optional q/k/v biases, a SwiGLU feed-forward block, an untied output
head.  No kernels, no cache, no batching tricks.  It imports nothing of the
program: the weights come from ``benchmarks/weights.py``, one layer at a time,
so that it fits beside (or after) a served 7B model on one chip.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
everything here runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

Q_BLOCK = 256  # query rows per attention block: scores stay [B, H, 256, T]


def _rope_tables(hf: Dict[str, Any], positions):
    import jax.numpy as jnp

    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    inv = 1.0 / (float(hf["rope_theta"]) ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    import jax.numpy as jnp

    x1, x2 = jnp.split(x, 2, axis=-1)  # [B, T, heads, D/2]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _window(hf: Dict[str, Any], layer: int):
    """The sliding window of ``layer``, or None (HF semantics: Qwen2 switches
    it by ``use_sliding_window`` and only for layers >= ``max_window_layers``)."""
    w = hf.get("sliding_window")
    if not w or not hf.get("use_sliding_window", hf.get("model_type") != "qwen2"):
        return None
    if hf.get("model_type") == "qwen2" and layer < int(hf.get("max_window_layers", 28)):
        return None
    return int(w)


@functools.lru_cache(maxsize=None)
def _layer_fn(hf_items, window, kv_round=None):
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)
    H, KH = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    eps = float(hf["rms_norm_eps"])

    def layer(x, p, cos, sin):
        B, T, _ = x.shape
        h = _rms(x, p["attn_norm"], eps)
        q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = _rope(q.reshape(B, T, H, D), cos, sin)
        k = _rope(k.reshape(B, T, KH, D), cos, sin)
        v = v.reshape(B, T, KH, D)
        if kv_round:  # the control only: keys and values as a lower-precision cache would hold them
            k, v = (t.astype(getattr(jnp, kv_round)).astype(jnp.float32) for t in (k, v))
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
        kpos = jnp.arange(T)

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * (D**-0.5)
            qpos = q0 + jnp.arange(Q_BLOCK)
            ok = kpos[None, :] <= qpos[:, None]
            if window is not None:
                ok = ok & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(ok[None, None], s, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))  # [T/Qb, B, Qb, H, D]
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * D)
        x = x + o @ p["wo"]
        h = _rms(x, p["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]

    return jax.jit(layer)


def logits_at(
    hf: Dict[str, Any],
    layer_weights,
    top: Dict[str, Any],
    sequences: Sequence[Sequence[int]],
    first_positions: Sequence[int],
    kv_round: Optional[str] = None,
    columns: Optional[Sequence[int]] = None,
) -> List[np.ndarray]:
    """Reference logits for each sequence at positions ``first .. len-2``: the
    rows that predict the tokens from index ``first + 1`` on.

    ``layer_weights(i)`` returns layer ``i``'s float32 leaves; it is called
    once per layer and the result dropped before the next, so one layer's
    weights are resident at a time.  Sequences are padded on the right to one
    length (a multiple of ``Q_BLOCK``); under causal attention the padding
    cannot reach a real position.  ``columns`` keeps only those vocabulary
    columns of the result.  ``kv_round`` (a ``jax.numpy`` dtype name) is for
    the control: keys and values rounded through that type.
    """
    import jax
    import jax.numpy as jnp

    hf_items = tuple(sorted((k, v) for k, v in hf.items() if isinstance(v, (int, float, str, bool, type(None)))))  # a cache key
    layer_fns = {w: _layer_fn(hf_items, w, kv_round) for w in {_window(hf, i) for i in range(hf["num_hidden_layers"])}}
    T = max(len(s) for s in sequences)
    T = -(-T // Q_BLOCK) * Q_BLOCK
    ids = np.zeros((len(sequences), T), np.int32)
    for i, s in enumerate(sequences):
        ids[i, : len(s)] = np.asarray(s, np.int32)
    out: List[np.ndarray] = []
    with jax.default_matmul_precision("highest"):
        x = top["tok_embed"][jnp.asarray(ids)]
        cos, sin = _rope_tables(hf, jnp.arange(T))
        for i in range(hf["num_hidden_layers"]):
            p = layer_weights(i)
            x = layer_fns[_window(hf, i)](x, p, cos, sin)
            del p
        # one head program per cell: every sequence's rows go through the same
        # padded [R, E] block (a slice per length would compile per length)
        R = -(-max(len(s) - 1 - f for s, f in zip(sequences, first_positions)) // 64) * 64
        head = _head_fn(float(hf["rms_norm_eps"]))
        for i, s in enumerate(sequences):
            n = len(s) - 1 - first_positions[i]
            idx = np.minimum(first_positions[i] + np.arange(R), T - 1).astype(np.int32)
            rows = head(x[i], jnp.asarray(idx), top["final_norm"], top["lm_head"])
            out.append(np.asarray(rows if columns is None else rows[:, jnp.asarray(columns)])[:n])
    return out


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    import jax

    return jax.jit(lambda xi, idx, norm, head: _rms(xi[idx], norm, eps) @ head)
