"""The plain reference of the ``scmoe`` family: LongCat-Flash's shortcut-connected
double layer, forward pass in float32, straight ``jax.numpy``.

After the public ``transformers`` ``longcat_flash`` module, which
``meituan-longcat/LongCat-Flash-Omni``'s config belongs to, from the builder's
knowledge of it (the configuration file lists under ``assumed`` every placement
the config's keys do not themselves state).  A layer, with ``n`` RMSNorm (eps
``rms_norm_eps``) and sublayer ``i`` in {0, 1} owning ``attn_norm_i``, its
attention weights, ``mlp_norm_i`` and a dense SwiGLU ``D_i`` of ``ffn_hidden_size``:

    x1  = x  + A_0(n(x))          h = n_mlp0(x1)
    m   = MoE(h)                  # the shortcut: joins the residual at the layer's end
    x2  = x1 + D_0(h)
    x3  = x2 + A_1(n(x2))
    out = x3 + D_1(n_mlp1(x3)) + m

- ``A_i``: latent attention as ``benchmarks/reference/mla_moe.py`` has it
  (``c_q = n(h W_DQ)``, ``[q_nope | q_rope] = c_q W_UQ`` per head; ``[c | k_r] = h
  W_DKV``, ``c_kv = n(c)``, one rotary key per token shared by the heads; keys
  ``[c_kv W_UK | k_rope]``, values ``c_kv W_UV``; causal softmax at
  ``qk_head_dim^-0.5``; ``W_O``), in the EXPANDED form, with two differences:
  both parts of the queries are multiplied by ``sqrt(hidden / q_lora_rank)``
  after ``W_UQ`` (``mla_scale_q_lora``) and the normed latent ``c_kv`` by
  ``sqrt(hidden / kv_lora_rank)`` before ``W_UK`` / ``W_UV``
  (``mla_scale_kv_lora``); the rotary key is not scaled.  Plain rotary tables
  (``rope_theta``, no scaling).
- ``MoE(h)``: ``s = softmax(h W_r)`` over ``n_routed + zero_expert_num``
  outputs; picks = top-``moe_topk`` of ``s + b`` (``b``: the correction bias, on
  the picks only); ``g_e = routed_scaling_factor * s_e`` for the picks, NOT
  normalised over them; ``MoE(h) = sum_{picked e < n_routed} g_e E_e(h) +
  (sum_{picked e >= n_routed} g_e) h``: a pick past the routed experts is an
  identity expert.  ``E_e``: SwiGLU of ``expert_ffn_hidden_size``.

Departures, each noted: (1) **the share**: the reference is given the same
experts as the program (``held = [first, first + n)`` of the ``n_routed``
routed ones); what the absent ranks' experts would add is left out and that
partial result goes on (model-configs guide, section 4).  The identity part is
computed IN FULL: a zero-compute expert is evaluated where the token lives.
With all experts held it is the uncut model.  (2) rotary pairs are half-split
and ``kv_b_proj`` is given as ``W_UK`` and ``W_UV``, as in ``mla_moe``.  (3) The
audio and vision towers and the codec of the Omni model are outside the
language model's config and are not built.

The controls ``no_zero`` (the identity part dropped) and ``no_scale`` (both
attention scales 1) are this function with a part of the mathematics left out:
what a program that dropped it would compute.

No kernels, no cache, no batching tricks; it imports nothing of the program.
Everything runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import sys
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmarks.reference.mla_moe import Q_BLOCK, _head_fn, _rms, _rope, _swiglu, round_through_e4m3  # noqa: F401


def softmax_scale(hf: Dict[str, Any]) -> float:
    return (int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"])) ** -0.5


def attention_scales(hf: Dict[str, Any]):
    """-> (factor on the queries, factor on the normed latent)."""
    E = float(hf["hidden_size"])
    return ((E / hf["q_lora_rank"]) ** 0.5 if hf.get("mla_scale_q_lora") else 1.0,
            (E / hf["kv_lora_rank"]) ** 0.5 if hf.get("mla_scale_kv_lora") else 1.0)


def route(hf: Dict[str, Any], h, router, bias=None):
    """-> (picked ids [.., k] over the router's whole width, weights [.., k],
    the scores the picks were taken from [.., width]: softmax + bias)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(h @ router, axis=-1)
    choice = s if bias is None else s + bias
    idx = jnp.argsort(-choice, axis=-1)[..., : int(hf["moe_topk"])]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if hf.get("norm_topk_prob", False):
        w = w / w.sum(-1, keepdims=True)
    return idx, w * float(hf.get("routed_scaling_factor", 1.0)), choice


def moe(hf: Dict[str, Any], p: Dict[str, Any], h, n_routed: int, first_expert: int = 0, real=None, zero: bool = True):
    """The expert layer on normed input ``h``: for each HELD expert
    (``p["w_gate"]`` etc. stacked over them, ids ``first_expert ..``) its
    weighted result where it was picked, and the identity experts' part in full
    (``zero`` False: the control without it) -> (m, counts over the positions
    ``real`` marks [near-tied last picks, those with a held expert among the
    two, positions, picks on identity experts, picks on held experts])."""
    import jax
    import jax.numpy as jnp

    idx, w, choice = route(hf, h, p["router"], p.get("router_bias"))
    on_zero = idx >= n_routed
    m = jnp.where(on_zero, w, 0.0).sum(-1)[..., None] * h if zero else jnp.zeros_like(h)

    def add_expert(m, ew):  # the held experts, one at a time (a scan: one body to compile, the same sums)
        e, wg, wu, wd = ew
        g_e = jnp.where(idx == first_expert + e, w, 0.0).sum(-1)  # 0 where not picked
        return m + g_e[..., None] * _swiglu(h, wg, wu, wd), None

    n_held = p["w_gate"].shape[0]
    m, _ = jax.lax.scan(add_expert, m, (jnp.arange(n_held), p["w_gate"], p["w_up"], p["w_down"]))
    picked = (idx[..., :, None] == jnp.arange(choice.shape[-1])).any(-2)
    last_pick = jnp.where(picked, choice, jnp.inf).min(-1)
    best_left = jnp.where(picked, -jnp.inf, choice).max(-1)
    real = jnp.ones(h.shape[:-1], bool) if real is None else real
    near = ((last_pick - best_left) < jnp.abs(last_pick) * 2.0 ** -8) & real
    ids = jnp.arange(choice.shape[-1])
    here = (ids >= first_expert) & (ids < first_expert + n_held)
    involved = ((choice == last_pick[..., None]) | (choice == best_left[..., None])) & here
    on_held = (idx >= first_expert) & (idx < first_expert + n_held)
    counts = [near.sum(), (near & involved.any(-1)).sum(), real.sum(),
              (on_zero & real[..., None]).sum(), (on_held & real[..., None]).sum()]
    return m, jnp.stack(counts).astype(jnp.float32)


def attention(hf: Dict[str, Any], p: Dict[str, Any], x, cos, sin, scaled: bool = True):
    """``A(n(x))`` of one sublayer, expanded form, causal, in blocks of ``Q_BLOCK`` queries."""
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    H, dn, dr, dv, C = (int(hf[k]) for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                              "v_head_dim", "kv_lora_rank"))
    eps = float(hf["rms_norm_eps"])
    q_scale, kv_scale = attention_scales(hf) if scaled else (1.0, 1.0)
    h = _rms(x, p["attn_norm"], eps)
    q = (_rms(h @ p["w_dq"], p["q_norm"], eps) @ p["w_uq"]).reshape(B, T, H, dn + dr) * q_scale
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], axis=-1)
    ckv = h @ p["w_dkv"]
    c_kv = _rms(ckv[..., :C], p["kv_norm"], eps) * kv_scale
    k_rope = _rope(ckv[..., None, C:], cos, sin)  # [B, T, 1, dr]: not scaled
    k = jnp.concatenate([(c_kv @ p["w_uk"]).reshape(B, T, H, dn), jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
    v = (c_kv @ p["w_uv"]).reshape(B, T, H, dv)
    kpos = jnp.arange(T)
    scale = softmax_scale(hf)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        ok = kpos[None, :] <= (q0 + jnp.arange(Q_BLOCK))[:, None]
        s = jnp.where(ok[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))  # [T/Qb, B, Qb, H, dv]
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H * dv) @ p["wo"]


@functools.lru_cache(maxsize=None)
def _layer_fn(hf_items, n_routed: int, first_expert: int, zero: bool = True, scaled: bool = True):
    """One double layer as a jitted function of (x, weights, cos, sin, real
    positions).  ``weights``: ``{"sub": the two sublayers' leaves stacked on a
    leading axis of 2, "moe": router, bias and the held experts}``."""
    import jax

    hf = dict(hf_items)
    eps = float(hf["rms_norm_eps"])

    def layer(x, p, cos, sin, real):
        p0, p1 = ({k: v[i] for k, v in p["sub"].items()} for i in (0, 1))
        x1 = x + attention(hf, p0, x, cos, sin, scaled)
        h = _rms(x1, p0["mlp_norm"], eps)
        m, counts = moe(hf, p["moe"], h, n_routed, first_expert, real, zero)
        x2 = x1 + _swiglu(h, p0["w_gate"], p0["w_up"], p0["w_down"])
        x3 = x2 + attention(hf, p1, x2, cos, sin, scaled)
        return x3 + _swiglu(_rms(x3, p1["mlp_norm"], eps), p1["w_gate"], p1["w_up"], p1["w_down"]) + m, counts

    return jax.jit(layer)


def logits_at(
    hf: Dict[str, Any],
    layer_weights,
    top: Dict[str, Any],
    sequences: Sequence[Sequence[int]],
    first_positions: Sequence[int],
    *,
    n_routed: int,
    first_expert: int = 0,
    zero: bool = True,
    scaled: bool = True,
    columns: Optional[Sequence[int]] = None,
    counts: Optional[List[float]] = None,
) -> List[np.ndarray]:
    """Reference logits for each sequence at positions ``first .. len-2``.

    ``layer_weights(i)`` returns double layer ``i``'s float32 leaves
    (:func:`_layer_fn` has the form); it is called once per layer and the
    result dropped before the next.  ``n_routed``: the routed experts over all
    ranks (the router is that plus ``zero_expert_num`` wide).  Sequences are
    padded on the right to a multiple of ``Q_BLOCK``; under causal attention the
    padding cannot reach a real position.  ``zero`` / ``scaled`` False are the
    controls.  ``counts``, a list, receives :func:`moe`'s five counts summed
    over the layers."""
    import jax
    import jax.numpy as jnp

    hf_items = tuple(sorted((k, v) for k, v in hf.items() if isinstance(v, (int, float, str, bool, type(None)))))
    T = -(-max(len(s) for s in sequences) // Q_BLOCK) * Q_BLOCK
    ids = np.zeros((len(sequences), T), np.int32)
    real = np.zeros(ids.shape, bool)
    for i, s in enumerate(sequences):
        ids[i, : len(s)] = np.asarray(s, np.int32)
        real[i, : len(s)] = True
    out: List[np.ndarray] = []
    total = np.zeros(5)
    dim, theta = int(hf["qk_rope_head_dim"]), float(hf["rope_theta"])
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    with jax.default_matmul_precision("highest"):
        x = top["tok_embed"][jnp.asarray(ids)]
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        for i in range(int(hf["num_layers"])):
            p = layer_weights(i)
            x, c = _layer_fn(hf_items, n_routed, first_expert, zero, scaled)(x, p, cos, sin, real)
            total += np.asarray(c)
            del p
        R = -(-max(len(s) - 1 - f for s, f in zip(sequences, first_positions)) // 64) * 64
        head = _head_fn(float(hf["rms_norm_eps"]))
        for i, s in enumerate(sequences):
            n = len(s) - 1 - first_positions[i]
            idx = np.minimum(first_positions[i] + np.arange(R), T - 1).astype(np.int32)
            rows = head(x[i], jnp.asarray(idx), top["final_norm"], top["lm_head"])
            out.append(np.asarray(rows if columns is None else rows[:, jnp.asarray(columns)])[:n])
    if counts is not None:
        counts[:] = [float(t) for t in total]
    if total[2]:
        picks = total[2] * int(hf["moe_topk"])
        print(f"reference scmoe: near-tied last pick (relative 2^-8) at {int(total[0])} of {int(total[2])} real "
              f"(position, expert layer) pairs ({100.0 * total[0] / total[2]:.3f}%), {int(total[1])} of them with a held "
              f"expert among the two; {100.0 * total[3] / picks:.2f}% of picks on identity experts, "
              f"{100.0 * total[4] / picks:.2f}% on held experts", file=sys.stderr)
    return out
