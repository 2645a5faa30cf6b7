"""The plain reference of the ``mla_moe`` family: a decoder-only transformer
with latent attention (MLA) and sigmoid-routed experts, forward pass in float32.

Straight ``jax.numpy`` after the published description of DeepSeek-V3
(arXiv:2412.19437, sections 2.1.1 and 2.1.2) and its Hugging Face modelling
code, whose keys ``skt/A.X-K1``'s config uses.  Per layer, with ``n`` RMSNorm:

- ``c_q = n(h W_DQ)``; ``[q_nope | q_rope] = c_q W_UQ`` per head;
  ``[c | k_r] = h W_DKV``; ``c_kv = n(c)``; ``k_rope = rope(k_r)``, one per
  token, shared by all heads; ``q_rope = rope(q_rope)``;
  ``k_nope = c_kv W_UK``, ``v = c_kv W_UV`` per head;
  ``s = ([q_nope|q_rope] . [k_nope|k_rope]) * qk_head_dim^-0.5 * m^2`` with
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1`` (yarn); causal softmax;
  ``out = concat(softmax(s) v) W_O``.  This is the EXPANDED form: keys and
  values are built for every position, so it checks the program's absorbed
  decode (which attends over ``c_kv`` itself) as well as its prefill.
- layers below ``first_k_dense_replace``: SwiGLU of ``intermediate_size``.
  The others: ``sigma = sigmoid(h W_r)`` over all routed experts; groups of
  ``n_routed / n_group``; a group's score is the sum of its two highest
  ``sigma``; the ``topk_group`` best groups are kept; top-``k`` of ``sigma``
  inside them; ``g = sigma_sel / sum(sigma_sel) * routed_scaling_factor``;
  ``y = shared(h) + sum over picked experts e that are HELD of g_e expert_e(h)``.
  No capacity: every pick of a held expert is computed.

Departures, each noted: (1) **the share**: the reference is given the same
experts as the program (``held = [first, first + n)`` of the router's width);
what the absent experts would add is left out and that partial result goes on
(model-configs guide, section 4).  With all experts held it is the uncut model.
(2) rotary pairs are half-split (``rotate_half``) where the published
checkpoints interleave them and de-interleave at run time: a fixed permutation
of ``W_UQ``'s and ``W_DKV``'s rotary columns, which seeded weights do not
have.  (3) ``kv_b_proj`` is given as ``W_UK`` and ``W_UV``.  (4) no
score-correction bias (``topk_method`` is ``"none"``).

No kernels, no cache, no batching tricks; it imports nothing of the program.
Everything runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math
import sys
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

Q_BLOCK = 256  # query rows per attention block: scores stay [B, H, 256, T]


def yarn_inv_freq(hf: Dict[str, Any]) -> np.ndarray:
    """Inverse frequencies of the rotary columns (``qk_rope_head_dim``), yarn's
    NTK-by-parts where the config has it (transformers ``_compute_yarn_parameters``)."""
    dim, theta = int(hf["qk_rope_head_dim"]), float(hf["rope_theta"])
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = hf.get("rope_scaling")
    if not rs:
        return 1.0 / pos_freqs
    factor, orig = float(rs["factor"]), float(rs["original_max_position_embeddings"])
    beta_fast, beta_slow = float(rs.get("beta_fast") or 32), float(rs.get("beta_slow") or 1)

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr_dim(beta_fast)), 0), min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    return (1.0 / (factor * pos_freqs)) * (1.0 - extrapolate) + (1.0 / pos_freqs) * extrapolate


def mscales(hf: Dict[str, Any]):
    """-> (factor on cos/sin, factor on the softmax scale)."""
    rs = hf.get("rope_scaling")
    if not rs:
        return 1.0, 1.0
    factor = float(rs["factor"])

    def m(x):
        return 1.0 if factor <= 1.0 or not x else 0.1 * float(x) * math.log(factor) + 1.0

    return m(rs.get("mscale") or 1) / m(rs.get("mscale_all_dim") or 0), m(rs.get("mscale_all_dim") or 0) ** 2


def softmax_scale(hf: Dict[str, Any]) -> float:
    return (int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"])) ** -0.5 * mscales(hf)[1]


def _rope(x, cos, sin):
    import jax.numpy as jnp

    x1, x2 = jnp.split(x, 2, axis=-1)  # [B, T, heads, D/2]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def route(hf: Dict[str, Any], h, router):
    """-> (picked expert ids [.., k], weights [.., k], the scores the picks were
    taken from [.., n_routed]: sigma, -1 outside the kept groups)."""
    import jax
    import jax.numpy as jnp

    n, k = router.shape[-1], int(hf["num_experts_per_tok"])
    groups, keep = int(hf.get("n_group") or 1), int(hf.get("topk_group") or 1)
    sigma = jax.nn.sigmoid(h @ router)
    choice = sigma
    if groups > 1:
        g = sigma.reshape(sigma.shape[:-1] + (groups, n // groups))
        group_score = jnp.sort(g, axis=-1)[..., -2:].sum(-1)
        kept = jnp.argsort(-group_score, axis=-1)[..., :keep]
        in_kept = (jnp.arange(groups) == kept[..., None]).any(-2)
        choice = jnp.where(in_kept[..., None], g, -1.0).reshape(sigma.shape)
    idx = jnp.argsort(-choice, axis=-1)[..., :k]
    w = jnp.take_along_axis(sigma, idx, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    return idx, w * float(hf.get("routed_scaling_factor", 1.0)), choice


def round_through_e4m3(x):
    """float32 ``x`` rounded to the nearest value float8 e4m3 (``float8_e4m3fn``:
    4 exponent bits, 3 of mantissa, subnormals below 2^-6, largest 448) can hold,
    ties to even, **by arithmetic**, for the controls.  Not ``x.astype(float8)
    .astype(float32)``: on a TPU v5e bfloat16 weights came back from that pair
    exactly as they were (the weights control read a gap of 0.0 at every token;
    my chip run, PR 29), so a control built on it tests nothing there."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)
    e = jnp.maximum((bits >> 23) - 127, -6)  # the value's binade; subnormals share the lowest
    # the spacing of e4m3's values in that binade is 2^(e-3): both factors are exact powers of two
    inv_q = jax.lax.bitcast_convert_type((127 - (e - 3)) << 23, jnp.float32)
    q = jax.lax.bitcast_convert_type((127 + (e - 3)) << 23, jnp.float32)
    return jnp.clip(jnp.round(x * inv_q) * q, -448.0, 448.0)


def _swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def moe_ffn(hf: Dict[str, Any], p: Dict[str, Any], h, first_expert: int = 0, real=None):
    """An expert layer's feed-forward on normed input ``h``: the shared expert
    plus, for each HELD expert (``p["w_gate"]`` etc. stacked over them, ids
    ``first_expert ..``), its weighted result where it was picked -> (y,
    [near-tied, near-tied with a held expert among the two, all] counts over
    the positions ``real`` marks (default: all): the last pick and the best
    expert left out closer than bfloat16 can tell apart, a relative 2^-8)."""
    import jax
    import jax.numpy as jnp

    idx, w, choice = route(hf, h, p["router"])
    y = _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"]) if "ws_gate" in p else jnp.zeros_like(h)

    def add_expert(y, ew):  # the held experts, one at a time (a scan: one body to compile, the same sums)
        e, wg, wu, wd = ew
        g_e = jnp.where(idx == first_expert + e, w, 0.0).sum(-1)  # 0 where not picked
        return y + g_e[..., None] * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(add_expert, y, (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"], p["w_down"]))
    picked = (idx[..., :, None] == jnp.arange(choice.shape[-1])).any(-2)
    last_pick = jnp.where(picked, choice, jnp.inf).min(-1)
    best_left = jnp.where(picked, -jnp.inf, choice).max(-1)
    real = jnp.ones(h.shape[:-1], bool) if real is None else real
    near = ((last_pick - best_left) < last_pick * 2.0 ** -8) & real
    ids = jnp.arange(choice.shape[-1])
    held = (ids >= first_expert) & (ids < first_expert + p["w_gate"].shape[0])
    involved = ((choice == last_pick[..., None]) | (choice == best_left[..., None])) & held  # either of the two is held here
    return y, jnp.stack([near.sum(), (near & involved.any(-1)).sum(), real.sum()]).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _layer_fn(hf_items, scale: float, is_moe: bool, first_expert: int, kv_round: bool = False):
    """One layer as a jitted function of (x, weights, cos, sin, real positions).  ``hf_items``
    are the configuration's scalar keys (a cache key: no nested group), so the
    softmax ``scale``, which reads ``rope_scaling``, comes beside them."""
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)
    H = hf["num_attention_heads"]
    dn, dr, dv, C = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
    eps = float(hf["rms_norm_eps"])

    def layer(x, p, cos, sin, real):
        B, T, _ = x.shape
        h = _rms(x, p["attn_norm"], eps)
        q = (_rms(h @ p["w_dq"], p["q_norm"], eps) @ p["w_uq"]).reshape(B, T, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], axis=-1)
        ckv = h @ p["w_dkv"]
        c_kv = _rms(ckv[..., :C], p["kv_norm"], eps)
        k_rope = _rope(ckv[..., None, C:], cos, sin)  # [B, T, 1, dr]
        if kv_round:  # the control only: the cached row as a float8 cache would hold it
            c_kv, k_rope = round_through_e4m3(c_kv), round_through_e4m3(k_rope)
        k = jnp.concatenate([(c_kv @ p["w_uk"]).reshape(B, T, H, dn), jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
        v = (c_kv @ p["w_uv"]).reshape(B, T, H, dv)
        kpos = jnp.arange(T)

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
            ok = kpos[None, :] <= (q0 + jnp.arange(Q_BLOCK))[:, None]
            s = jnp.where(ok[None, None], s, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))  # [T/Qb, B, Qb, H, dv]
        x = x + jnp.moveaxis(o, 0, 1).reshape(B, T, H * dv) @ p["wo"]
        h = _rms(x, p["mlp_norm"], eps)
        if not is_moe:
            return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), jnp.zeros((3,), jnp.float32)
        y, near = moe_ffn(hf, p, h, first_expert, real)
        return x + y, near

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    import jax

    return jax.jit(lambda xi, idx, norm, head: _rms(xi[idx], norm, eps) @ head)


def logits_at(
    hf: Dict[str, Any],
    layer_weights,
    top: Dict[str, Any],
    sequences: Sequence[Sequence[int]],
    first_positions: Sequence[int],
    *,
    first_expert: int = 0,
    kv_round: bool = False,
    columns: Optional[Sequence[int]] = None,
    near_ties: Optional[List[float]] = None,
) -> List[np.ndarray]:
    """Reference logits for each sequence at positions ``first .. len-2``.

    ``layer_weights(i)`` returns layer ``i``'s float32 leaves (an expert
    layer's ``w_gate``/``w_up``/``w_down`` stacked over the HELD experts, ids
    ``first_expert ..``); it is called once per layer and the result dropped
    before the next.  Sequences are padded on the right to a multiple of
    ``Q_BLOCK``; under causal attention the padding cannot reach a real
    position (padded positions route too; their rows are never read).
    ``kv_round`` is the control: the cached row rounded through float8 e4m3.
    ``near_ties``, a list, receives ``[near, near with a held expert among the
    two, total]`` summed over expert layers: the real (position, layer) pairs
    whose last pick and best non-pick lie within a relative 2^-8."""
    import jax
    import jax.numpy as jnp

    hf_items = tuple(sorted((k, v) for k, v in hf.items() if isinstance(v, (int, float, str, bool, type(None)))))
    nd = int(hf.get("first_k_dense_replace", 0))
    T = -(-max(len(s) for s in sequences) // Q_BLOCK) * Q_BLOCK
    ids = np.zeros((len(sequences), T), np.int32)
    for i, s in enumerate(sequences):
        ids[i, : len(s)] = np.asarray(s, np.int32)
    out: List[np.ndarray] = []
    real = np.zeros(ids.shape, bool)
    for i, s in enumerate(sequences):
        real[i, : len(s)] = True
    ties = np.zeros(3)
    with jax.default_matmul_precision("highest"):
        x = top["tok_embed"][jnp.asarray(ids)]
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(hf), jnp.float32)[None, :]
        cos, sin = jnp.cos(ang) * mscales(hf)[0], jnp.sin(ang) * mscales(hf)[0]
        for i in range(hf["num_hidden_layers"]):
            p = layer_weights(i)
            x, t = _layer_fn(hf_items, softmax_scale(hf), i >= nd, first_expert, kv_round)(x, p, cos, sin, real)
            ties += np.asarray(t)
            del p
        R = -(-max(len(s) - 1 - f for s, f in zip(sequences, first_positions)) // 64) * 64
        head = _head_fn(float(hf["rms_norm_eps"]))
        for i, s in enumerate(sequences):
            n = len(s) - 1 - first_positions[i]
            idx = np.minimum(first_positions[i] + np.arange(R), T - 1).astype(np.int32)
            rows = head(x[i], jnp.asarray(idx), top["final_norm"], top["lm_head"])
            out.append(np.asarray(rows if columns is None else rows[:, jnp.asarray(columns)])[:n])
    if near_ties is not None:
        near_ties[:] = [float(t) for t in ties]
    if ties[2]:
        print(f"reference mla_moe: near-tied last pick (relative 2^-8) at {int(ties[0])} of {int(ties[2])} real "
              f"(position, expert layer) pairs ({100.0 * ties[0] / ties[2]:.3f}%), {int(ties[1])} of them with a held "
              f"expert among the two ({100.0 * ties[1] / ties[2]:.3f}%)", file=sys.stderr)
    return out
