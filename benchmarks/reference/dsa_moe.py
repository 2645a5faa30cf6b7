"""The plain reference of the ``dsa_moe`` family: the ``mla_moe`` block (latent
attention, sigmoid-routed experts; ``benchmarks/reference/mla_moe.py`` has its
equations and departures, all of which hold here) with DeepSeek-V3.2's learned
sparse attention and the router's score-correction bias.  Forward pass in
float32, straight ``jax.numpy``, after the published description (DeepSeek-V3.2
-Exp's report, section 2.1, and the ``inference/model.py`` beside the released
checkpoint, whose keys ``deepseek-ai/DeepSeek-V3.2``'s config uses).

Added per layer, with ``x`` the attention's input after ``attn_norm``, ``c_q =
n(x W_DQ)`` the query latent MLA already makes, ``t`` a query position and ``s
<= t`` a cached one:

- index queries ``q^I_t = c_q W_IQ`` as ``index_n_heads`` heads of
  ``index_head_dim``; rotary over the FIRST ``qk_rope_head_dim`` lanes of each
  head (the published ``split([rope, nope])``), MLA's tables, the rest untouched;
- index key ``k^I_s = LayerNorm(x_s W_IK)`` (scale and bias, eps
  ``rms_norm_eps``: the published module's 1e-6), one per token, shared by all
  index heads, the same rotary;
- head weights ``w_t = x_t W_Iw * index_n_heads^-0.5 * index_head_dim^-0.5``;
- index score ``I(t, s) = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])``;
- selection ``S_t``: the ``min(index_topk, t + 1)`` positions ``s <= t`` of
  largest ``I(t, s)``, exactly; ties at the last place to the lowest position;
- attention: MLA as before, softmax over ``s in S_t`` only;
- router: ``choice = sigmoid(h W_r) + b`` picks the groups (by the sum of a
  group's two highest ``choice``) and the top-k inside them; the weights are the
  UNBIASED sigmoid scores of the picks, normalised, times the scaling factor.

Departures beyond ``mla_moe``'s: (5) the published inference code rotates
``q^I`` and ``k^I`` by a Hadamard matrix and quantises both to FP8 before the
product.  The rotation is orthogonal: it leaves every ``q . k`` unchanged in
exact arithmetic, and is left out.  The index key is kept as the configuration
states its cache (bfloat16 in the program, float32 here); the control
``idx_fp8`` reads what FP8-rounded index keys would select.  (6) The
multi-token-prediction module (``num_nextn_predict_layers``) is a draft head
for speculation that the published serving code does not run: not built.

The scores and the selection are made in blocks of ``Q_BLOCK`` queries, one
sequence at a time and a layer resident at a time, so that a 14k-token sequence
at the published widths fits a 16 GB chip.  It imports nothing of the program.
Everything runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import sys
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmarks.reference.mla_moe import (  # noqa: F401  (softmax_scale: the family's sizing and the tests read it here)
    _head_fn,
    _rms,
    _rope,
    _swiglu,
    mscales,
    round_through_e4m3,
    softmax_scale,
    yarn_inv_freq,
)

Q_BLOCK = 128  # query rows per block: index scores [B, 128, heads, T] and attention scores [B, H, 128, T]


def route(hf: Dict[str, Any], h, router, bias=None):
    """-> (picked expert ids [.., k], weights [.., k], the scores the picks were
    taken from [.., n_routed]: sigma + bias, -inf outside the kept groups)."""
    import jax
    import jax.numpy as jnp

    n, k = router.shape[-1], int(hf["num_experts_per_tok"])
    groups, keep = int(hf.get("n_group") or 1), int(hf.get("topk_group") or 1)
    sigma = jax.nn.sigmoid(h @ router)
    choice = sigma if bias is None else sigma + bias
    if groups > 1:
        g = choice.reshape(choice.shape[:-1] + (groups, n // groups))
        group_score = jnp.sort(g, axis=-1)[..., -2:].sum(-1)
        kept = jnp.argsort(-group_score, axis=-1)[..., :keep]
        in_kept = (jnp.arange(groups) == kept[..., None]).any(-2)
        choice = jnp.where(in_kept[..., None], g, -jnp.inf).reshape(sigma.shape)
    idx = jnp.argsort(-choice, axis=-1)[..., :k]
    w = jnp.take_along_axis(sigma, idx, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    return idx, w * float(hf.get("routed_scaling_factor", 1.0)), choice


def moe_ffn(hf: Dict[str, Any], p: Dict[str, Any], h, first_expert: int = 0, real=None):
    """``mla_moe.moe_ffn`` with the router's bias (``p["router_bias"]``, absent: none)
    -> (y, [near-tied, near-tied with a held expert among the two, all])."""
    import jax
    import jax.numpy as jnp

    idx, w, choice = route(hf, h, p["router"], p.get("router_bias"))
    y = _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"]) if "ws_gate" in p else jnp.zeros_like(h)

    def add_expert(y, ew):  # the held experts, one at a time
        e, wg, wu, wd = ew
        g_e = jnp.where(idx == first_expert + e, w, 0.0).sum(-1)  # 0 where not picked
        return y + g_e[..., None] * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(add_expert, y, (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"], p["w_down"]))
    picked = (idx[..., :, None] == jnp.arange(choice.shape[-1])).any(-2)
    last_pick = jnp.where(picked, choice, jnp.inf).min(-1)
    best_left = jnp.where(picked, -jnp.inf, choice).max(-1)
    real = jnp.ones(h.shape[:-1], bool) if real is None else real
    near = ((last_pick - best_left) < jnp.abs(last_pick) * 2.0 ** -8) & real
    ids = jnp.arange(choice.shape[-1])
    held = (ids >= first_expert) & (ids < first_expert + p["w_gate"].shape[0])
    involved = ((choice == last_pick[..., None]) | (choice == best_left[..., None])) & held
    return y, jnp.stack([near.sum(), (near & involved.any(-1)).sum(), real.sum()]).astype(jnp.float32)


def index_parts(hf: Dict[str, Any], p: Dict[str, Any], h, c_q, cos, sin, idx_round: bool = False):
    """-> (q_idx [B,T,Hi,Di], w_idx [B,T,Hi], k_idx [B,T,Di]) of normed input ``h``
    and query latent ``c_q``; ``idx_round``: the control, index keys through e4m3."""
    import jax.numpy as jnp

    B, T, _ = h.shape
    Hi, Di, dr = int(hf["index_n_heads"]), int(hf["index_head_dim"]), int(hf["qk_rope_head_dim"])

    def rotate(x):  # [B, T, heads, Di]: the first dr lanes
        return jnp.concatenate([_rope(x[..., :dr], cos, sin), x[..., dr:]], axis=-1)

    q_idx = rotate((c_q @ p["w_iq"]).reshape(B, T, Hi, Di))
    k = h @ p["w_ik"]
    mu = k.mean(-1, keepdims=True)
    k = (k - mu) * jnp.reciprocal(jnp.sqrt(((k - mu) ** 2).mean(-1, keepdims=True) + float(hf["rms_norm_eps"])))
    k_idx = rotate((k * p["ik_norm"] + p["ik_bias"])[:, :, None, :])[:, :, 0, :]
    if idx_round:
        k_idx = round_through_e4m3(k_idx)
    return q_idx, (h @ p["w_iw"]) * (Hi ** -0.5 * Di ** -0.5), k_idx


def select_block(scores, ok, topk: int):
    """Exact top-``topk`` of ``scores`` [.., T] among ``ok`` -> (keep [.., T] bool,
    [.., ] bool: the query's last kept and best dropped score lie within a
    relative 2^-8, what one bfloat16 rounding tells apart).  All of ``ok`` where
    fewer are; ties at the last place to the lowest position."""
    import jax.numpy as jnp

    T = scores.shape[-1]
    if T <= topk:
        return ok, jnp.zeros(scores.shape[:-1], bool)
    masked = jnp.where(ok, scores, -jnp.inf)
    srt = jnp.sort(masked, axis=-1)
    kth, nxt = srt[..., T - topk], srt[..., T - topk - 1]
    gt = masked > kth[..., None]
    eq = (masked == kth[..., None]) & ok
    room = topk - gt.sum(-1, keepdims=True)
    keep = (gt | (eq & (jnp.cumsum(eq, axis=-1) <= room))) & ok
    near = (nxt > -jnp.inf) & ((kth - nxt) < jnp.abs(kth) * 2.0 ** -8)
    return keep, near


@functools.lru_cache(maxsize=None)
def _layer_fn(hf_items, scale: float, is_moe: bool, first_expert: int, select: bool = True, idx_round: bool = False):
    """One layer as a jitted function of (x [1,T,E], weights, cos, sin, real [1,T])
    -> (x, [router near-ties (3), queries with a near-tied selection, queries that select, pairs kept])."""
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)
    H = hf["num_attention_heads"]
    dn, dr, dv, C = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
    eps, topk = float(hf["rms_norm_eps"]), int(hf["index_topk"])

    def layer(x, p, cos, sin, real):
        B, T, _ = x.shape
        h = _rms(x, p["attn_norm"], eps)
        c_q = _rms(h @ p["w_dq"], p["q_norm"], eps)
        q = (c_q @ p["w_uq"]).reshape(B, T, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], axis=-1)
        ckv = h @ p["w_dkv"]
        c_kv = _rms(ckv[..., :C], p["kv_norm"], eps)
        k_rope = _rope(ckv[..., None, C:], cos, sin)  # [B, T, 1, dr]
        k = jnp.concatenate([(c_kv @ p["w_uk"]).reshape(B, T, H, dn), jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
        v = (c_kv @ p["w_uv"]).reshape(B, T, H, dv)
        q_idx, w_idx, k_idx = index_parts(hf, p, h, c_q, cos, sin, idx_round)
        kpos = jnp.arange(T)

        def block(q0):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, q0, Q_BLOCK, axis=1)
            qpos = q0 + jnp.arange(Q_BLOCK)
            ok = jnp.broadcast_to((kpos[None, :] <= qpos[:, None])[None], (B, Q_BLOCK, T))
            near = jnp.zeros((B, Q_BLOCK), bool)
            if select:
                si = jnp.einsum("bqhd,bkd->bqhk", cut(q_idx), k_idx)
                si = jnp.einsum("bqhk,bqh->bqk", jnp.maximum(si, 0.0), cut(w_idx))
                ok, near = select_block(si, ok, topk)
            s = jnp.einsum("bqhd,bkhd->bhqk", cut(q), k) * scale
            s = jnp.where(ok[:, None], s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
            rq = cut(real)
            counts = jnp.stack([(near & rq).sum(), (rq & (qpos[None, :] >= topk)).sum(), (ok & rq[:, :, None]).sum()])
            return o, counts.astype(jnp.float32)

        o, counts = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))  # [T/Qb, B, Qb, H, dv]
        x = x + jnp.moveaxis(o, 0, 1).reshape(B, T, H * dv) @ p["wo"]
        h = _rms(x, p["mlp_norm"], eps)
        if not is_moe:
            return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), jnp.concatenate([jnp.zeros((3,)), counts.sum(0)])
        y, near = moe_ffn(hf, p, h, first_expert, real)
        return x + y, jnp.concatenate([near, counts.sum(0)])

    return jax.jit(layer)


def logits_at(
    hf: Dict[str, Any],
    layer_weights,
    top: Dict[str, Any],
    sequences: Sequence[Sequence[int]],
    first_positions: Sequence[int],
    *,
    first_expert: int = 0,
    select: bool = True,
    idx_round: bool = False,
    columns: Optional[Sequence[int]] = None,
    counts: Optional[List[float]] = None,
) -> List[np.ndarray]:
    """Reference logits for each sequence at positions ``first .. len-2``.

    ``layer_weights(i)`` returns layer ``i``'s float32 leaves; it is called
    once per layer, every sequence goes through the layer ONE AT A TIME, and
    the leaves are dropped before the next.  Sequences are padded on the right
    to one common length, a multiple of ``Q_BLOCK`` (of 1,024 past 1,024
    tokens, so that a cell's checks meet few shapes); under causal attention
    and a selection among ``s <= t`` the padding cannot reach a real position.
    ``select`` False is the ``dense`` control (every ``s <= t`` attended: the
    block without its indexer), ``idx_round`` the ``idx_fp8`` one.  ``counts``,
    a list, receives ``[router near-ties, of them with a held expert, real
    (position, expert layer) pairs, queries whose selection is near-tied at
    its last place, queries that select at all, pairs kept]`` summed over
    layers."""
    import jax
    import jax.numpy as jnp

    hf_items = tuple(sorted((k, v) for k, v in hf.items() if isinstance(v, (int, float, str, bool, type(None)))))
    nd = int(hf.get("first_k_dense_replace", 0))
    longest = max(len(s) for s in sequences)
    granule = Q_BLOCK if longest <= 1024 else 1024
    T = -(-longest // granule) * granule
    tot = np.zeros(6)
    out: List[np.ndarray] = []
    with jax.default_matmul_precision("highest"):
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(hf), jnp.float32)[None, :]
        cos, sin = jnp.cos(ang) * mscales(hf)[0], jnp.sin(ang) * mscales(hf)[0]
        xs, reals = [], []
        for s in sequences:
            ids = np.zeros((1, T), np.int32)
            ids[0, : len(s)] = np.asarray(s, np.int32)
            xs.append(top["tok_embed"][jnp.asarray(ids)])
            reals.append(jnp.asarray(np.arange(T)[None, :] < len(s)))
        for i in range(hf["num_hidden_layers"]):
            p = layer_weights(i)
            fn = _layer_fn(hf_items, softmax_scale(hf), i >= nd, first_expert, select, idx_round)
            for j in range(len(xs)):
                xs[j], t = fn(xs[j], p, cos, sin, reals[j])
                tot += np.asarray(t)
            del p
        R = -(-max(len(s) - 1 - f for s, f in zip(sequences, first_positions)) // 64) * 64
        head = _head_fn(float(hf["rms_norm_eps"]))
        for i, s in enumerate(sequences):
            n = len(s) - 1 - first_positions[i]
            idx = np.minimum(first_positions[i] + np.arange(R), T - 1).astype(np.int32)
            rows = head(xs[i][0], jnp.asarray(idx), top["final_norm"], top["lm_head"])
            out.append(np.asarray(rows if columns is None else rows[:, jnp.asarray(columns)])[:n])
    if counts is not None:
        counts[:] = [float(t) for t in tot]
    if tot[2] or tot[4]:
        print(f"reference dsa_moe: near-tied last pick (relative 2^-8) at {int(tot[0])} of {int(tot[2])} real (position, "
              f"expert layer) pairs ({100.0 * tot[0] / max(tot[2], 1):.3f}%), {int(tot[1])} with a held expert among the "
              f"two ({100.0 * tot[1] / max(tot[2], 1):.3f}%); selection near-tied at its last place for {int(tot[3])} of "
              f"{int(tot[4])} (query, layer) pairs that select ({100.0 * tot[3] / max(tot[4], 1):.3f}%); "
              f"{int(tot[5])} pairs kept", file=sys.stderr)
    return out
