"""Mixtral-style MoE MLP: top-k router with capacity-based dense dispatch.

Per BASELINE.md config #5 (Mixtral 8x7B continuous batching).  TPU-first choices:

- dispatch/combine are dense one-hot einsums (GShard/Switch style) — everything is a
  static-shape matmul that tiles onto the MXU; no sorting/ragged gathers;
- expert weight tensors carry a leading ``expert`` axis sharded over the mesh's
  ``expert`` (or folded into ``model``) axis; the dispatch einsum makes XLA emit the
  all-to-all over ICI;
- over-capacity tokens are dropped (standard capacity-factor semantics) — the router
  gates renormalise over the kept experts.

The decoder (:mod:`.llama`) calls :func:`moe_mlp` in place of its dense SwiGLU when
``cfg.is_moe``; everything else (attention, cache, generation) is shared.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops.quant import deq

from ..parallel.sharding import with_constraint
from .config import DecoderConfig


def expert_capacity(cfg: DecoderConfig, num_tokens: int) -> int:
    cap = math.ceil(
        num_tokens * cfg.experts_per_token / cfg.num_experts * cfg.expert_capacity_factor
    )
    # keep the MXU fed and the (8,128) tiling happy
    return max(8, int(math.ceil(cap / 8) * 8))


def moe_mlp(cfg: DecoderConfig, p, x: jnp.ndarray) -> jnp.ndarray:
    """x: [B, S, E] -> [B, S, E] through top-k routed experts."""
    B, S, E = x.shape
    T = B * S
    X, K = cfg.num_experts, cfg.experts_per_token
    C = expert_capacity(cfg, T)
    xt = x.reshape(T, E)

    router_logits = jnp.einsum("te,ex->tx", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)  # [T, X]
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [T, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((T, X, C), cfg.dtype)
    combine = jnp.zeros((T, X, C), jnp.float32)
    counts = jnp.zeros((X,), jnp.int32)
    for choice in range(K):  # K is tiny and static (2)
        onehot_e = jax.nn.one_hot(gate_idx[:, choice], X, dtype=jnp.int32)  # [T, X]
        pos = jnp.cumsum(onehot_e, axis=0) - onehot_e + counts[None, :]
        counts = counts + onehot_e.sum(axis=0)
        pos_in_e = (pos * onehot_e).sum(-1)  # [T]
        keep = pos_in_e < C
        pos_oh = jax.nn.one_hot(pos_in_e, C, dtype=cfg.dtype) * keep[:, None]
        slot = onehot_e.astype(cfg.dtype)[:, :, None] * pos_oh[:, None, :]
        dispatch = dispatch + slot
        combine = combine + gate_vals[:, choice, None, None] * slot.astype(jnp.float32)

    xe = jnp.einsum("txc,te->xce", dispatch, xt)  # [X, C, E]
    xe = with_constraint(xe, ("expert", None, "embed"))
    h = jax.nn.silu(jnp.einsum("xce,xef->xcf", xe, deq(p["w_gate"], cfg.dtype))) * jnp.einsum(
        "xce,xef->xcf", xe, deq(p["w_up"], cfg.dtype)
    )
    h = with_constraint(h, ("expert", None, "mlp"))
    ye = jnp.einsum("xcf,xfe->xce", h, deq(p["w_down"], cfg.dtype))  # [X, C, E]
    out = jnp.einsum("txc,xce->te", combine.astype(cfg.dtype), ye)
    return out.reshape(B, S, E)


# ---------------------------------------------------------------------------
# Dropless sigmoid-routed experts, a layer told which experts it holds
# (DeepSeek-V3 family; models/mla_moe.py)
# ---------------------------------------------------------------------------

# rows of one grouped-matmul tile, and the largest token count the dense pass takes
GROUP_TILE = 128
DENSE_MAX_TOKENS = 64
# counters a routed layer returns, per call: [picks, picks on held experts,
# layer-steps with a token, held experts hit] then tokens per held expert
MOE_STAT_HEAD = 4


@jax.named_scope("moe/router")
def route_sigmoid_groups(lm, top_k: int, xt: jnp.ndarray, router: jnp.ndarray):
    """-> (expert ids [T, K] over ALL ``router_experts``, weights [T, K] f32).

    float32 sigmoid scores; experts in ``n_group`` contiguous groups, a group
    scored by the sum of its two highest experts, the ``topk_group`` best
    groups kept, top-``K`` scores inside them; weights are the picked scores
    normalised over the picks (``norm_topk_prob``) times
    ``routed_scaling_factor``.  No score-correction bias: the config names none."""
    T = xt.shape[0]
    scores = jax.nn.sigmoid(
        jnp.einsum("te,ex->tx", xt.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    )
    choice = scores
    if lm.n_group > 1:
        grouped = scores.reshape(T, lm.n_group, -1)
        group_score = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0].sum(-1)  # [T, G]
        kept = jax.lax.top_k(group_score, lm.topk_group)[1]  # [T, topk_group]
        keep = jnp.zeros((T, lm.n_group), bool).at[jnp.arange(T)[:, None], kept].set(True)
        choice = jnp.where(keep[:, :, None], grouped, -1.0).reshape(T, -1)
    idx = jax.lax.top_k(choice, top_k)[1]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if lm.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
    return idx, w * lm.routed_scaling_factor


def _swiglu_tile(x, wg, wu, wd, w_row, dtype):
    """One expert over one tile of rows, each row's result times its weight
    BEFORE the down-projection (linear, so equal to weighting after it)."""
    h = jax.nn.silu(jnp.einsum("te,ef->tf", x, wg.astype(dtype))) * jnp.einsum("te,ef->tf", x, wu.astype(dtype))
    return jnp.einsum("tf,fe->te", (h.astype(jnp.float32) * w_row[:, None]).astype(dtype), wd.astype(dtype),
                      preferred_element_type=jnp.float32)


def held_experts_mlp(cfg: DecoderConfig, p, x: jnp.ndarray, valid: jnp.ndarray):
    """The routed part of an expert layer, as THIS rank computes it:
    ``sum over picked experts held here of g_e * expert_e(x)`` -> (y [B, S, E],
    stats int32 [MOE_STAT_HEAD + experts_held]).

    Routes over all ``router_experts``; picks that land on another rank's
    experts add nothing here (their ranks add them; nothing stands in for the
    exchange).  **No capacity and no dropped token**, whatever the router does:

    - up to ``DENSE_MAX_TOKENS`` tokens (a decode step): every held expert runs
      over every token and a ``[T, held]`` combine mask keeps the picks.  Exact,
      memory-bound (every held expert's weights are read once a step), and the
      work does not depend on the routing.
    - more (prefill): picks are sorted by expert and the held experts run as a
      grouped matmul over row tiles of ``GROUP_TILE``: a loop whose trip count is
      the number of LIVE tiles (``sum ceil(count_e / tile)``), so work follows
      the picks that landed here, and an expert that draws every token simply
      takes more tiles.

    ``valid`` [B, S] marks real tokens (pad positions and frozen slots route
    too, their rows are discarded by the caller; they are kept out of the
    counters and, in the grouped path, out of the work)."""
    lm = cfg.latent_moe
    B, S, E = x.shape
    T, K, Xh = B * S, cfg.experts_per_token, lm.experts_held
    xt = x.reshape(T, E)
    ok = valid.reshape(T)
    idx, w = route_sigmoid_groups(lm, K, xt, p["router"])
    with jax.named_scope("moe/dispatch"):
        local = idx - lm.first_expert  # [T, K]
        here = (local >= 0) & (local < Xh) & ok[:, None]
        local = jnp.where(here, local, Xh)  # Xh: not held here
        onehot = jax.nn.one_hot(local, Xh + 1, dtype=jnp.float32)[..., :Xh]  # [T, K, Xh]
        per_expert = onehot.sum((0, 1)).astype(jnp.int32)  # tokens per held expert
        stats = jnp.concatenate([
            jnp.stack([ok.sum() * K, here.sum(), ok.any().astype(jnp.int32), (per_expert > 0).sum()]).astype(jnp.int32),
            per_expert,
        ])
    if T <= DENSE_MAX_TOKENS:
        with jax.named_scope("moe/dispatch"):
            combine = jnp.einsum("tkx,tk->tx", onehot, w)  # [T, Xh] f32
        with jax.named_scope("moe/experts"):
            h = jax.nn.silu(jnp.einsum("te,xef->txf", xt, p["w_gate"].astype(cfg.dtype))) * jnp.einsum(
                "te,xef->txf", xt, p["w_up"].astype(cfg.dtype))
            h = (h.astype(jnp.float32) * combine[:, :, None]).astype(cfg.dtype)
            y = jnp.einsum("txf,xfe->te", h, p["w_down"].astype(cfg.dtype), preferred_element_type=jnp.float32)
        return y.astype(cfg.dtype).reshape(B, S, E), stats

    tm = GROUP_TILE
    with jax.named_scope("moe/dispatch"):
        N = T * K
        flat_e = local.reshape(N)
        order = jnp.argsort(flat_e, stable=True)  # held experts first, by expert; the rest last
        sorted_tok = (order // K).astype(jnp.int32)
        sorted_w = w.reshape(N)[order]
        offs = jnp.cumsum(per_expert) - per_expert  # first sorted position of each expert
        tiles = -(-per_expert // tm)
        tile_end = jnp.cumsum(tiles)
        n_tiles = tile_end[-1]

    def tile_body(t, acc):
        with jax.named_scope("moe/dispatch"):
            e = jnp.searchsorted(tile_end, t, side="right").astype(jnp.int32)
            j = t - (tile_end[e] - tiles[e])
            pos = offs[e] + j * tm + jnp.arange(tm, dtype=jnp.int32)
            live = pos < offs[e] + per_expert[e]
            pos = jnp.minimum(pos, N - 1)
            tok = sorted_tok[pos]
            w_row = jnp.where(live, sorted_w[pos], 0.0)
            x_tile = xt[tok]
        with jax.named_scope("moe/experts"):
            y_tile = _swiglu_tile(
                x_tile,
                jax.lax.dynamic_index_in_dim(p["w_gate"], e, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(p["w_up"], e, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(p["w_down"], e, 0, keepdims=False),
                w_row, cfg.dtype,
            )
        with jax.named_scope("moe/combine"):
            return acc.at[tok].add(y_tile)  # dead rows add zeros

    y = jax.lax.fori_loop(0, n_tiles, tile_body, jnp.zeros((T, E), jnp.float32))
    return y.astype(cfg.dtype).reshape(B, S, E), stats


@jax.named_scope("moe/shared")
def shared_experts_mlp(cfg: DecoderConfig, p, x: jnp.ndarray) -> jnp.ndarray:
    """The shared expert(s): a SwiGLU every token passes through, on every rank."""
    h = jax.nn.silu(jnp.einsum("bse,ef->bsf", x, p["ws_gate"].astype(cfg.dtype))) * jnp.einsum(
        "bse,ef->bsf", x, p["ws_up"].astype(cfg.dtype))
    return jnp.einsum("bsf,fe->bse", h, p["ws_down"].astype(cfg.dtype))
