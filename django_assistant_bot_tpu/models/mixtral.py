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

from ..ops import moe as moe_ops
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
# Dropless routed experts, a layer told which experts it holds: sigmoid scores
# picked by groups (DeepSeek-V3 family) or softmax scores with identity experts
# (LongCat-Flash); models/mla_moe.py
# ---------------------------------------------------------------------------

# rows of one grouped-matmul tile, and the largest token count the dense pass takes
GROUP_TILE = 128
DENSE_MAX_TOKENS = 64
# the held experts' three matrices: one layer's ``[held, ...]`` or the whole stack ``[layers, held, ...]``
HELD_KEYS = ("w_gate", "w_up", "w_down")
# counters a routed layer returns, per call: [picks, picks on held experts,
# layer-steps with a token, held experts hit] then tokens per held expert and,
# where the router has identity experts (:func:`zero_stat_width`), the picks
# that fell on them and the tokens by their number of REAL picks, 0..top_k
MOE_STAT_HEAD = 4


def zero_stat_width(cfg: DecoderConfig) -> int:
    """Trailing counters of a layer whose router has identity experts: 0 without."""
    return 2 + cfg.experts_per_token if cfg.latent_moe.zero_experts else 0


@jax.named_scope("moe/router")
def route_sigmoid_groups(lm, top_k: int, xt: jnp.ndarray, router: jnp.ndarray, bias=None):
    """-> (expert ids [T, K] over ALL ``router_experts``, weights [T, K] f32).

    float32 sigmoid scores; experts in ``n_group`` contiguous groups, a group
    scored by the sum of its two highest experts, the ``topk_group`` best
    groups kept, top-``K`` scores inside them; weights are the picked scores
    normalised over the picks (``norm_topk_prob``) times
    ``routed_scaling_factor``.  ``bias`` [router_experts] (``topk_method:
    noaux_tc``'s score-correction bias, float32) is added to the scores that
    PICK groups and experts; the weights stay the unbiased scores of the picks."""
    T = xt.shape[0]
    scores = jax.nn.sigmoid(
        jnp.einsum("te,ex->tx", xt.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    )
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if lm.n_group > 1:
        grouped = choice.reshape(T, lm.n_group, -1)
        group_score = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0].sum(-1)  # [T, G]
        kept = jax.lax.top_k(group_score, lm.topk_group)[1]  # [T, topk_group]
        keep = jnp.zeros((T, lm.n_group), bool).at[jnp.arange(T)[:, None], kept].set(True)
        # below every score a kept group can hold: sigmoid > 0, a bias may go under -1
        choice = jnp.where(keep[:, :, None], grouped, -1.0 if bias is None else -jnp.inf).reshape(T, -1)
    idx = jax.lax.top_k(choice, top_k)[1]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if lm.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
    return idx, w * lm.routed_scaling_factor


@jax.named_scope("moe/router")
def route_softmax(lm, top_k: int, xt: jnp.ndarray, router: jnp.ndarray, bias=None):
    """-> (ids [T, K] over the router's whole width, weights [T, K] f32).

    float32 softmax scores over ``router_width`` outputs (every rank's routed
    experts, then the identity experts); top-``K`` of the scores plus ``bias``
    (the correction bias: on the picks only); weights are the picked scores
    themselves times ``routed_scaling_factor``, normalised over the picks only
    under ``norm_topk_prob``."""
    scores = jax.nn.softmax(
        jnp.einsum("te,ex->tx", xt.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST), axis=-1)
    idx = jax.lax.top_k(scores if bias is None else scores + bias.astype(jnp.float32), top_k)[1]
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


def held_experts_mlp(cfg: DecoderConfig, p, x: jnp.ndarray, valid: jnp.ndarray, layer=None):
    """The routed part of an expert layer, as THIS rank computes it:
    ``sum over picked experts held here of g_e * expert_e(x)`` -> (y [B, S, E],
    stats int32 [MOE_STAT_HEAD + experts_held + zero_stat_width]).

    ``p["w_gate"]``, ``p["w_up"]``, ``p["w_down"]`` are one layer's held experts
    ``[held, ...]`` or, with ``layer`` (a traced index), the WHOLE stack
    ``[expert layers, held, ...]``: the layer scans close over the stack and
    pass the index, so that the experts can be read where they lie.

    Routes over all ``router_experts``; picks that land on another rank's
    experts add nothing here (their ranks add them; nothing stands in for the
    exchange).  **No capacity and no dropped token**, whatever the router does.
    A pick past the routed experts is an **identity expert** (``zero_experts``):
    it adds its weight times ``x`` itself, in full on the rank the token lives
    on, and costs nothing: such picks never enter the work lists, the tiles or
    the tokens per expert.  Two call shapes, by the static row count:

    - up to ``DENSE_MAX_TOKENS`` tokens (a decode step): an expert runs over
      every row with its column of a ``[T, held]`` combine matrix as row
      weights (zero for rows that did not pick it): no sort, no gather.
    - more (prefill): picks are sorted by expert and run as row tiles of
      ``GROUP_TILE``, ``sum ceil(count_e / tile)`` of them, so work follows the
      picks that landed here, and an expert that draws every token simply takes
      more tiles.

    Two implementations of both, by platform and shape
    (:func:`~..ops.moe.held_experts_path`).  ``kernel`` (a TPU, lane-wide
    widths): one Pallas call a layer (:func:`~..ops.moe.grouped_swiglu`) over
    a work list of the experts HIT (decode) or of the live tiles (prefill),
    each listed expert's matrices DMA'd from the stack by ``(layer, expert)``:
    a step reads only the experts a token landed on, and no layer's experts
    are copied out of the stack.  ``xla`` (the CPU, toy widths; the kernel's
    test oracle): the layer's experts sliced from the stack, then a dense
    masked pass that reads every held expert once a step (decode) or a loop
    over the live tiles (prefill).  Same mathematics on both: bfloat16
    operands, float32 accumulation, the row weight applied in float32 before
    the down-projection.

    ``valid`` [B, S] marks real tokens (pad positions and frozen slots route
    too, their rows are discarded by the caller; they are kept out of the
    counters and out of the work lists)."""
    lm = cfg.latent_moe
    B, S, E = x.shape
    T, K, Xh = B * S, cfg.experts_per_token, lm.experts_held
    xt = x.reshape(T, E)
    ok = valid.reshape(T)
    stack = tuple(p[k] for k in HELD_KEYS)
    kernel = moe_ops.held_experts_path(E, stack[0].shape[-1]) == "kernel"
    if kernel and layer is None:
        stack, layer = tuple(w[None] for w in stack), jnp.zeros((), jnp.int32)
    elif not kernel and layer is not None:
        stack = tuple(jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False) for w in stack)
    route = route_softmax if lm.scoring_func == "softmax" else route_sigmoid_groups
    idx, w = route(lm, K, xt, p["router"], p.get("router_bias"))
    zero = idx >= lm.router_experts  # [T, K]: picks on identity experts (none without them)

    def finish(y):
        """The held experts' sum ``y`` [T, E] plus the identity experts' part -> the layer's result."""
        if lm.zero_experts:
            with jax.named_scope("moe/zero"):
                y = y.astype(jnp.float32) + jnp.where(zero, w, 0.0).sum(-1)[:, None] * xt.astype(jnp.float32)
        return y.astype(cfg.dtype).reshape(B, S, E), stats

    with jax.named_scope("moe/dispatch"):
        local = idx - lm.first_expert  # [T, K]
        here = (local >= 0) & (local < Xh) & ok[:, None]
        local = jnp.where(here, local, Xh)  # Xh: not held here
        onehot = jax.nn.one_hot(local, Xh + 1, dtype=jnp.float32)[..., :Xh]  # [T, K, Xh]
        per_expert = onehot.sum((0, 1)).astype(jnp.int32)  # tokens per held expert
        hit = per_expert > 0
        stats = jnp.concatenate([
            jnp.stack([ok.sum() * K, here.sum(), ok.any().astype(jnp.int32), hit.sum()]).astype(jnp.int32),
            per_expert,
        ])
        if lm.zero_experts:
            by_real = jax.nn.one_hot(K - zero.sum(-1), K + 1, dtype=jnp.int32) * ok[:, None]
            stats = jnp.concatenate([stats, (zero & ok[:, None]).sum()[None].astype(jnp.int32), by_real.sum(0)])
    if T <= DENSE_MAX_TOKENS:
        with jax.named_scope("moe/dispatch"):
            combine = jnp.einsum("tkx,tk->tx", onehot, w)  # [T, Xh] f32
            if kernel:
                # the work list: the experts hit, in order, each over all T rows (padded to whole sublane
                # tiles).  Compacted by comparison, not by nonzero + gather: a few small fused operations
                # in place of a dozen, six times a step
                place = (jnp.cumsum(hit) - 1 == jnp.arange(Xh)[:, None]) & hit  # [item, expert]
                experts = (place * jnp.arange(Xh)).sum(1)
                pad = -T % 16
                rows = jnp.pad(xt, ((0, pad), (0, 0)))
                w_rows = jnp.pad(jnp.where(place[:, :, None], combine.T[None], 0.0).sum(1), ((0, 0), (0, pad)))
        with jax.named_scope("moe/experts"):
            if kernel:
                y = moe_ops.grouped_swiglu(rows, w_rows, *stack, layer, experts, hit.sum()[None], shared_rows=True)[:T]
            else:
                wg, wu, wd = (m.astype(cfg.dtype) for m in stack)
                h = jax.nn.silu(jnp.einsum("te,xef->txf", xt, wg)) * jnp.einsum("te,xef->txf", xt, wu)
                h = (h.astype(jnp.float32) * combine[:, :, None]).astype(cfg.dtype)
                y = jnp.einsum("txf,xfe->te", h, wd, preferred_element_type=jnp.float32)
        return finish(y)

    tm = GROUP_TILE
    with jax.named_scope("moe/dispatch"):
        N = T * K
        order = jnp.argsort(local.reshape(N), stable=True)  # held experts first, by expert; the rest last
        sorted_tok = (order // K).astype(jnp.int32)
        sorted_w = w.reshape(N)[order]
        offs = jnp.cumsum(per_expert) - per_expert  # first sorted position of each expert
        tiles = -(-per_expert // tm)
        tile_end = jnp.cumsum(tiles)
        n_tiles = tile_end[-1]
        # the work list, one item a tile, at its static maximum: a token picks an expert
        # once, so an expert has at most T rows and the held experts T * min(K, Xh)
        max_tiles = min(T * min(K, Xh) // tm + Xh, Xh * -(-T // tm))
        t = jnp.arange(max_tiles, dtype=jnp.int32)
        e = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"), Xh - 1).astype(jnp.int32)
        pos = (offs[e] + (t - (tile_end[e] - tiles[e])) * tm)[:, None] + jnp.arange(tm, dtype=jnp.int32)[None, :]
        live = (pos < (offs[e] + per_expert[e])[:, None]) & (t < n_tiles)[:, None]
        pos = jnp.minimum(pos, N - 1)
        tok = sorted_tok[pos]  # [max_tiles, tm]: a real token in every row, dead rows weigh nothing
        w_rows = jnp.where(live, sorted_w[pos], 0.0)

    if kernel:
        with jax.named_scope("moe/dispatch"):  # only the live tiles' rows are gathered; the rest is never read
            rows = jax.lax.fori_loop(
                0, n_tiles, lambda i, buf: jax.lax.dynamic_update_slice_in_dim(buf, xt[tok[i]], i * tm, 0),
                jax.lax.empty((max_tiles * tm, E), xt.dtype))
        with jax.named_scope("moe/experts"):
            tiles_y = moe_ops.grouped_swiglu(rows, w_rows, *stack, layer, e, n_tiles[None], shared_rows=False)

        def tile_body(i, acc):
            with jax.named_scope("moe/combine"):  # dead rows add zeros
                return acc.at[tok[i]].add(jax.lax.dynamic_slice_in_dim(tiles_y, i * tm, tm, 0))
    else:
        def tile_body(i, acc):
            with jax.named_scope("moe/dispatch"):
                x_tile = xt[tok[i]]
            with jax.named_scope("moe/experts"):
                y_tile = _swiglu_tile(
                    x_tile, *(jax.lax.dynamic_index_in_dim(m, e[i], 0, keepdims=False) for m in stack),
                    w_rows[i], cfg.dtype)
            with jax.named_scope("moe/combine"):
                return acc.at[tok[i]].add(y_tile)  # dead rows add zeros

    return finish(jax.lax.fori_loop(0, n_tiles, tile_body, jnp.zeros((T, E), jnp.float32)))


@jax.named_scope("moe/shared")
def shared_experts_mlp(cfg: DecoderConfig, p, x: jnp.ndarray) -> jnp.ndarray:
    """The shared expert(s): a SwiGLU every token passes through, on every rank."""
    h = jax.nn.silu(jnp.einsum("bse,ef->bsf", x, p["ws_gate"].astype(cfg.dtype))) * jnp.einsum(
        "bse,ef->bsf", x, p["ws_up"].astype(cfg.dtype))
    return jnp.einsum("bsf,fe->bse", h, p["ws_down"].astype(cfg.dtype))
