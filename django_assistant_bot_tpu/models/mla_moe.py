"""Latent-attention + routed-expert decoder (DeepSeek-V3 family; LongCat-Flash's
shortcut-connected double layer), served as one rank of an expert-parallel
deployment.

The block, per layer (``n`` RMSNorm; ``cfg.latent_moe`` has the sizes):

- attention: ``c_q = n(h W_DQ)``, ``[q_nope | q_rope] = c_q W_UQ`` per head;
  ``[c | k_r] = h W_DKV``, ``c_kv = n(c)``, ``k_rope = rope(k_r)`` (one per
  token, shared by every head), ``q_rope = rope(q_rope)``; keys are
  ``[c_kv W_UK | k_rope]``, values ``c_kv W_UV``; softmax scale
  ``qk_head_dim^-0.5 * softmax_scale_mult``; out ``= concat(o) W_O``.
- the cache holds ``[c_kv | k_rope]`` (after the norm, after the rotation),
  padded to whole 128-lane tiles: ONE row per token per layer, the same row as
  key and as value (:class:`LatentKVCache`).  Prefill expands keys and values
  from the rows (flash kernel at the published widths); a decode step attends
  over the latent itself with the up-projections absorbed (``q_abs = q_nope
  W_UK^T``, ``o = (softmax(...) c_kv) W_UV``) and never expands the cache.
- feed-forward: the first ``first_dense_layers`` layers a dense SwiGLU; the
  rest ``shared(h) + sum over picked experts HELD HERE of g_e expert_e(h)``
  (:func:`.mixtral.held_experts_mlp`: sigmoid scores, group-limited top-k, no
  capacity, no dropped token).  What the absent ranks' experts would add is
  left out, and that partial result goes on to the next layer.

- learned sparse attention (``cfg.latent_moe.index_topk`` > 0; DeepSeek-V3.2's
  lightning indexer): index queries ``c_q W_IQ`` per index head, ONE index key
  ``LayerNorm(h W_IK)`` per token (rotary over the first ``qk_rope_head_dim``
  lanes of both, the same tables), head weights ``h W_Iw * Hi^-0.5 * Di^-0.5``
  in float32; ``I(t, s) = sum_j w[t,j] relu(q[t,j] . k[s])``; a query attends
  the ``index_topk`` positions ``s <= t`` of largest ``I`` (all of them below
  that many), exactly.  The cache holds the index key beside the latent row
  (``LatentKVCache.idx``, the same block tables).  Prefill scores and selects a
  tile at a time, over the part of the view its live keys reach (and not at
  all while every key is kept), and attends under the selection as a mask
  (``ops.attention.sparse_select`` / ``sparse_attention``); a decode step
  scores the slot's index keys, takes the top-k and gathers ONLY the selected
  latent rows.  With ``index_topk`` 0 none of this exists: the same weights,
  cache and programs as before it was written.

- the shortcut-connected double layer (``cfg.latent_moe.double_layer``;
  LongCat-Flash): ``x1 = x + A_0(n(x))``, ``h = n(x1)``, ``m = MoE(h)``,
  ``x2 = x1 + D_0(h)``, ``x3 = x2 + A_1(n(x2))``, ``out = x3 + D_1(n(x3)) + m``:
  two attention sublayers and two dense SwiGLUs, each with its own norms and
  weights, and ONE expert layer that reads the first sublayer's normed hidden
  state and joins the residual at the layer's end (in a deployment its
  exchange hides behind the second sublayer; on one chip there is none and
  the order is XLA's).  No leading dense layer, no shared expert; softmax
  scores over the routed experts and ``zero_experts`` identity experts
  (:func:`.mixtral.held_experts_mlp`); queries and the normed latent scaled
  (``q_scale``, ``kv_scale``: the cached row holds the SCALED latent).
  ``dense_layers`` then holds the ``2 * num_layers`` sublayers in order, the
  weights a dense layer has, and ``moe_layers`` the routers and held experts
  of the ``num_layers`` expert layers; the cache's layer axis counts
  attention sublayers (:func:`cache_layers`), rows ``2l`` and ``2l + 1``.

Two stacks of weights, ``dense_layers`` and ``moe_layers``, each scanned over
its own leading axis, but for the held experts' three matrices: the scan
bodies close over those stacks whole and index them by ``(layer, expert)``
(:func:`_scan_layers`), so that a step reads only the experts a token landed
on and no program copies a layer's experts.  The same entry points as
:mod:`.llama`'s paged path (``serving/engine.py`` picks the module once, by
``cfg.arch``):
``prefill``, ``insert_sequences_paged``, ``copy_pages``,
``prefill_chunk_paged``, ``prefill_suffix_paged``, ``decode_step_paged``,
``init_paged_cache``, ``paged_cache_shardings``.  Each of the three programs
(one-shot prefill, prefill against the cache, a decode step) defines only its
attention sublayer; :func:`_scan_layers` runs the layers around it, whatever
the block form.  The contiguous cache,
speculation and quantised weights are not implemented for this block; the
registry refuses them.

Rotary pairs are half-split (``ops/rope.py``), as everywhere in this repo; a
published checkpoint (interleaved pairs, ``kv_b_proj`` with keys and values
interleaved per head) is permuted at load time into ``w_uk`` / ``w_uv``.

**Two forms of the tree.**  A checkpoint (``save_model``, :func:`init`, the
benchmark's families) holds every projection as ``[layers, in, out]``.  The
device holds six leaves otherwise (:func:`held_params`, made once at load; the
only function that knows both forms; :func:`logical_axes` describes this one),
each in the form the decode step's dot contracts, so that the layer scan's
slice is the dot's operand and the weight is read once a layer a step:

- ``w_uq``  ``[L, R, H*(dn+dr)]`` -> ``w_uq_nope`` ``[L, H, dn, R]`` and
  ``w_uq_rope`` ``[L, dr, H, R]`` (the rotation splits ``dr`` in halves, so
  its lanes lie major; a head's ``dn+dr`` = 192 columns are no whole lane
  tiles, so the two parts are two leaves)
- ``w_uk``  ``[L, C, H*dn]``      -> ``[L, H, dn, C]``
- ``w_uv``  ``[L, C, H*dv]``      -> ``[L, H, dv, C]``
- ``w_iq``  ``[L, R, Hi*Di]``     -> ``[L, Hi, Di, R]``
- ``w_dkv`` ``[L, E, C+dr]``      -> ``[L, E, latent_width]`` (zero columns)
- ``lm_head`` ``[E, V]``          -> ``[E, V up to whole lane tiles]`` (zero
  columns; the logits are cut back to ``V``)

Held as in the checkpoint, the compiled tick re-laid out every one of them
once a tick at its entry (0.9 GB written at DeepSeek-V3.2's widths: the dots
of a head-shaped result take the contracted axis last, and the device's own
default layout of a width that is not whole lane tiles is the transposed one)
and inside the scan copied a layer of the first four into fast memory before
the dot ran from there, the read and the dot one after the other (PERF.md
section 5, PR 43).  Prefill contracts the same leaves.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (
    attention,
    index_scores,
    sparse_kernel_shaped,
    latent_decode_attention,
    latent_decode_kv_path,
    latent_decode_update_attend,
    paged_decode_plan,
    paged_index_scores,
    sparse_attention,
    sparse_decode_select,
    sparse_latent_decode_attention,
    sparse_select,
    topk_select_paged,
)
from ..ops.moe import held_experts_path
from ..ops.norms import layer_norm, rms_norm
from ..ops.rope import apply_rope, rope_frequencies
from ..parallel.sharding import with_constraint
from .config import DecoderConfig
from .llama import _embed, _head_logits
from .mixtral import HELD_KEYS, MOE_STAT_HEAD, held_experts_mlp, shared_experts_mlp, zero_stat_width

Params = Dict[str, Any]

KV_KIND = "latent"
# counters of the sparse attention, the trailing columns of ``LatentKVCache.stats``
# where the block selects: [programs, queries, causal pairs, selected pairs,
# pairs the selection's counting ran over] of decode steps (row 0), and of chunk
# programs then every other prefill program (row 1), each a layer's worth
# (every layer selects as many)
DSA_STAT = 10


def kv_kind(cfg: DecoderConfig) -> str:
    """What a cached token is: one latent row, or a latent row and an index key."""
    return "latent+index" if cfg.latent_moe.index_topk else KV_KIND


class LatentKVCache(NamedTuple):
    """Page pool of latent rows.  kv: [L, P, page, W], one row per token per
    layer (``c_kv | k_rope | zero pad``); lengths: [B] tokens present per slot;
    stats: int32 [2, MOE_STAT_HEAD + experts_held (+ zero_stat_width) (+ DSA_STAT)], the routed
    layers' counters since the last tick read them (row 0: decode steps, row 1:
    prefill) and, where the block selects, the sparse attention's, summed
    on the device and handed out with a tick's tokens; idx: [L, P, page, Di]
    the indexer's key of the same token on the same page, or None (a block
    without an indexer).  Block tables are the host's, as for
    :class:`.llama.PagedKVCache`."""

    kv: jnp.ndarray
    lengths: jnp.ndarray
    stats: jnp.ndarray
    idx: Optional[jnp.ndarray] = None

    @property
    def n_pages(self) -> int:
        return self.kv.shape[1]

    @property
    def page_size(self) -> int:
        return self.kv.shape[2]


_NOT_HERE = "is not implemented for the latent-attention MoE block (models/mla_moe.py)"


def check_serving(*, speculative=0, prefix_cache=0, kv_cache_dtype=None,
                  attn_fp8=False, kv_host_tier=False, quantize=None) -> None:
    """Refuse, with the reason, every serving option this block does not
    implement, before any weight is loaded or program built (the registry and
    the engine both ask)."""
    if speculative:
        raise ValueError(f"speculative={speculative}: tree verification (verify_tree_step_paged) {_NOT_HERE}")
    if prefix_cache:
        raise ValueError(f"prefix_cache={prefix_cache}: the prefix cache's page gather/restore names a K and a V pool "
                         f"(this block has a latent row and, with an indexer, a second kind of row) and {_NOT_HERE}; "
                         "set prefix_cache=0")
    if kv_host_tier:
        raise ValueError(f"kv_host_bytes / kv_spill_dir: the host KV tier {_NOT_HERE}")
    if kv_cache_dtype or attn_fp8:
        raise ValueError(f"kv_cache_dtype={kv_cache_dtype!r} / attn_fp8: a reduced-precision latent cache {_NOT_HERE}")
    if quantize:
        raise ValueError(f"quantize={quantize!r}: int8/int4 of the latent projections and the experts {_NOT_HERE}; "
                         "serve the weights in the checkpoint's dtype")


def cache_layers(cfg: DecoderConfig) -> int:
    """Rows of the cache's layer axis: one per attention sublayer (two a double layer)."""
    return cfg.num_layers * (2 if cfg.latent_moe.double_layer else 1)


def kv_bytes_per_token(cfg: DecoderConfig, kv_dtype=None) -> int:
    """Bytes one cached token takes over all layers (pad lanes included, and
    the index key where the block has an indexer)."""
    lm = cfg.latent_moe
    width = lm.latent_width + (lm.index_head_dim if lm.index_topk else 0)
    return cache_layers(cfg) * width * jnp.dtype(kv_dtype or cfg.dtype).itemsize


def decode_kv_path(cfg: DecoderConfig, kv_dtype, page: int, *, fp8_dot: bool = False) -> str:
    """``"kernel"`` or ``"xla"``: what :func:`decode_step_paged` does with the
    pools, asked by the step itself and by the engine's label; with an indexer
    ``"kernel"`` means its three sparse stages are Pallas calls too."""
    lm = cfg.latent_moe
    return latent_decode_kv_path(kv_dtype or cfg.dtype, page, lm.latent_width,
                                 index_width=lm.index_head_dim if lm.index_topk else 0)


def moe_experts_path(cfg: DecoderConfig) -> str:
    """``"kernel"`` or ``"xla"``: how the held experts run (:func:`~..ops.moe.held_experts_path`)."""
    return held_experts_path(cfg.hidden_size, cfg.latent_moe.moe_intermediate_size)


def init_paged_cache(cfg: DecoderConfig, batch: int, n_pages: int, page_size: int, dtype=None) -> LatentKVCache:
    lm = cfg.latent_moe
    dsa = bool(lm.index_topk)
    return LatentKVCache(
        kv=jnp.zeros((cache_layers(cfg), n_pages, page_size, lm.latent_width), dtype or cfg.dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
        stats=jnp.zeros((2, MOE_STAT_HEAD + lm.experts_held + zero_stat_width(cfg) + (DSA_STAT if dsa else 0)), jnp.int32),
        idx=jnp.zeros((cfg.num_layers, n_pages, page_size, lm.index_head_dim), dtype or cfg.dtype) if dsa else None,
    )


def paged_cache_shardings(cfg: DecoderConfig, mesh, batch: int) -> LatentKVCache:
    """Replicated: a latent row belongs to every head, so there is no head axis
    to shard the pool over (data-parallel attention is one pool per replica)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    return LatentKVCache(kv=rep, lengths=rep, stats=rep, idx=rep if cfg.latent_moe.index_topk else None)


def _stack_sizes(cfg: DecoderConfig) -> tuple[int, int]:
    """Leading axes of ``dense_layers`` and ``moe_layers``."""
    if cfg.latent_moe.double_layer:
        return 2 * cfg.num_layers, cfg.num_layers
    nd = cfg.latent_moe.first_dense_layers
    return nd, cfg.num_layers - nd


def logical_axes(cfg: DecoderConfig) -> Params:
    """Axes of the HELD tree (:func:`held_params`; the module docstring has the
    forms): the up-projections carry their heads on an axis of their own
    (``w_uq_nope`` ``[L, H, dn, R]``, ``w_uq_rope`` ``[L, dr, H, R]``, ``w_uk`` /
    ``w_uv`` ``[L, H, d, C]``), which is the axis a mesh shards."""
    E, F = "embed", "mlp"
    attn = {
        "attn_norm": (None, E), "w_dq": (None, E, None), "q_norm": (None, None),
        "w_uq_nope": (None, "heads", None, None), "w_uq_rope": (None, None, "heads", None), "w_dkv": (None, E, None),
        "kv_norm": (None, None), "w_uk": (None, "heads", None, None), "w_uv": (None, "heads", None, None),
        "wo": (None, "heads", E), "mlp_norm": (None, E),
    }
    if cfg.latent_moe.index_topk:  # the indexer is small and whole on every device
        attn.update(w_iq=(None, None, None, None), w_ik=(None, E, None), ik_norm=(None, None), ik_bias=(None, None),
                    w_iw=(None, E, None))
    dense = dict(attn, w_gate=(None, E, F), w_up=(None, E, F), w_down=(None, F, E))
    # the held experts stay whole on every device of this process: "expert" is the
    # axis ACROSS ranks (parallel/sharding.py), and a rank is one process here
    moe = dict(
        {} if cfg.latent_moe.double_layer else attn, router=(None, E, None),
        w_gate=(None, None, E, F), w_up=(None, None, E, F), w_down=(None, None, F, E),
    )
    if cfg.latent_moe.n_shared_experts:
        moe.update(ws_gate=(None, E, F), ws_up=(None, E, F), ws_down=(None, F, E))
    if cfg.latent_moe.router_bias:
        moe["router_bias"] = (None, None)
    axes = {"tok_embed": ("vocab_in", E), "final_norm": (E,), "dense_layers": dense, "moe_layers": moe}
    if not cfg.tie_embeddings:
        axes["lm_head"] = (E, "vocab_out")
    return axes


def init(cfg: DecoderConfig, rng: jax.Array) -> Params:
    """Random parameters as a checkpoint holds them (:func:`held_params` makes
    the tree the entry points take): ``dense_layers`` and ``moe_layers``
    stacked on a leading axis each, only the held experts drawn."""
    lm = cfg.latent_moe
    E, F, H = cfg.hidden_size, cfg.intermediate_size, cfg.num_heads
    R, C, dn, dr, dv = lm.q_lora_rank, lm.kv_lora_rank, lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim
    Fm, Fs, Xh = lm.moe_intermediate_size, lm.moe_intermediate_size * lm.n_shared_experts, lm.experts_held
    nd, nm = _stack_sizes(cfg)
    Hi, Di = lm.index_n_heads, lm.index_head_dim
    keys = iter(jax.random.split(rng, 60))

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape) * fan_in ** -0.5).astype(cfg.dtype)

    def attn(L):
        out = {
            "attn_norm": jnp.ones((L, E), cfg.dtype), "w_dq": dense((L, E, R), E),
            "q_norm": jnp.ones((L, R), cfg.dtype), "w_uq": dense((L, R, H * (dn + dr)), R),
            "w_dkv": dense((L, E, C + dr), E), "kv_norm": jnp.ones((L, C), cfg.dtype),
            "w_uk": dense((L, C, H * dn), C), "w_uv": dense((L, C, H * dv), C),
            "wo": dense((L, H * dv, E), H * dv), "mlp_norm": jnp.ones((L, E), cfg.dtype),
        }
        if lm.index_topk:
            out.update(w_iq=dense((L, R, Hi * Di), R), w_ik=dense((L, E, Di), E), ik_norm=jnp.ones((L, Di), cfg.dtype),
                       ik_bias=jnp.zeros((L, Di), cfg.dtype), w_iw=dense((L, E, Hi), E))
        return out

    params = {
        "tok_embed": dense((cfg.vocab_size, E), 1.0),
        "final_norm": jnp.ones((E,), cfg.dtype),
        "dense_layers": dict(attn(nd), w_gate=dense((nd, E, F), E), w_up=dense((nd, E, F), E),
                             w_down=dense((nd, F, E), F)),
        "moe_layers": dict(
            {} if lm.double_layer else attn(nm), router=dense((nm, E, lm.router_width), E),
            w_gate=dense((nm, Xh, E, Fm), E), w_up=dense((nm, Xh, E, Fm), E), w_down=dense((nm, Xh, Fm, E), Fm),
        ),
    }
    if Fs:
        params["moe_layers"].update(ws_gate=dense((nm, E, Fs), E), ws_up=dense((nm, E, Fs), E), ws_down=dense((nm, Fs, E), Fs))
    if lm.router_bias:
        params["moe_layers"]["router_bias"] = jnp.zeros((nm, lm.router_width), cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense((E, cfg.vocab_size), E)
    return params


def _zero_columns(x, n: int):
    """``x`` with ``n`` zero columns after its last axis (``x`` itself for 0)."""
    if not n:
        return x
    xp = np if isinstance(x, np.ndarray) else jnp
    return xp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n)])


def held_params(cfg: DecoderConfig, params: Params) -> Params:
    """A checkpoint's tree -> the tree the device holds and every entry point
    takes (the module docstring has the six leaves that differ and why).  Called
    once where weights are loaded, before they are placed (``models.held_params``:
    the registry, ``tools/``), and nothing of the checkpoint's form is kept.
    The up-projections are transposed ON THE DEVICE, one program a stack, and
    come back as device arrays whatever they came as: the same 0.5 GB moved by
    NumPy on the host was 8 s of a 100 s boot (PERF.md section 6, PR 43; a
    registry that keeps a host copy for replicas of its own keeps these leaves
    on the default device).  What is only padded stays where it was.  The
    widths are ``cfg``'s; a tree without indexer leaves (``index_topk`` 0) or
    ``lm_head`` (tied) stays without."""
    lm = cfg.latent_moe
    H, dn, dr, dv, C, R = (cfg.num_heads, lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim, lm.kv_lora_rank,
                           lm.q_lora_rank)

    @jax.jit
    def contracted_last(p: Params) -> Params:
        L = p["w_uq"].shape[0]
        w_uq = p["w_uq"].reshape(L, R, H, dn + dr)
        out = dict(
            w_uq_nope=w_uq[..., :dn].transpose(0, 2, 3, 1), w_uq_rope=w_uq[..., dn:].transpose(0, 3, 2, 1),
            w_uk=p["w_uk"].reshape(L, C, H, dn).transpose(0, 2, 3, 1),
            w_uv=p["w_uv"].reshape(L, C, H, dv).transpose(0, 2, 3, 1),
        )
        if "w_iq" in p:
            out["w_iq"] = p["w_iq"].reshape(L, R, lm.index_n_heads, lm.index_head_dim).transpose(0, 2, 3, 1)
        return out

    def stack(p: Params) -> Params:
        if "w_uq" not in p:  # the double layer's expert stack: no attention of its own
            return p
        moved = {k: p[k] for k in ("w_uq", "w_uk", "w_uv", "w_iq") if k in p}
        kept = {k: v for k, v in p.items() if k not in moved}
        return dict(kept, w_dkv=_zero_columns(p["w_dkv"], lm.latent_width - C - dr), **contracted_last(moved))

    held = dict(params, dense_layers=stack(params["dense_layers"]), moe_layers=stack(params["moe_layers"]))
    if "lm_head" in held:
        held["lm_head"] = _zero_columns(held["lm_head"], (-cfg.vocab_size) % 128)
    return held


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def softmax_scale(cfg: DecoderConfig) -> float:
    lm = cfg.latent_moe
    return float(lm.qk_head_dim ** -0.5 * lm.softmax_scale_mult)


def _rope_tables(cfg: DecoderConfig, max_len: int):
    cos, sin = rope_frequencies(
        cfg.latent_moe.qk_rope_head_dim, max_len, cfg.rope_theta,
        scaling=cfg.rope_scaling, deployed_len=cfg.max_seq_len,
    )
    return jnp.asarray(cos), jnp.asarray(sin)


def _mm(pattern: str, x, w, dtype):
    return jnp.einsum(pattern, x, w.astype(dtype))


def _scaled(x: jnp.ndarray, s: float) -> jnp.ndarray:
    """``x * s`` from float32, rounded once (a bfloat16 ``sqrt(12)`` would itself be 0.13% off); ``x`` for 1.0."""
    return x if s == 1.0 else (x.astype(jnp.float32) * s).astype(x.dtype)


def _queries_and_row(cfg: DecoderConfig, p: Params, h: jnp.ndarray, cos, sin):
    """-> (q_nope [B,S,H,dn], q_rope [B,S,H,dr] rotated, row [B,S,W], c_q
    [B,S,R]): the queries of ``h`` (times ``q_scale``), the row the cache keeps
    for it (the normed latent times ``kv_scale``, the rotary key as it is), and
    the query latent (the indexer's queries come from it too)."""
    lm = cfg.latent_moe
    B, S, _ = h.shape
    dr, C = lm.qk_rope_head_dim, lm.kv_lora_rank
    with jax.named_scope("attn/q_down"):
        c_q = rms_norm(_mm("bse,er->bsr", h, p["w_dq"], cfg.dtype), p["q_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn/q_up"):
        q_nope = _scaled(_mm("bsr,hdr->bshd", c_q, p["w_uq_nope"], cfg.dtype), lm.q_scale)
        q_rope = apply_rope(_scaled(_mm("bsr,dhr->bshd", c_q, p["w_uq_rope"], cfg.dtype), lm.q_scale), cos, sin)
    with jax.named_scope("attn/kv_down"):
        ckv = _mm("bse,ec->bsc", h, p["w_dkv"], cfg.dtype)
        c_kv = _scaled(rms_norm(ckv[..., :C], p["kv_norm"], cfg.rms_norm_eps), lm.kv_scale)
        k_rope = apply_rope(ckv[..., None, C:C + dr], cos, sin)[..., 0, :]
        pad = lm.latent_width - C - dr
        row = jnp.concatenate([c_kv, k_rope] + ([jnp.zeros((B, S, pad), c_kv.dtype)] if pad else []), axis=-1)
    return q_nope, q_rope, row, c_q


def _index_parts(cfg: DecoderConfig, p: Params, h: jnp.ndarray, c_q: jnp.ndarray, cos, sin):
    """The indexer's share of a token -> (q_idx [B,S,Hi,Di] rotated, w_idx
    [B,S,Hi] float32, k_idx [B,S,Di] rotated: what the second cache keeps).
    Rotary over the FIRST ``qk_rope_head_dim`` lanes of a head, MLA's tables."""
    lm = cfg.latent_moe
    Hi, Di, dr = lm.index_n_heads, lm.index_head_dim, lm.qk_rope_head_dim

    def rotate(x):  # [B, S, heads, Di]
        return jnp.concatenate([apply_rope(x[..., :dr], cos, sin), x[..., dr:]], axis=-1)

    with jax.named_scope("attn/index_q"):
        q_idx = rotate(_mm("bsr,hdr->bshd", c_q, p["w_iq"], cfg.dtype))
        w_idx = jnp.einsum("bse,eh->bsh", h.astype(jnp.float32), p["w_iw"].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST) * float(Hi ** -0.5 * Di ** -0.5)
    with jax.named_scope("attn/index_k"):
        k = layer_norm(_mm("bse,ed->bsd", h, p["w_ik"], cfg.dtype), p["ik_norm"], p["ik_bias"], cfg.rms_norm_eps)
        k_idx = rotate(k[:, :, None, :])[:, :, 0, :]
    return q_idx, w_idx, k_idx


def _dsa_counts(real: jnp.ndarray, qpos: jnp.ndarray, keep_sum, scanned) -> jnp.ndarray:
    """One layer's [queries, causal pairs, selected pairs] over the real queries,
    and the (query, position) pairs its selection counted over (0: all kept)."""
    return jnp.stack([real.sum(), jnp.where(real, qpos + 1, 0).sum(), keep_sum, scanned]).astype(jnp.int32)


def _stats_row(cfg: DecoderConfig, moe_layers: jnp.ndarray, dsa_layers, kind: int) -> jnp.ndarray:
    """A program's counters as one row of ``LatentKVCache.stats``: the routed
    layers' summed over layers and, where the block selects, the sparse
    attention's of ONE layer (every layer counts the same) in the columns of
    ``kind`` (0: a decode step or a chunk program, 1: any other prefill)."""
    row = moe_layers.sum(0)
    if dsa_layers is None:
        return row
    per = dsa_layers.sum(0) // cfg.num_layers
    mine = jnp.concatenate([(per[:1] > 0).astype(jnp.int32), per])
    zero = jnp.zeros((DSA_STAT // 2,), jnp.int32)
    return jnp.concatenate([row, mine, zero] if kind == 0 else [row, zero, mine])


def _lane_pad(cfg: DecoderConfig) -> int:
    """Zero lanes that bring the query/key width to whole lane tiles (192 -> 256)."""
    D = cfg.latent_moe.qk_head_dim
    return (-D) % 128 if D > 64 else 0


@jax.named_scope("attn/kv_up")
def _expanded_queries(cfg: DecoderConfig, q_nope, q_rope):
    """-> q [B,H,Sq,D] in the expanded form's layout, padded as the keys are."""
    pad = _lane_pad(cfg)
    zq = [jnp.zeros(q_nope.shape[:-1] + (pad,), q_nope.dtype)] if pad else []
    q = jnp.concatenate([q_nope, q_rope] + zq, axis=-1).transpose(0, 2, 1, 3)
    return with_constraint(q, ("batch", "heads", "length", "head_dim"))


@jax.named_scope("attn/kv_up")
def _expanded_keys_values(cfg: DecoderConfig, p: Params, rows):
    """Latent ``rows`` [B,Sk,W] -> (k [B,H,Sk,D], v [B,H,Sk,dv]): keys ``[rows
    W_UK | k_rope | zero pad]``, values ``rows W_UV``.  Each over its held
    leaf as ONE ``[H*d, C]`` matrix (a view: heads are the leaf's major axis),
    positions major: a head-batched product leaves its result positions-minor,
    and the chunk's values of a whole context (537 MB at 16,384) or the plain
    path's scores would then be copied once a layer."""
    lm = cfg.latent_moe
    B, Sk, _ = rows.shape
    H, dn, dr, dv, C = cfg.num_heads, lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim, lm.kv_lora_rank
    c_kv = rows[..., :C].astype(cfg.dtype)
    k_rope = rows[..., C:C + dr].astype(cfg.dtype)
    k_nope = _mm("bsc,oc->bso", c_kv, p["w_uk"].reshape(H * dn, C), cfg.dtype).reshape(B, Sk, H, dn)
    v = _mm("bsc,oc->bso", c_kv, p["w_uv"].reshape(H * dv, C), cfg.dtype).reshape(B, Sk, H, dv)
    pad = _lane_pad(cfg)
    zk = [jnp.zeros((B, Sk, H, pad), k_nope.dtype)] if pad else []
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, Sk, H, dr))] + zk, axis=-1)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def _expanded_attention(cfg: DecoderConfig, p: Params, q_nope, q_rope, rows, *, causal=False, mask=None, keep=None,
                        live=None):
    """Prefill's form: keys and values expanded from latent ``rows`` [B,Sk,W]
    -> o [B,Sq,H*dv].  The flash kernel takes it where it is kernel-shaped
    (``ops.attention.attention``): query/key width padded with zeros to whole
    lane tiles, value width as it is.  ``keep`` [B,Sq,Sk] (with ``live`` [B], the
    keys any query of a row can keep) is the sparse attention's selection,
    causality included: the pairs attended, under the masked flash kernel
    (``ops.attention.sparse_attention``)."""
    B, H, dv = rows.shape[0], cfg.num_heads, cfg.latent_moe.v_head_dim
    q = _expanded_queries(cfg, q_nope, q_rope)
    k, v = _expanded_keys_values(cfg, p, rows)
    if keep is not None:
        o = sparse_attention(q, k.swapaxes(2, 3), v, keep, live, scale=softmax_scale(cfg))  # attn/sparse_core
    else:
        o = attention(q, k, v, causal=causal, mask=mask, scale=softmax_scale(cfg))  # attn/core
    return o.transpose(0, 2, 1, 3).reshape(B, q_nope.shape[1], H * dv)


def _sparse_attention_over_pages(cfg: DecoderConfig, p: Params, q_nope, q_rope, q_idx, w_idx, pool, ipool, layer,
                                 block_tables, pos, ok, live):
    """A chunk's sparse attention over its rows' pages -> (o [B,C,H*dv], pairs
    kept, pairs the selection counted over).  The view is built a PAGE at a time, and only the pages that hold a
    live key (``live`` [B]: keys below it can be kept): the page's latent rows
    and index keys are read where they lie, the rows expanded into keys and
    values, and all three written into the view's buffers in place.  What a
    chunk costs then follows the context held, not ``max_seq_len``: dead pages
    are never gathered or expanded, on a TPU their part of the buffers is
    never written or read (the two kernels skip tiles past the live keys), and
    the selection scores and counts the live keys in steps, or nothing where
    they are within ``index_topk`` (``ops.attention.sparse_select``)."""
    lm = cfg.latent_moe
    B, C = q_nope.shape[:2]
    L, P, page, W = pool.shape
    S = block_tables.shape[1] * page
    H, dv, Di = cfg.num_heads, lm.v_head_dim, lm.index_head_dim
    D = lm.qk_head_dim + _lane_pad(cfg)
    # the plain path reads the whole view under a mask: there the dead part has to be finite
    make = jax.lax.empty if sparse_kernel_shaped(C, S, D, dv) else jnp.zeros
    # the keys transposed, positions on the last axis: how a page's expansion comes out of its matmul and how
    # the kernel contracts them; any other layout costs a copy of the whole view a layer
    view = (make((B, H, D, S), cfg.dtype), make((B, H, S, dv), cfg.dtype), make((B, S, Di), ipool.dtype))

    def add_page(j, view):
        kb, vb, ib = view
        pages = jnp.clip(jax.lax.dynamic_index_in_dim(block_tables, j, 1, keepdims=False), 0, P - 1)  # [B]
        with jax.named_scope("attn/kv_read"):
            rows, keys = pool[layer, pages], ipool[layer, pages]
        k, v = _expanded_keys_values(cfg, p, rows)
        with jax.named_scope("attn/kv_up"):
            at = j * page
            return (jax.lax.dynamic_update_slice_in_dim(kb, k.swapaxes(2, 3), at, 3),
                    jax.lax.dynamic_update_slice_in_dim(vb, v, at, 2), jax.lax.dynamic_update_slice_in_dim(ib, keys, at, 1))

    kb, vb, ib = jax.lax.fori_loop(0, jnp.max(-(-live // page)), add_page, view)
    keep, scanned = sparse_select(q_idx, w_idx, ib, pos, ok, lm.index_topk, live)
    o = sparse_attention(_expanded_queries(cfg, q_nope, q_rope), kb, vb, keep, live, scale=softmax_scale(cfg))  # attn/sparse_core
    return o.transpose(0, 2, 1, 3).reshape(B, C, H * dv), keep.sum(), scanned


@jax.named_scope("attn/out")
def _attn_out(cfg: DecoderConfig, p: Params, o: jnp.ndarray) -> jnp.ndarray:
    return _mm("bso,oe->bse", o, p["wo"], cfg.dtype)


def _dense_mlp(cfg: DecoderConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("ffn/gate_up"):
        h = jax.nn.silu(_mm("bse,ef->bsf", x, p["w_gate"], cfg.dtype)) * _mm("bse,ef->bsf", x, p["w_up"], cfg.dtype)
        h = with_constraint(h, ("batch", "length", "mlp"))
    with jax.named_scope("ffn/down"):
        return _mm("bsf,fe->bse", h, p["w_down"], cfg.dtype)


def _ffn(cfg: DecoderConfig, p: Params, x: jnp.ndarray, valid, held: Optional[Params], layer):
    """-> (y, routed-layer counters or zeros).  ``held``: None in the dense
    stack, else the expert stack's held experts, whole, with ``layer`` the
    model's layer index."""
    h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    if held is None:
        return _dense_mlp(cfg, p, h), jnp.zeros((MOE_STAT_HEAD + cfg.latent_moe.experts_held,), jnp.int32)
    y, stats = held_experts_mlp(cfg, dict(p, **held), h, valid, layer - cfg.latent_moe.first_dense_layers)
    if cfg.latent_moe.n_shared_experts:
        y = y + shared_experts_mlp(cfg, p, h)
    return y, stats


def _scan_layers(cfg: DecoderConfig, params: Params, attend, valid, x, pools, *, constrain: bool = False):
    """Every layer as ``lax.scan``s -> (x, pools, per-sublayer outputs on a
    leading axis of :func:`cache_layers`, routed-layer counters ``[layers, n]``).

    ``attend(p, x, pools, row) -> (x + A(n(x)), pools, out)`` is the calling
    program's attention sublayer over one sublayer's weights ``p``, writing and
    reading the cache's layer ``row``; the feed-forward parts and the residual
    stream's order are here, once for the three programs.  Two block forms:

    - one attention and one FFN a layer: a scan over the dense stack, then one
      over the expert stack (two compiled bodies whatever the depth);
    - the shortcut-connected double layer (the module docstring has its
      equations): one scan, a body of two attention sublayers, two dense FFNs
      and the expert layer, the sublayers' weights ``dense_layers[2l]`` and
      ``[2l + 1]`` and their cache rows the same two.

    The held experts do NOT ride the expert scan's ``xs``: a scan's slice of
    them is a value the size of a layer's experts (1.06 GB at A.X-K1's widths)
    that XLA either reads whole (the decode step's einsums) or first copies out
    so that a loop can index it (prefill).  The bodies close over the whole
    stack and the layer index comes from ``xs``, as the latent pool rides the
    carry, so the experts are read in place, by ``(layer, expert)``
    (:func:`.mixtral.held_experts_mlp`).  Router, norms, shared expert and
    attention weights stay in ``xs``."""
    nd, nm = _stack_sizes(cfg)
    moe = params["moe_layers"]
    held = {k: moe[k] for k in HELD_KEYS}
    sliced = {k: v for k, v in moe.items() if k not in HELD_KEYS}

    def out_of(x):
        return with_constraint(x, ("batch", "length", "embed")) if constrain else x

    if cfg.latent_moe.double_layer:
        sub = params["dense_layers"]

        def body(carry, inputs):
            x, pools = carry
            pm, layer = inputs
            # a sublayer's weights sliced from the stack by its own row, as a scan slices its xs: the slice is the
            # dot's operand.  (The stack as xs in [layers, 2, ...] form made a copy of both sublayers' weights a layer)
            p0, p1 = ({k: jax.lax.dynamic_index_in_dim(v, 2 * layer + i, 0, keepdims=False) for k, v in sub.items()}
                      for i in (0, 1))
            x, pools, out0 = attend(p0, x, pools, 2 * layer)
            h = rms_norm(x, p0["mlp_norm"], cfg.rms_norm_eps)
            m, stats = held_experts_mlp(cfg, dict(pm, **held), h, valid, layer)  # the shortcut: joins below
            x, pools, out1 = attend(p1, x + _dense_mlp(cfg, p0, h), pools, 2 * layer + 1)
            x = x + _dense_mlp(cfg, p1, rms_norm(x, p1["mlp_norm"], cfg.rms_norm_eps)) + m
            return (out_of(x), pools), (jax.tree.map(lambda a, b: jnp.stack([a, b]), out0, out1), stats)

        (x, pools), (out, stats) = jax.lax.scan(body, (x, pools), (sliced, jnp.arange(nm)))
        return x, pools, jax.tree.map(lambda a: a.reshape((nd,) + a.shape[2:]), out), stats

    def make_body(held):
        def body(carry, inputs):
            x, pools = carry
            p, layer = inputs
            x, pools, out = attend(p, x, pools, layer)
            y, stats = _ffn(cfg, p, x, valid, held, layer)
            return (out_of(x + y), pools), (out, stats)

        return body

    carry, y_d = jax.lax.scan(make_body(None), (x, pools), (params["dense_layers"], jnp.arange(nd)))
    (x, pools), y_m = jax.lax.scan(make_body(held), carry, (sliced, jnp.arange(nd, nd + nm)))
    out, stats = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0), y_d, y_m)
    return x, pools, out, stats


def _finish(params: Params, cfg: DecoderConfig, x_last: jnp.ndarray) -> jnp.ndarray:
    logits = _head_logits(params, cfg, rms_norm(x_last, params["final_norm"], cfg.rms_norm_eps))
    return logits[..., :cfg.vocab_size].astype(jnp.float32)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill(params: Params, cfg: DecoderConfig, input_ids: jnp.ndarray, lengths: jnp.ndarray):
    """Right-padded prompts through the model -> (last-token logits [B,V] f32,
    latent rows [L,B,S,W], counters): the second and third go to
    :func:`insert_sequences_paged` where the llama path hands ``ks, vs``.  With
    an indexer the second is ``(latent rows, index keys [L,B,S,Di])``, and a
    sequence longer than ``index_topk`` attends under the selection; a shorter
    one selects everything, which is the causal attention itself."""
    B, S = input_ids.shape
    lm = cfg.latent_moe
    cos, sin = _rope_tables(cfg, S)
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    x = _embed(params, cfg, input_ids)
    qpos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    select = bool(lm.index_topk) and S > lm.index_topk
    if select:  # right-padded: causal and real keeps real queries on real keys
        ok = (jnp.arange(S)[None, None, :] <= qpos[:, :, None]) & valid[:, :, None]

    def attend(p, x, pools, row_at):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_rope, row, c_q = _queries_and_row(cfg, p, h, cos, sin)
        if lm.index_topk:
            q_idx, w_idx, k_idx = _index_parts(cfg, p, h, c_q, cos, sin)
        if select:
            keep, scanned = sparse_select(q_idx, w_idx, k_idx, qpos, ok, lm.index_topk, lengths)
            o = _expanded_attention(cfg, p, q_nope, q_rope, row, keep=keep, live=lengths)
            selected = keep.sum()
        else:
            # right-padded input: causal masking alone keeps real queries on real keys
            o = _expanded_attention(cfg, p, q_nope, q_rope, row, causal=True)
            selected, scanned = jnp.where(valid, qpos + 1, 0).sum(), 0
        out = (row, k_idx, _dsa_counts(valid, qpos, selected, scanned)) if lm.index_topk else (row,)
        return x + _attn_out(cfg, p, o), pools, out

    x, _, out, stats = _scan_layers(cfg, params, attend, valid, x, None, constrain=True)
    last = jnp.take_along_axis(x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    if lm.index_topk:
        return _finish(params, cfg, last), (out[0], out[1]), _stats_row(cfg, stats, out[2], 1)
    return _finish(params, cfg, last), out[0], stats.sum(0)


def insert_sequences_paged(
    cache: LatentKVCache,
    rows: jnp.ndarray,  # [L, B, Sb, W] from prefill
    stats: jnp.ndarray,  # prefill's routed-layer counters
    lengths: jnp.ndarray,  # [B]
    slots: jnp.ndarray,  # [B] int32 (max_slots sentinel = pad row)
    block_tables: jnp.ndarray,  # [B, NB]; pad rows carry the P sentinel
) -> LatentKVCache:
    """Write prefilled rows into their slots' pages (positions [0, Sb)), whole
    pages at a time; blocks past a row's allocation and pad rows drop.  With an
    indexer ``rows`` is :func:`prefill`'s pair and the index keys go to the
    same pages of the second pool."""
    P, page = cache.n_pages, cache.page_size

    def write(pool, rows):
        Sb = rows.shape[2]
        nbw = min(block_tables.shape[1], -(-Sb // page))
        if nbw * page != Sb:
            rows = jnp.pad(rows, ((0, 0), (0, 0), (0, nbw * page - Sb), (0, 0)))
        for j in range(nbw):
            blk = jax.lax.slice_in_dim(rows, j * page, (j + 1) * page, axis=2)
            pool = pool.at[:, jnp.minimum(block_tables[:, j], P)].set(blk.astype(pool.dtype), mode="drop")
        return pool

    if cache.idx is None:
        kv, idx = write(cache.kv, rows), None
    else:
        kv, idx = write(cache.kv, rows[0]), write(cache.idx, rows[1])
    return LatentKVCache(
        kv=kv,
        lengths=cache.lengths.at[slots].set(lengths.astype(cache.lengths.dtype), mode="drop"),
        stats=cache.stats.at[1].add(stats),
        idx=idx,
    )


def copy_pages(cache: LatentKVCache, src: jnp.ndarray, dst: jnp.ndarray) -> LatentKVCache:
    """Clone whole pages inside the pool (the allocator's copy-on-write
    primitive), index keys with their latent rows; dst entries >= P drop."""
    P = cache.n_pages

    def clone(pool):
        return pool.at[:, jnp.minimum(dst, P)].set(jnp.take(pool, jnp.clip(src, 0, P - 1), axis=1), mode="drop")

    return cache._replace(kv=clone(cache.kv), idx=None if cache.idx is None else clone(cache.idx))


def _gather_rows(pool, layer, block_tables):
    """One layer's logical view of each row's pages -> [B, NB*page, W], gathered
    from the whole pool by (layer, page): slicing the layer out first is a copy
    the size of a layer of the pool, every layer of every chunk."""
    L, P, page, W = pool.shape
    B, NB = block_tables.shape
    return pool[layer, jnp.clip(block_tables, 0, P - 1)].reshape(B, NB * page, W)


def _prefill_against_cache(params, cfg, input_ids, cache, block_tables, starts, valids, *, chunk: bool):
    """Chunk and suffix prefill: ``C`` new tokens per row at positions
    ``starts + [0, C)`` against what the row's pages already hold.  Per layer
    the new rows are scattered into the pool token by token (pad tokens and
    unallocated blocks drop, so a shared prefix page is never written), then
    the row's logical view is gathered and attended in the expanded form.

    With an indexer the index keys are written and gathered the same way, and
    where the view is longer than ``index_topk`` the queries attend under the
    selection: index scores reduced over the indexer's heads a tile at a time,
    the top-k as a threshold found by counting, the attention as a flash
    kernel that takes the selection as its mask, so that nothing of [heads,
    chunk, context] size exists; scores and counting run over the live part of
    the view, in static steps.  Only positions below a query's own and on an
    allocated page can be selected: a reused page's stale rows lie past the
    slot's position.  -> (logits, latent pool, index pool or None, counters)."""
    B, C = input_ids.shape
    L, P, page, W = cache.kv.shape
    lm = cfg.latent_moe
    NB = block_tables.shape[1]
    S = NB * page
    pos = starts[:, None] + jnp.arange(C)[None, :]  # [B, C]
    real = jnp.arange(C)[None, :] < valids[:, None]
    cos_t, sin_t = _rope_tables(cfg, S)
    safe = jnp.minimum(pos, S - 1)
    cos, sin = cos_t[safe], sin_t[safe]
    phys = jnp.take_along_axis(block_tables, jnp.minimum(pos // page, NB - 1), axis=1)
    phys = jnp.where(real & (pos < S), jnp.minimum(phys, P), P)
    off = pos % page
    select = bool(lm.index_topk) and S > lm.index_topk
    if select:
        allocated = jnp.repeat((block_tables >= 0) & (block_tables < P), page, axis=1)  # [B, S]
        ok = (jnp.arange(S)[None, None, :] <= pos[:, :, None]) & allocated[:, None, :] & real[:, :, None]
        live = jnp.minimum(starts + valids, S)
    else:
        mask = (jnp.arange(S)[None, None, None, :] <= pos[:, None, :, None])  # [B,1,C,S]
    x = _embed(params, cfg, input_ids)

    def attend(p, x, pools, layer):
        pool, ipool = pools
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_rope, row, c_q = _queries_and_row(cfg, p, h, cos, sin)
        if lm.index_topk:
            q_idx, w_idx, k_idx = _index_parts(cfg, p, h, c_q, cos, sin)
        with jax.named_scope("attn/kv_write"):
            pool = pool.at[layer, phys, off].set(row.astype(pool.dtype), mode="drop")
            if lm.index_topk:
                ipool = ipool.at[layer, phys, off].set(k_idx.astype(ipool.dtype), mode="drop")
        if select:
            o, selected, scanned = _sparse_attention_over_pages(
                cfg, p, q_nope, q_rope, q_idx, w_idx, pool, ipool, layer, block_tables, pos, ok, live)
        else:
            with jax.named_scope("attn/kv_read"):
                rows = _gather_rows(pool, layer, block_tables)
            o = _expanded_attention(cfg, p, q_nope, q_rope, rows, mask=mask)
            selected, scanned = jnp.where(real, pos + 1, 0).sum(), 0
        dsa = _dsa_counts(real, pos, selected, scanned) if lm.index_topk else None
        return x + _attn_out(cfg, p, o), (pool, ipool), dsa

    x, (pool, ipool), dsa, stats = _scan_layers(cfg, params, attend, real, x, (cache.kv, cache.idx))
    last = jnp.take_along_axis(x, jnp.maximum(valids - 1, 0)[:, None, None], axis=1)[:, 0]
    return _finish(params, cfg, last), pool, ipool, _stats_row(cfg, stats, dsa, 0 if chunk else 1)


def prefill_suffix_paged(params, cfg, input_ids, cache, block_tables, slots, starts, valids):
    """Suffix tokens ``[B, C]`` after each row's ``starts`` cached tokens ->
    (logits [B,V] f32, cache)."""
    logits, pool, ipool, stats = _prefill_against_cache(
        params, cfg, input_ids, cache, block_tables, starts, valids, chunk=False)
    lengths = cache.lengths.at[slots].set((starts + valids).astype(cache.lengths.dtype), mode="drop")
    return logits, LatentKVCache(kv=pool, lengths=lengths, stats=cache.stats.at[1].add(stats), idx=ipool)


def prefill_chunk_paged(params, cfg, input_ids, cache, block_table, slot, start, valid):
    """One chunk ``[1, C]`` of one long prompt extends the slot's page chain ->
    (logits [1,V] f32, cache)."""
    logits, pool, ipool, stats = _prefill_against_cache(
        params, cfg, input_ids, cache, block_table[None, :], jnp.reshape(start, (1,)), jnp.reshape(valid, (1,)),
        chunk=True,
    )
    lengths = jax.lax.dynamic_update_index_in_dim(
        cache.lengths, (start + valid).astype(cache.lengths.dtype), slot, 0
    )
    return logits, LatentKVCache(kv=pool, lengths=lengths, stats=cache.stats.at[1].add(stats), idx=ipool)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode_step_paged(
    params: Params,
    cfg: DecoderConfig,
    tokens: jnp.ndarray,  # [B] int32
    cache: LatentKVCache,
    block_tables: jnp.ndarray,  # [B, NB] int32
    *,
    active: Optional[jnp.ndarray] = None,
    attn_fp8: bool = False,
) -> tuple[jnp.ndarray, LatentKVCache]:
    """One autoregressive step for every active slot over the latent pool ->
    (logits [B,V] f32, cache).

    Per layer the step writes ONE ``[W]`` row per slot at ``(block_table[b, pos
    // page], pos % page)`` and attends over the latent rows themselves:
    ``q_abs = q_nope W_UK^T`` per head, scores ``(q_abs . c_kv + q_rope .
    k_rope) * scale``, ``o = (softmax c_kv) W_UV``.  The pool rides the layer
    scans' CARRY with the layer index in ``xs``; on a TPU one Pallas call per
    layer (:func:`~..ops.attention.latent_decode_update_attend`) patches the
    row in place and DMAs the live pages, each once for scores and values, so
    nothing in the step makes a value the size of a layer of the pool (PERF.md
    section 5, PR 25).  Elsewhere a drop-mode scatter and the plain gather
    (:func:`~..ops.attention.latent_decode_attention`) do the same.  Inactive
    rows and rows past their allocation write nothing on either path.

    With an indexer (``index_topk``) and a view longer than it, a layer writes
    the slot's latent row and index key where they belong, scores the index
    keys of the slot's pages, takes the top-k among positions ``<= pos`` on
    allocated pages, and attends those latent rows in the absorbed form.  On a
    TPU all three stages follow the same work list, the two pools left in HBM:
    :func:`~..ops.attention.paged_index_scores` (the key written in place, a
    ``[B, NB, page]`` row of scores out), :func:`~..ops.attention.
    topk_select_paged` (exact, by counting) and the latent kernel under the
    selection as a mask; nothing of ``[slots, view]`` x heads or key width is
    made, nothing is sorted, and a slot that is not active is not worked on.
    Elsewhere the plain functions: the index keys gathered, ``top_k``, the
    selected rows gathered (:func:`~..ops.attention.
    sparse_latent_decode_attention`)."""
    if attn_fp8:
        raise NotImplementedError("attn_fp8 is not implemented for the latent cache")
    lm = cfg.latent_moe
    B = tokens.shape[0]
    L, P, page, W = cache.kv.shape
    NB = block_tables.shape[1]
    S = NB * page
    H, dr, dv, C = cfg.num_heads, lm.qk_rope_head_dim, lm.v_head_dim, lm.kv_lora_rank
    if active is None:
        active = jnp.ones((B,), bool)
    active = active & (cache.lengths < S)
    positions = jnp.minimum(cache.lengths, S - 1)
    cos_t, sin_t = _rope_tables(cfg, S)
    cos, sin = cos_t[positions][:, None, :], sin_t[positions][:, None, :]
    scale = softmax_scale(cfg)
    select = bool(lm.index_topk) and S > lm.index_topk
    kernel = decode_kv_path(cfg, cache.kv.dtype, page) == "kernel"
    if kernel:
        plan = paged_decode_plan(block_tables, positions, active, n_pages=P, page=page)
    if not kernel or (lm.index_topk and not select):  # where a row or a key is written by a scatter
        phys = jnp.take_along_axis(block_tables, (positions // page)[:, None], axis=1)[:, 0]
        phys_w = jnp.where(active, jnp.minimum(phys, P), P)
        off = positions % page
    x = _embed(params, cfg, tokens)[:, None, :]
    valid = active[:, None]
    if select and not kernel:
        allocated = jnp.repeat((block_tables >= 0) & (block_tables < P), page, axis=1)  # [B, S]
        ok = (jnp.arange(S)[None, :] <= positions[:, None]) & allocated & active[:, None]
    if lm.index_topk and not select:  # the counters' selected pairs where the view keeps everything
        every_pair = jnp.where(active, positions + 1, 0).sum()

    def attend(p, x, pools, layer):
        pool, ipool = pools
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_rope, row, c_q = _queries_and_row(cfg, p, h, cos, sin)
        with jax.named_scope("attn/absorb"):
            q_abs = _mm("bhd,hdc->bhc", q_nope[:, 0], p["w_uk"], cfg.dtype)
            pad = W - C - dr
            q = jnp.concatenate(
                [q_abs, q_rope[:, 0]] + ([jnp.zeros((B, H, pad), q_abs.dtype)] if pad else []), axis=-1
            )
        if lm.index_topk:
            q_idx, w_idx, k_idx = _index_parts(cfg, p, h, c_q, cos, sin)
        if kernel:
            keep = None
            if select:
                scores, ipool = paged_index_scores(
                    q_idx[:, 0], w_idx[:, 0], k_idx[:, 0], ipool, layer, block_tables, positions, plan)
                keep = topk_select_paged(scores, active, lm.index_topk)
                with jax.named_scope("attn/select"):
                    selected = keep.sum()
            elif lm.index_topk:  # everything is attended: the key is kept for a longer view's sake
                with jax.named_scope("attn/kv_write"):
                    ipool = ipool.at[layer, phys_w, off].set(k_idx[:, 0].astype(ipool.dtype), mode="drop")
            o_lat, pool = latent_decode_update_attend(
                q, row[:, 0], pool, layer, block_tables, positions, plan, scale=scale, value_width=C, keep=keep
            )
        else:
            with jax.named_scope("attn/kv_write"):
                pool = pool.at[layer, phys_w, off].set(row[:, 0].astype(pool.dtype), mode="drop")
                if lm.index_topk:
                    ipool = ipool.at[layer, phys_w, off].set(k_idx[:, 0].astype(ipool.dtype), mode="drop")
            if select:
                with jax.named_scope("attn/kv_read"):
                    keys = _gather_rows(ipool, layer, block_tables)  # the index keys of the slot's pages
                idx, picked = sparse_decode_select(index_scores(q_idx, w_idx, keys)[:, 0], ok, lm.index_topk)
                o_lat = sparse_latent_decode_attention(
                    q, pool, layer, block_tables, idx, picked, scale=scale, value_width=C)
                selected = picked.sum()
            else:
                o_lat = latent_decode_attention(
                    q, jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False), block_tables, positions,
                    scale=scale, value_width=C, active=active,
                )
        with jax.named_scope("attn/absorb"):
            o = _mm("bhc,hdc->bhd", o_lat, p["w_uv"], cfg.dtype)
        if not lm.index_topk:
            dsa = None
        elif select:  # a step's selection counts over the whole view of every active row
            dsa = _dsa_counts(active, positions, selected, active.sum() * S)
        else:
            dsa = _dsa_counts(active, positions, every_pair, 0)
        return x + _attn_out(cfg, p, o.reshape(B, 1, H * dv)), (pool, ipool), dsa

    x, (pool, ipool), dsa, stats = _scan_layers(cfg, params, attend, valid, x, (cache.kv, cache.idx))
    new_cache = LatentKVCache(
        kv=pool,
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths),
        stats=cache.stats.at[0].add(_stats_row(cfg, stats, dsa, 0)),
        idx=ipool,
    )
    return _finish(params, cfg, x[:, 0]), new_cache
