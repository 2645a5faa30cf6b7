"""Attention: pallas flash kernel for TPU + a jnp reference path.

Replaces the torch SDPA the reference reaches through ``AutoModel`` forwards
(reference: assistant/ai/embedders/transformers.py:15-29, providers/transformers.py:35-94).

Two paths, one contract:

- :func:`dot_product_attention` — pure jnp, f32 accumulation.  Used on CPU, in tests,
  and for short decode steps where the MXU is already saturated by the projections.
- :func:`flash_attention` — pallas TPU kernel, blocked online-softmax so the [S, S]
  score matrix never materialises in HBM (O(S) memory; the win for long prefill).

Both take ``[batch, heads, seq, head_dim]`` and support causal masking and GQA
(kv heads broadcast by the caller via repeat — XLA dedups the memory).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant import quantize_fp8

NEG_INF = -1e30

_FP8_DTYPES = (jnp.float8_e4m3fn, jnp.float8_e5m2)


def _check_fp8_dot(kv_dtype, site: str) -> None:
    if not any(jnp.dtype(kv_dtype) == jnp.dtype(t) for t in _FP8_DTYPES):
        raise ValueError(
            f"{site}: fp8_dot=True requires an fp8 KV cache "
            f"(float8_e4m3fn / float8_e5m2), got {kv_dtype}"
        )


def dot_product_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, H, Sk, D]
    v: jnp.ndarray,  # [B, H, Sk, D]
    *,
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,  # broadcastable to [B, H, Sq, Sk]; True=keep
    q_offset: int | jnp.ndarray = 0,  # absolute position of q[0] (decode w/ KV cache)
    window: Optional[int] = None,  # sliding window: keep iff kpos > qpos - window
    scale: Optional[float] = None,  # default: D ** -0.5
) -> jnp.ndarray:
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal or window is not None:
        qpos = jnp.arange(q.shape[2]) + q_offset
        kpos = jnp.arange(k.shape[2])
        keep = jnp.ones((q.shape[2], k.shape[2]), bool)
        if causal:
            keep &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            # HF sliding-window semantics (masking_utils.sliding_window_overlay):
            # a query attends to the `window` most recent positions incl. itself
            keep &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(keep[None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@jax.named_scope("attn/kv_read")
def gqa_dot_product_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, KH, Sk, D] — KV heads NOT repeated
    v: jnp.ndarray,  # [B, KH, Sk, D]
    *,
    mask: Optional[jnp.ndarray] = None,  # broadcastable to [B, 1, Sq, Sk]; True=keep
) -> jnp.ndarray:
    """Grouped-query attention that contracts query groups against the shared
    KV heads directly — no ``repeat(q_per_kv)`` materialization.

    On the decode path the repeat is the single biggest memory consumer: a
    [B, KH, S, D] view of a slot's keys repeated to H heads writes+reads q_per_kv x the
    cache bytes EVERY step (multi-GB of pure copy traffic at serving shapes).
    Grouping the einsum reads the cache once.
    """
    B, H, Sq, D = q.shape
    KH = k.shape[1]
    G = H // KH
    scale = D ** -0.5
    if k.dtype != q.dtype:
        # reduced-precision KV cache (e.g. fp8): a pure convert on the matmul
        # operand — fused into the dot, so the cache is READ at its own width
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    qg = q.reshape(B, KH, G, Sq, D)
    scores = jnp.einsum(
        "bkgqd,bksd->bkgqs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        m = mask[:, :, None] if mask.ndim == 4 else mask  # insert group axis
        scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs, v)
    return out.reshape(B, H, Sq, D)


@jax.named_scope("attn/kv_read")
def paged_gqa_decode_attention(
    q: jnp.ndarray,  # [B, H, 1, D]
    k_pool: jnp.ndarray,  # [P, KH, page, D] page pool, storage dtype (bf16 / fp8)
    v_pool: jnp.ndarray,  # [P, KH, page, D]
    block_tables: jnp.ndarray,  # [B, NB] int32 — physical page per logical block;
    #                             entries >= P mean "unallocated" (read masked)
    positions: jnp.ndarray,  # [B] int32 — absolute position of each slot's query
    *,
    active: Optional[jnp.ndarray] = None,  # [B] bool; inactive rows don't widen the read
    window: Optional[int] = None,
    fp8_dot: bool = False,
) -> jnp.ndarray:
    """Length-aware decode attention over the page pool: the KV "row" of a
    slot is a chain of fixed-size pages scattered through a shared pool,
    resolved one gather per logical block, and every block past the batch's
    maximum valid position is SKIPPED.

    The count of blocks read is a *traced* ``fori_loop`` bound derived from
    ``positions`` — one compiled program for every fill level, no dynamic
    shapes, no recompiles; inactive rows are excluded so one stale long slot
    cannot widen a short batch's read.  Per-slot validity inside the boundary
    page is handled by masking.  Logical blocks past a row's allocation
    gather a clamped page whose keys are masked out (scores pinned to
    ``NEG_INF`` -> exact zero contribution).

    Reduced-precision pools dequantize PER PAGE: the ``astype`` sits on the
    gathered operand, so the pool streams from HBM at its own width and no
    bf16-sized copy of the cache is ever made.

    Numerics: online softmax (flash discipline) with f32 running max/sum/acc —
    equal to the full softmax up to reduction order (tests/test_kv_paging.py,
    tests/test_longctx_decode.py: per-dtype tolerance across ragged lengths
    and page boundaries).  A row whose band starts past the first processed
    page self-corrects: its all-masked pages contribute with ``m = -inf`` and
    are zeroed by ``alpha = exp(-inf - m_new)`` once a live page arrives.

    ``fp8_dot`` (docs/QUANT.md "fp8 in-dot"): keep the fp8 pool operand in its
    storage dtype THROUGH the QK dot instead of upcasting first.  The query is
    quantized to the pool's fp8 format once, outside the page loop, and its
    per-(kv-head, group) f32 scale multiplies the f32 score partials — the
    same scale-on-partials discipline as the int4 ``qeinsum`` (the per-page
    pool scale is 1.0 by the storage contract, so only the query scale
    appears).  The PV dot likewise runs fp8 x fp8; the softmax normalizer
    ``l`` stays computed from the f32 probabilities.
    ``paged_tree_attention`` deliberately keeps the dequant read: the verify
    forward is one tick amortized over K+1 tokens, so its attention dot is
    not the bandwidth bottleneck the per-step decode dot is.
    """
    B, H, Sq, D = q.shape
    if Sq != 1:
        raise ValueError(f"decode attention expects Sq=1 queries, got {Sq}")
    P, KH, page, _ = k_pool.shape
    NB = block_tables.shape[1]
    S = NB * page
    G = H // KH
    scale = D ** -0.5
    if active is None:
        active = jnp.ones((B,), bool)
    qg = q.reshape(B, KH, G, D)
    if fp8_dot:
        _check_fp8_dot(k_pool.dtype, "paged_gqa_decode_attention")
        qg_q, qg_s = quantize_fp8(qg, axis=-1, dtype=k_pool.dtype)

    act_pos = jnp.where(active, positions, 0)
    hi = jnp.minimum(jnp.max(act_pos) // page + 1, NB)
    if window is not None:
        min_pos = jnp.min(jnp.where(active, positions, S))
        lo = jnp.minimum(jnp.maximum(min_pos - window + 1, 0) // page, hi)
    else:
        lo = jnp.zeros((), hi.dtype)

    def body(ci, carry):
        m, l, acc = carry
        phys = jax.lax.dynamic_slice_in_dim(block_tables, ci, 1, axis=1)[:, 0]
        phys = jnp.clip(phys, 0, P - 1)  # sentinel rows read a live page, masked below
        k_blk = jnp.take(k_pool, phys, axis=0)  # [B, KH, page, D]
        v_blk = jnp.take(v_pool, phys, axis=0)
        if fp8_dot:
            s = jnp.einsum(
                "bkgd,bksd->bkgs", qg_q, k_blk,
                preferred_element_type=jnp.float32,
            ) * (qg_s * scale)  # [B, KH, G, page]
        else:
            if k_blk.dtype != q.dtype:
                k_blk = k_blk.astype(q.dtype)
                v_blk = v_blk.astype(q.dtype)
            s = jnp.einsum(
                "bkgd,bksd->bkgs", qg, k_blk, preferred_element_type=jnp.float32
            ) * scale  # [B, KH, G, page]
        kpos = ci * page + jnp.arange(page)
        keep = kpos[None, :] <= positions[:, None]  # [B, page]
        if window is not None:
            keep &= kpos[None, :] > positions[:, None] - window
        s = jnp.where(keep[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum(
            "bkgs,bksd->bkgd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((B, KH, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, 1), jnp.float32)
    a0 = jnp.zeros((B, KH, G, D), jnp.float32)
    _, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out.reshape(B, H, 1, D)


@jax.named_scope("attn/kv_read")
def paged_tree_attention(
    q: jnp.ndarray,  # [B, H, T, D] — one query per speculation-tree node
    k_pool: jnp.ndarray,  # [P, KH, page, D] page pool, storage dtype
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, NB] int32; entries >= P unallocated
    lengths: jnp.ndarray,  # [B] int32 — verified prefix length per row
    tree_k: jnp.ndarray,  # [B, KH, T, D] — the tree's freshly-projected keys
    tree_v: jnp.ndarray,
    anc_mask: jnp.ndarray,  # [T, T] bool — anc_mask[t, u]: u ancestor-or-self of t
    depths: jnp.ndarray,  # [T] int32 node depths (root = 0)
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Tree-query variant of :func:`paged_gqa_decode_attention` for the
    speculative verify forward: every tree node attends to the row's
    verified prefix (positions ``< lengths``, read IN PLACE from the page
    pool one block-table gather per logical block — never a materialised
    dense copy of the logical row) plus its own root-path ancestors through
    the tree's fresh K/V, processed as one final masked chunk in the same
    online-softmax stream.

    The page loop reuses the decode read's structure exactly (same loop
    bounds, same masking, same f32 running max/sum/acc); the tree chunk is
    one more update with the ancestor mask in place of the positional one.
    Reduced-precision pools dequantize per page like the decode path.
    """
    B, H, T, D = q.shape
    P, KH, page, _ = k_pool.shape
    NB = block_tables.shape[1]
    G = H // KH
    scale = D ** -0.5
    qg = q.reshape(B, KH, G, T, D)
    # pages [lo, hi) cover every row's verified prefix (lengths == 0 rows
    # read nothing from the pool; their tree self-attention keeps l > 0)
    hi = jnp.minimum((jnp.max(lengths) + page - 1) // page, NB)
    qpos = lengths[:, None] + depths[None, :]  # [B, T] query positions
    if window is not None:
        lo = jnp.minimum(
            jnp.maximum(jnp.min(lengths) - window + 1, 0) // page, hi
        )
    else:
        lo = jnp.zeros((), hi.dtype)

    def body(ci, carry):
        m, l, acc = carry
        phys = jax.lax.dynamic_slice_in_dim(block_tables, ci, 1, axis=1)[:, 0]
        phys = jnp.clip(phys, 0, P - 1)
        k_blk = jnp.take(k_pool, phys, axis=0)  # [B, KH, page, D]
        v_blk = jnp.take(v_pool, phys, axis=0)
        if k_blk.dtype != q.dtype:
            k_blk = k_blk.astype(q.dtype)
            v_blk = v_blk.astype(q.dtype)
        s = jnp.einsum(
            "bkgtd,bksd->bkgts", qg, k_blk, preferred_element_type=jnp.float32
        ) * scale  # [B, KH, G, T, page]
        kpos = ci * page + jnp.arange(page)
        keep = kpos[None, None, :] < lengths[:, None, None]  # [B, 1, page]
        if window is not None:
            keep = keep & (kpos[None, None, :] > qpos[:, :, None] - window)
        s = jnp.where(keep[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum(
            "bkgts,bksd->bkgtd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((B, KH, G, T, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, T, 1), jnp.float32)
    a0 = jnp.zeros((B, KH, G, T, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    # the tree itself, as the final online-softmax chunk: ancestor-masked
    # (root attends to itself, so every live query has l > 0 even at
    # lengths == 0)
    tk = tree_k.astype(q.dtype) if tree_k.dtype != q.dtype else tree_k
    tv = tree_v.astype(q.dtype) if tree_v.dtype != q.dtype else tree_v
    s = jnp.einsum(
        "bkgtd,bkud->bkgtu", qg, tk, preferred_element_type=jnp.float32
    ) * scale  # [B, KH, G, T, T]
    keep = anc_mask[None, :, :]  # [1, T, T]
    if window is not None:
        upos = lengths[:, None] + depths[None, :]  # [B, T] key positions
        keep = keep & (upos[:, None, :] > qpos[:, :, None] - window)
    s = jnp.where(keep[:, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = alpha * acc + jnp.einsum(
        "bkgtu,bkud->bkgtd", p.astype(tv.dtype), tv,
        preferred_element_type=jnp.float32,
    )
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out.reshape(B, H, T, D)


# ---------------------------------------------------------------------------
# Pallas paged decode: the step's K/V row written in place, live pages read by DMA
# ---------------------------------------------------------------------------


def _packed_rows(dtype) -> int:
    """Rows of one packed (8, 128) x 32-bit tile: 8 / 16 / 32 for 4 / 2 / 1-byte types."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def paged_decode_kv_path(
    kv_dtype, page: int, head_dim: int, *, fp8_dot: bool = False
) -> str:
    """Which implementation a paged decode step takes: ``"kernel"``
    (:func:`paged_decode_update_attend`) or ``"xla"`` (scatter +
    :func:`paged_gqa_decode_attention`).

    By platform and shape, as :func:`attention` chooses the flash kernel: the
    CPU has no Mosaic compiler, and the kernel moves whole ``[page, head_dim]``
    tiles, so it takes lane-wide heads and pages of whole packed tiles (every
    served geometry; toy models keep the plain function).  ``fp8_dot`` — fp8
    operands THROUGH the dots, where the kernel dequantises per page — stays
    on the plain function too.  On a TPU a kernel-shaped step always reaches
    the kernel: one that does not compile fails the boot, it does not fall
    back."""
    kernel_shaped = head_dim % 128 == 0 and page % _packed_rows(kv_dtype) == 0
    if kernel_shaped and not fp8_dot and jax.default_backend() == "tpu":
        return "kernel"
    return "xla"


def paged_decode_plan(
    block_tables: jnp.ndarray,  # [B, NB] int32; entries >= n_pages unallocated
    positions: jnp.ndarray,  # [B] int32
    active: jnp.ndarray,  # [B] bool
    *,
    n_pages: int,
    page: int,
    window: Optional[int] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The pages one decode step touches, as a work list for the kernel:
    ``items[i] = slot * NB + block`` for every (active slot, logical block)
    whose keys the slot's query can see and whose table entry names a page —
    slot-major, blocks ascending — and their count ``[1]``.

    Per ROW (``[lo_b, hi_b)`` from the row's own position and window), not
    the batch-wide ``[lo, hi)`` of :func:`paged_gqa_decode_attention`: a block
    the plain loop visits for another row's sake is fully masked for this one
    and contributes exactly zero there, so leaving it out changes no result.
    Inactive rows and sentinel blocks are not on the list: nothing of theirs
    is read, and a slot whose write block is not on the list writes nothing.
    The same for every layer of a step, so it is built once outside the scan."""
    B, NB = block_tables.shape
    blk = jnp.arange(NB, dtype=jnp.int32)[None, :]
    live = active[:, None] & (blk <= (positions // page)[:, None])
    if window is not None:
        live &= blk >= (jnp.maximum(positions - window + 1, 0) // page)[:, None]
    live &= (block_tables >= 0) & (block_tables < n_pages)
    flat = live.reshape(-1)
    items = jnp.nonzero(flat, size=B * NB, fill_value=0)[0].astype(jnp.int32)
    return items, jnp.sum(flat, dtype=jnp.int32)[None]


def _paged_decode_kernel(
    # scalar prefetch (SMEM)
    items_ref,  # [B*NB] the plan's work list
    n_ref,  # [1] live entries of it
    bt_ref,  # [B*NB] block tables, flat
    pos_ref,  # [B]
    layer_ref,  # [1]
    # inputs
    q_ref,  # [B, KH, Gp, D] VMEM, query groups padded to whole sublane tiles
    kn_ref,  # [B, KH, 1, D] f32 VMEM: the step's new K row per slot
    vn_ref,
    k_hbm,  # [L, P, KH, page, D] HBM: never loaded whole
    v_hbm,
    # outputs
    o_ref,  # [B, KH, Gp, D] f32 VMEM: the accumulator, normalised at the end
    k_out,  # the same buffers as k_hbm / v_hbm (input_output_aliases)
    v_out,
    # scratch
    kbuf,  # [2, KH, page, D] double-buffered page
    vbuf,
    m_scr,  # [B, KH, Gp, 128] f32 running max (every lane the same)
    l_scr,  # [B, KH, Gp, 128] f32 running sum
    rsem,  # DMA [2 (k/v), 2 (buffer)]
    wsem,  # DMA [2 (k/v)]
    *,
    nb: int,
    page: int,
    window: Optional[int],
    sub: int,  # rows of one packed sublane tile of the pool's dtype
):
    """One layer of one decode step over the plan's work list.

    Per item — one page of one slot — the page is DMA'd HBM -> VMEM (the next
    item's DMA is already in flight), folded into that slot's online softmax,
    and, where it is the page the slot's position falls in, first patched with
    the step's new row and the one ``sub``-row tile that holds it DMA'd back:
    the only bytes of the pool this kernel writes."""
    KH, Gp, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    layer = layer_ref[0]
    n = n_ref[0]
    scale = D ** -0.5

    def page_copies(i, s):
        phys = bt_ref[items_ref[i]]
        return (
            pltpu.make_async_copy(k_hbm.at[layer, phys], kbuf.at[s], rsem.at[0, s]),
            pltpu.make_async_copy(v_hbm.at[layer, phys], vbuf.at[s], rsem.at[1, s]),
        )

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(n > 0)
    def _first():
        for c in page_copies(0, 0):
            c.start()

    def item(i, carry):
        s = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _prefetch():
            for c in page_copies(i + 1, 1 - s):
                c.start()

        it = items_ref[i]
        slot = it // nb
        j = it - slot * nb
        phys = bt_ref[it]
        pos = pos_ref[slot]
        writes = j == pos // page  # the page the step's own row lands in
        off = pos - j * page
        al = pl.multiple_of((off // sub) * sub, sub)
        for c in page_copies(i, s):
            c.wait()

        def tile_copies():
            rows = pl.ds(al, sub)
            return (
                pltpu.make_async_copy(
                    kbuf.at[s, :, rows, :], k_out.at[layer, phys, :, rows, :], wsem.at[0]
                ),
                pltpu.make_async_copy(
                    vbuf.at[s, :, rows, :], v_out.at[layer, phys, :, rows, :], wsem.at[1]
                ),
            )

        @pl.when(writes)
        def _write_row():
            # through float32 and back: exact for every pool dtype, and the
            # select never runs on a packed type
            at_row = jax.lax.broadcasted_iota(jnp.int32, (KH, sub, D), 1) == off - al
            for buf, new_ref in ((kbuf, kn_ref), (vbuf, vn_ref)):
                tile = buf[s, :, pl.ds(al, sub), :].astype(jnp.float32)
                buf[s, :, pl.ds(al, sub), :] = jnp.where(
                    at_row, new_ref[slot], tile
                ).astype(buf.dtype)
            for c in tile_copies():
                c.start()

        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (Gp, page), 1)
        keep = kpos <= pos
        if window is not None:
            keep &= kpos > pos - window
        for h in range(KH):
            q = q_ref[slot, h]  # [Gp, D]
            k = kbuf[s, h].astype(q.dtype)  # [page, D]; a pure convert for fp8 pools
            v = vbuf[s, h].astype(q.dtype)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [Gp, page]
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_scr[slot, h][:, :1]
            l_prev = l_scr[slot, h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            o_ref[slot, h] = alpha * o_ref[slot, h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            m_scr[slot, h] = jnp.broadcast_to(m_new, (Gp, 128))
            l_scr[slot, h] = jnp.broadcast_to(l_new, (Gp, 128))

        @pl.when(writes)
        def _drain():  # the buffer is the next-but-one item's DMA target
            for c in tile_copies():
                c.wait()

        return carry

    jax.lax.fori_loop(0, n, item, 0)

    def normalise(b, carry):
        for h in range(KH):
            o_ref[b, h] = o_ref[b, h] / jnp.maximum(l_scr[b, h][:, :1], 1e-30)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], normalise, 0)


def _paged_decode_call(
    q, k_new, v_new, k_pool, v_pool, layer, block_tables, positions, items, n_items,
    *, window: Optional[int], interpret: bool,
):
    """The bare kernel call on one device's share of the heads."""
    B, H, Sq, D = q.shape
    L, P, KH, page, _ = k_pool.shape
    NB = block_tables.shape[1]
    G = H // KH
    if Sq != 1:
        raise ValueError(f"decode attention expects Sq=1 queries, got {Sq}")
    sub = _packed_rows(k_pool.dtype)
    if page % sub or D % 128:
        raise ValueError(
            f"paged decode kernel needs page % {sub} == 0 and head_dim % 128 == 0, "
            f"got page={page}, head_dim={D}"
        )
    Gp = -(-G // 16) * 16  # whole bf16 sublane tiles; pad rows are zero queries
    qg = q.reshape(B, KH, G, D)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    page_bytes = KH * page * D * jnp.dtype(k_pool.dtype).itemsize
    o, k_pool, v_pool = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, nb=NB, page=page, window=window, sub=sub
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[vmem, vmem, vmem, hbm, hbm],
            out_specs=[vmem, hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, KH, page, D), k_pool.dtype),
                pltpu.VMEM((2, KH, page, D), v_pool.dtype),
                pltpu.VMEM((B, KH, Gp, 128), jnp.float32),
                pltpu.VMEM((B, KH, Gp, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, Gp, D), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operand numbering counts the five scalar-prefetch arguments
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            # four page buffers + the per-slot state and the score tiles
            vmem_limit_bytes=int(4 * page_bytes + (16 << 20)),
        ),
        name="paged_decode",
        interpret=interpret,
    )(
        items, n_items, block_tables.reshape(-1).astype(jnp.int32),
        positions.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
        qg, k_new.astype(jnp.float32), v_new.astype(jnp.float32), k_pool, v_pool,
    )
    out = o[:, :, :G].astype(q.dtype).reshape(B, H, 1, D)
    return out, k_pool, v_pool


@jax.named_scope("attn/kv_read")
def paged_decode_update_attend(
    q: jnp.ndarray,  # [B, H, 1, D]
    k_new: jnp.ndarray,  # [B, KH, 1, D] the step's key per slot (after RoPE)
    v_new: jnp.ndarray,  # [B, KH, 1, D]
    k_pool: jnp.ndarray,  # [L, P, KH, page, D] the whole pool, every layer
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,  # scalar int32
    block_tables: jnp.ndarray,  # [B, NB] int32
    positions: jnp.ndarray,  # [B] int32
    plan: tuple[jnp.ndarray, jnp.ndarray],  # paged_decode_plan(...), same window
    *,
    window: Optional[int] = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The decode step's K/V write and attention read as ONE Pallas call that
    touches the pool only where it must -> ``(o [B,H,1,D], k_pool, v_pool)``.

    The pools stay in HBM (``memory_space=ANY``) and are returned aliased
    (``input_output_aliases``): a custom call takes its operand in the default
    layout, which is the layout the pool has at the program's edge, so XLA has
    neither a reason nor the freedom to re-lay it out between the scatter's
    and the gather's preferred tilings — the copy that cost 5.5 ms of every
    17.8 ms step (PERF.md §5).  Per slot the kernel reads the pages its block
    table names over ``[lo_b, hi_b)`` and writes one ``[KH, D]`` row at
    ``(block_table[b, pos // page], pos % page)``; slots and blocks that are
    not on the plan (inactive, sentinel, past their allocation) read and WRITE
    nothing — the no-write rule is part of the page-sharing contract
    (``llama.decode_step_paged``).

    Numerics: :func:`paged_gqa_decode_attention`'s — operands in the query's
    dtype, float32 scores, running max/sum and accumulator, one page per
    online-softmax update — summed per row over the row's own pages, so equal
    to it up to the order of the page sums.  Inactive rows come back zero.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import MODEL_AXIS
    from ..parallel.sharding import active_mesh

    call = functools.partial(_paged_decode_call, window=window, interpret=interpret)
    args = (q, k_new, v_new, k_pool, v_pool, layer, block_tables, positions, *plan)
    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return call(*args)
    # Mosaic kernels cannot be partitioned automatically (sharded_flash_attention):
    # KV heads over `model`, the pool's own sharding (llama.paged_cache_shardings),
    # each device on its heads' pages with no collective; where the KV heads do
    # not divide the axis the pool is replicated and so is the work.
    split = k_pool.shape[2] % mesh.shape[MODEL_AXIS] == 0
    heads = P(None, MODEL_AXIS if split else None, None, None)
    pool = P(None, None, MODEL_AXIS if split else None, None, None)
    rep = P()
    return jax.shard_map(
        call,
        mesh=mesh,
        in_specs=(heads, heads, heads, pool, pool, rep, rep, rep, rep, rep),
        out_specs=(heads, pool, pool),
        check_vma=False,
    )(*args)


# ---------------------------------------------------------------------------
# Latent (MLA) paged decode: one row per token, read once as key and as value
# ---------------------------------------------------------------------------


def latent_decode_kv_path(kv_dtype, page: int, width: int, index_width: int = 0) -> str:
    """:func:`paged_decode_kv_path` for a latent pool ``[L, P, page, width]``:
    ``"kernel"`` (:func:`latent_decode_update_attend`) on a TPU when rows are
    whole lane tiles and pages whole packed tiles, ``"xla"`` elsewhere.  With
    an indexer (``index_width``: its key's width, the second pool's rows) the
    step's sparse stages are Pallas calls too (:func:`paged_index_scores`,
    :func:`topk_select_paged`, the kernel above under their mask), which lay a
    page's scores on the lanes: index keys and pages in whole lane tiles, or
    the whole step takes the plain path."""
    kernel_shaped = width % 128 == 0 and page % _packed_rows(kv_dtype) == 0
    if index_width:
        kernel_shaped = kernel_shaped and index_width % 128 == 0 and page % 128 == 0
    return "kernel" if kernel_shaped and jax.default_backend() == "tpu" else "xla"


def _walk_plan_pages(
    items_ref, n, bt_ref, pos_ref, layer, new_ref, pool_hbm, pool_out, buf, rsem, wsem, *, nb: int, page: int, sub: int, visit
):
    """The walk over :func:`paged_decode_plan`'s work list that the kernels of
    a pool of ONE array share (``[L, P, page, width]``: latent rows, index
    keys).  Per item, one page of one slot, the page is DMA'd HBM -> ``buf``
    (double-buffered: the next item's is in flight), patched with the step's
    own row ``new_ref[slot]`` where it is the page the slot's position falls
    in, the one ``sub``-row tile that holds the row DMA'd back (the only bytes
    of the pool a step writes), and handed to ``visit(slot, j, pos, s)``: block
    ``j`` of ``slot``, at position ``pos``, in ``buf[s]``."""

    def page_copy(i, s):
        return pltpu.make_async_copy(pool_hbm.at[layer, bt_ref[items_ref[i]]], buf.at[s], rsem.at[s])

    @pl.when(n > 0)
    def _first():
        page_copy(0, 0).start()

    def item(i, carry):
        s = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _prefetch():
            page_copy(i + 1, 1 - s).start()

        it = items_ref[i]
        slot = it // nb
        j = it - slot * nb
        phys = bt_ref[it]
        pos = pos_ref[slot]
        writes = j == pos // page
        off = pos - j * page
        al = pl.multiple_of((off // sub) * sub, sub)
        page_copy(i, s).wait()

        def tile_copy():
            rows = pl.ds(al, sub)
            return pltpu.make_async_copy(buf.at[s, rows, :], pool_out.at[layer, phys, rows, :], wsem.at[0])

        @pl.when(writes)
        def _write_row():
            at_row = jax.lax.broadcasted_iota(jnp.int32, (sub, buf.shape[2]), 0) == off - al
            tile = buf[s, pl.ds(al, sub), :].astype(jnp.float32)
            buf[s, pl.ds(al, sub), :] = jnp.where(at_row, new_ref[slot], tile).astype(buf.dtype)
            tile_copy().start()

        visit(slot, j, pos, s)

        @pl.when(writes)
        def _drain():  # the buffer is the next-but-one item's DMA target
            tile_copy().wait()

        return carry

    jax.lax.fori_loop(0, n, item, 0)


def _latent_decode_kernel(
    # scalar prefetch (SMEM)
    items_ref, n_ref, bt_ref, pos_ref, layer_ref,
    # inputs
    q_ref,  # [B, H, W] VMEM: absorbed query | rotary query | zero pad
    new_ref,  # [B, 1, W] f32 VMEM: the step's latent row per slot
    pool_hbm,  # [L, P, page, W] HBM: never loaded whole
    keep_ref,  # [B, NB, page] int32 VMEM: the selection (non-zero = attend), or None: no such operand
    # outputs
    o_ref,  # [B, H, Wv] f32 VMEM
    pool_out,  # the same buffer as pool_hbm (input_output_aliases)
    # scratch
    buf,  # [2, page, W] double-buffered page
    m_scr,  # [B, H, 128] f32 running max (every lane the same)
    l_scr,  # [B, H, 128] f32 running sum
    rsem,  # DMA [2 (buffer)]
    wsem,  # DMA [1]
    *,
    nb: int,
    page: int,
    sub: int,
    scale: float,
    value_width: int,
):
    """:func:`_paged_decode_kernel` for a latent pool (:func:`_walk_plan_pages`):
    per work item one page of ONE array, the step's row patched in, is folded
    into the slot's online softmax with all heads as the matmul's M dimension.
    The same page is the keys (all ``W`` lanes) and the values (the first
    ``value_width`` lanes): it is read from HBM once.

    Under ``keep_ref`` (the sparse attention's decode step) a key is attended
    where the selection kept it, ``keep & (kpos <= pos)`` in place of ``kpos
    <= pos``.  A row's first pages may then hold no kept key, so the
    probabilities are zeroed under the mask and not only the scores."""
    masked = keep_ref is not None
    H = q_ref.shape[1]
    layer = layer_ref[0]
    n = n_ref[0]
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    def attend(slot, j, pos, s):
        q = q_ref[slot]  # [H, W]
        rows = buf[s].astype(q.dtype)  # [page, W]
        sc = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [H, page]
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (H, page), 1)
        seen = kpos <= pos
        if masked:
            seen &= keep_ref[slot, pl.ds(j, 1), :] != 0  # [1, page]: every head attends the same keys
        sc = jnp.where(seen, sc, NEG_INF)
        m_prev = m_scr[slot][:, :1]
        l_prev = l_scr[slot][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        if masked:
            p = jnp.where(seen, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        o_ref[slot] = alpha * o_ref[slot] + jnp.dot(
            p.astype(q.dtype), rows[:, :value_width], preferred_element_type=jnp.float32
        )
        m_scr[slot] = jnp.broadcast_to(m_new, (H, 128))
        l_scr[slot] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), (H, 128))

    _walk_plan_pages(items_ref, n, bt_ref, pos_ref, layer, new_ref, pool_hbm, pool_out, buf, rsem, wsem,
                     nb=nb, page=page, sub=sub, visit=attend)

    def normalise(b, carry):
        o_ref[b] = o_ref[b] / jnp.maximum(l_scr[b][:, :1], 1e-30)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], normalise, 0)


def _latent_decode_kernel_unmasked(*refs, **static):
    """:func:`_latent_decode_kernel` for the call without a selection, whose
    refs hold no ``keep_ref`` after the three inputs."""
    _latent_decode_kernel(*refs[:8], None, *refs[8:], **static)


def latent_decode_update_attend(
    q: jnp.ndarray,  # [B, H, W]: absorbed query | rotary query | zeros, pool's lane layout
    row_new: jnp.ndarray,  # [B, W] the step's latent row per slot (norm and rotation applied)
    pool: jnp.ndarray,  # [L, P, page, W] the whole pool, every layer
    layer: jnp.ndarray,  # scalar int32
    block_tables: jnp.ndarray,  # [B, NB] int32
    positions: jnp.ndarray,  # [B] int32
    plan: tuple[jnp.ndarray, jnp.ndarray],  # paged_decode_plan(...)
    *,
    scale: float,
    value_width: int,
    keep: Optional[jnp.ndarray] = None,  # [B, NB, page] int32: topk_select_paged's selection
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A decode step's latent-row write and attention read over the latent
    itself, as ONE Pallas call -> ``(o [B, H, value_width], pool)``.

    :func:`paged_decode_update_attend`'s contract on a pool of one array: the
    pool stays in HBM and comes back aliased, a slot writes one row at
    ``(block_table[b, pos // page], pos % page)`` and reads the pages its table
    names over ``[0, pos]``; slots and blocks off the plan read and WRITE
    nothing.  Scores are ``q . row`` over all ``W`` lanes (the absorbed and the
    rotary part in one contraction; pad lanes are zero on both sides) in
    float32, values are the row's first ``value_width`` lanes.  Not sharded:
    the latent row is shared by every head, so a mesh replicates the pool.

    ``keep`` is the learned sparse attention's selection (static: without it
    the call traces the kernel it always did): a slot attends the keys it
    marks among ``[0, pos]``, each live page still read once and whole; a slot
    whose selection is empty comes back zero.  The call then sits under
    ``attn/sparse_core``, not ``attn/kv_read``."""
    with jax.named_scope("attn/kv_read" if keep is None else "attn/sparse_core"):
        B, H, W = q.shape
        L, P, page, _ = pool.shape
        NB = block_tables.shape[1]
        sub = _packed_rows(pool.dtype)
        if page % sub or W % 128 or value_width % 128 or H % 8:
            raise ValueError(
                f"latent decode kernel needs page % {sub} == 0, row and value widths in whole "
                f"lane tiles and heads % 8 == 0, got page={page}, W={W}, Wv={value_width}, H={H}"
            )
        items, n_items = plan
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        masked = keep is not None
        o, pool = pl.pallas_call(
            functools.partial(
                _latent_decode_kernel if masked else _latent_decode_kernel_unmasked,
                nb=NB, page=page, sub=sub, scale=float(scale), value_width=value_width,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(1,),
                in_specs=[vmem, vmem, hbm] + ([vmem] if masked else []),
                out_specs=[vmem, hbm],
                scratch_shapes=[
                    pltpu.VMEM((2, page, W), pool.dtype),
                    pltpu.VMEM((B, H, 128), jnp.float32),
                    pltpu.VMEM((B, H, 128), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((1,)),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((B, H, value_width), jnp.float32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            ],
            input_output_aliases={7: 1},  # counts the five scalar-prefetch arguments
            compiler_params=pltpu.CompilerParams(
                # queries, the float32 accumulator and running state of every slot,
                # two page buffers; room for the score tile and Pallas's own copies
                vmem_limit_bytes=2 * (B * H * (W * 2 + value_width * 4 + 2 * 128 * 4)) + 4 * page * W * 2 + (16 << 20)
                + (2 * B * NB * page * 4 if masked else 0),
            ),
            name="sparse_latent_decode" if masked else "latent_decode",
            interpret=interpret,
        )(
            items, n_items, block_tables.reshape(-1).astype(jnp.int32),
            positions.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
            q, row_new.astype(jnp.float32)[:, None, :], pool, *([keep] if masked else []),
        )
        return o.astype(q.dtype), pool


@jax.named_scope("attn/kv_read")
def latent_decode_attention(
    q: jnp.ndarray,  # [B, H, W]
    pool_layer: jnp.ndarray,  # [P, page, W] one layer, the step's rows already written
    block_tables: jnp.ndarray,  # [B, NB]
    positions: jnp.ndarray,  # [B]
    *,
    scale: float,
    value_width: int,
    active: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """The plain function the kernel is tested against (and the CPU's path):
    gather each slot's pages, float32 scores over ``[0, pos]``, softmax, values
    from the same rows -> ``[B, H, value_width]``; inactive rows come back zero."""
    P, page, W = pool_layer.shape
    B, NB = block_tables.shape
    rows = jnp.take(pool_layer, jnp.clip(block_tables, 0, P - 1).reshape(-1), axis=0)
    rows = rows.reshape(B, NB * page, W).astype(q.dtype)
    sc = jnp.einsum("bhw,bsw->bhs", q, rows, preferred_element_type=jnp.float32) * scale
    keep = jnp.arange(NB * page)[None, :] <= positions[:, None]
    keep &= jnp.repeat((block_tables >= 0) & (block_tables < P), page, axis=1)
    if active is not None:
        keep &= active[:, None]
    sc = jnp.where(keep[:, None, :], sc, NEG_INF)
    probs = jnp.where(keep[:, None, :], jax.nn.softmax(sc, axis=-1), 0.0).astype(q.dtype)
    return jnp.einsum("bhs,bsw->bhw", probs, rows[..., :value_width])


# ---------------------------------------------------------------------------
# Pallas flash attention
# ---------------------------------------------------------------------------

# The granularity of ADMISSION, not the kernel's tile: a sequence of two or
# more whole blocks of this many positions reaches the kernel
# (:func:`attention`), and the engine steps its prefill buckets by it
# (serving/engine.py ``prefill_shapes``).  The tile a call runs with is sized
# from its shape by :func:`flash_tiles`.
FLASH_BLOCK = 128

_LANES = 128


def _lanes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """``[rows, 128]`` whose lanes all hold their row's value -> ``[rows, n]``:
    whole lane tiles repeat the registers as they are, no cross-lane move."""
    reps, rem = divmod(n, _LANES)
    if rem:  # narrower than a lane tile, or off it (the tests' small tiles)
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if reps == 1 else jnp.tile(x, (1, reps))


def _flash_kernel(
    q_ref,  # [heads, block_q, D]
    k_ref,  # [heads, chunk_kv, D]
    v_ref,  # [heads, chunk_kv, Dv]
    o_ref,  # [heads, block_q, Dv]
    m_scr,  # [heads or 1, block_q, 128] running maximum, a value a row in every lane
    l_scr,  # [heads or 1, block_q, 128] running sum, likewise
    acc_scr,  # [heads or 1, block_q, Dv]
    *,
    kv_len: int,
    block_q: int,
    block_kv: int,
    chunk_kv: int,
    causal: bool,
    window: "Optional[int]",
    scale: "Optional[float]",
):
    """One (group of heads, query tile, kv chunk) program: online softmax over
    ``[block_q, block_kv]`` tiles of scores, a head after another.

    K and V stream through VMEM a CHUNK a grid step (whole for keys up to
    8,192) and the kernel walks the chunk in tiles of ``block_kv`` keys, the
    chunk's remainder as one short last tile.  The tile is the kernel's unit of
    work, chosen by :func:`flash_tiles`; ``FLASH_BLOCK`` is only what
    :func:`attention` admits.  Keys are contracted as they lie (``q . k^T`` as
    an NT matmul, no transpose of the tile).  Tiles wholly above the causal
    diagonal or wholly below the ``window`` band are never visited; tiles
    wholly inside take no mask at all (no iota, compare or select); only tiles
    the diagonal or the band's edge crosses are masked.  The softmax state
    (m, l, acc) lives in VMEM scratch, m and l a value a row repeated over the
    lanes so that every load and store is a whole register; it is kept a head
    each only where it must outlive a grid step (more than one chunk).
    ``o_ref`` is written once, on the last chunk.
    """
    heads, dv = q_ref.shape[0], v_ref.shape[-1]
    qi, ci = pl.program_id(1), pl.program_id(2)
    last_chunk = pl.num_programs(2) - 1
    q0 = qi * block_q  # this tile's first query position
    c0 = ci * chunk_kv  # this chunk's first key position
    whole, tail = divmod(chunk_kv, block_kv)
    edges = causal or window is not None

    # the keys some query of this tile sees are [first_key, end_key); in whole
    # tiles of this chunk that is [lo, hi)
    end_key = jnp.minimum(kv_len, q0 + block_q) if causal else kv_len
    first_key = jnp.maximum(0, q0 - window + 1) if window is not None else 0
    if edges:
        lo = jnp.minimum(jnp.maximum(first_key - c0, 0) // block_kv, whole)
        hi = jnp.minimum((jnp.maximum(end_key - c0, 0) + block_kv - 1) // block_kv, whole)
        # key position minus query position, for a tile whose first key is the first query
        d = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1) - jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
    else:
        lo, hi = 0, whole

    def head(h, carry):
        hs = h if m_scr.shape[0] == heads else 0

        def step(off, size, masked):
            """Fold the ``[block_q, size]`` tile of scores at key ``off`` of the chunk into the state."""
            k = k_ref[h, pl.ds(off, size), :]
            v = v_ref[h, pl.ds(off, size), :]
            # operands stay in their storage dtype (bf16, the MXU's fast path), sums in f32
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            if scale is not None:
                s = s * scale
            if masked:
                rel = q0 - (c0 + off)  # key <= query  <=>  d <= rel
                dd = d[:, :size]
                keep = dd <= rel if causal else None
                if window is not None:
                    band = dd > rel - window
                    keep = band if keep is None else keep & band
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[hs]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, size))
            m_scr[hs] = m_new
            l_scr[hs] = alpha * l_scr[hs] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[hs] = _lanes(alpha, dv) * acc_scr[hs] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )

        @pl.when(ci == 0)
        def _init():
            m_scr[hs] = jnp.full(m_scr.shape[1:], NEG_INF, jnp.float32)
            l_scr[hs] = jnp.zeros(l_scr.shape[1:], jnp.float32)
            acc_scr[hs] = jnp.zeros(acc_scr.shape[1:], jnp.float32)

        def tile(t, carry):
            off = pl.multiple_of(t * block_kv, block_kv)
            if not edges:
                step(off, block_kv, False)
                return carry
            k0 = c0 + off
            on_edge = k0 + block_kv - 1 > q0 if causal else False
            if window is not None:
                on_edge = on_edge | (k0 <= q0 + block_q - 1 - window)
            pl.when(on_edge)(lambda: step(off, block_kv, True))
            pl.when(jnp.logical_not(on_edge))(lambda: step(off, block_kv, False))
            return carry

        jax.lax.fori_loop(lo, hi, tile, 0)
        if tail:  # the chunk's remainder, masked wherever a mask exists: it is the diagonal's tile when it is live
            off = whole * block_kv
            if edges:
                live = (end_key > c0 + off) & (first_key < c0 + chunk_kv)
                pl.when(live)(lambda: step(off, tail, True))
            else:
                step(off, tail, False)

        @pl.when(ci == last_chunk)
        def _finalize():
            o_ref[h] = (acc_scr[hs] / _lanes(jnp.maximum(l_scr[hs], 1e-30), dv)).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def _flash_chunk(Sk: int) -> int:
    """Keys a grid step holds in VMEM, double-buffered by the pipeline: the
    whole sequence up to 8,192, from there the largest chunk under that which
    divides it (in whole admission blocks where the sequence has them)."""
    chunk = min(8192, Sk)
    while Sk % chunk:
        chunk -= FLASH_BLOCK if Sk % FLASH_BLOCK == 0 else 8
    return chunk


# what one program's blocks, state and score tiles may take of VMEM (v5e: 128 MiB a core,
# of which a kernel is given 16 MiB unless it asks); heads are grouped up to this
_FLASH_VMEM_BUDGET = 24 << 20


def _flash_vmem_bytes(block_q: int, block_kv: int, heads: int, chunk_kv: int, D: int, Dv: int, itemsize: int, chunks: int) -> int:
    """VMEM one program needs: its blocks twice (the pipeline's double buffer),
    the softmax state, and the score tile's temporaries (scores, probabilities
    in f32 and the operand's type, the position differences)."""
    D, Dv = -(-D // _LANES) * _LANES, -(-Dv // _LANES) * _LANES  # a narrower head still fills its lanes
    blocks = 2 * heads * itemsize * (block_q * (D + Dv) + chunk_kv * (D + Dv))
    state = (heads if chunks > 1 else 1) * block_q * (2 * _LANES + Dv) * 4
    tile = block_q * block_kv * (4 + 4 + 4 + itemsize)
    return blocks + state + tile


def flash_tiles(
    Sq: int, Sk: int, D: int, Dv: int, heads: int, *, window: Optional[int] = None, itemsize: int = 2
) -> "tuple[int, int, int]":
    """-> (query tile, key tile, heads a program) for a call of this shape.

    Measured on a v5e (``tools/time_prefill.py --flash`` and PR 38's sweep of
    every tile at the benchmark's shapes, PERF.md section 6): a pass over the
    score tile costs about a cycle a register whatever the tile, so what is
    left to save is per tile and per grid step, and the largest tile wins until
    it computes too much above the diagonal.  Keys: 512 a tile (1,024 is
    5-20% slower, 256 10-20%, 128 40-60%), the remainder as a short last tile.
    Queries: the whole sequence as one tile below 1,024 positions (no second
    grid step a head, though nothing above the diagonal is skipped), and from
    there the largest of 512, 384 and 256 that divides it, else 512 with a
    short last tile.  A ``window`` narrower than that caps both, so that a tile
    is not mostly masked scores.  Heads: up to 4 a program (2-18% over one;
    8 and 7 no better), as many as divide the call's heads and fit VMEM.
    """
    block_q = Sq if Sq < 1024 else next((t for t in (512, 384, 256) if Sq % t == 0), 512)
    block_kv = min(512, Sk)
    if window is not None:
        cap = max(FLASH_BLOCK, window // FLASH_BLOCK * FLASH_BLOCK)
        block_q, block_kv = min(block_q, cap), min(block_kv, cap)
    chunk_kv = _flash_chunk(Sk)
    chunks = Sk // chunk_kv
    group = max(
        g for g in (1, 2, 3, 4)
        if heads % g == 0
        and (g == 1 or _flash_vmem_bytes(block_q, block_kv, g, chunk_kv, D, Dv, itemsize, chunks) <= _FLASH_VMEM_BUDGET)
    )
    return block_q, block_kv, group


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_kv", "interpret", "window", "chunk_kv", "scale"
    ),
)
def flash_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, H, Sk, D]
    v: jnp.ndarray,  # [B, H, Sk, Dv]: Dv may differ from D (latent attention: 192 / 128)
    *,
    causal: bool = False,
    block_q: Optional[int] = None,  # default: from the shape (flash_tiles); tests force smaller
    block_kv: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    chunk_kv: Optional[int] = None,  # default: min(8192, Sk); tests force smaller
    scale: Optional[float] = None,  # default: D ** -0.5
) -> jnp.ndarray:
    """Blocked online-softmax attention: bf16 (storage dtype) operands, f32
    scores, sums and accumulator; causal and ``window`` as
    :func:`dot_product_attention` has them.  The tiles and the heads a program
    come from the call's static shape (:func:`flash_tiles`); a sequence the
    tile does not divide runs a short last tile."""
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    if Sq % 8 or Sk % 8 or D % 128 and D != 64 or Dv % 128 and Dv != 64:
        # Mosaic requires (8,128)-tile-aligned loads; reject early with a clear error
        # instead of a deep compiler failure.  Callers pad to a bucket first.
        raise ValueError(
            f"flash_attention needs 8-aligned sequences and head_dim 64/128k, got seq lens "
            f"({Sq},{Sk}), head_dim ({D},{Dv}); pad sequences to a multiple of 8"
        )
    tile_q, tile_kv, heads = flash_tiles(Sq, Sk, D, Dv, B * H, window=window, itemsize=q.dtype.itemsize)
    block_q = min(block_q or tile_q, Sq)
    block_kv = block_kv or tile_kv
    if block_q % 8 or block_kv % 8:
        raise ValueError(f"flash_attention needs 8-aligned tiles, got ({block_q},{block_kv})")
    if chunk_kv is None:
        chunk_kv = _flash_chunk(Sk)
    if Sk % chunk_kv or chunk_kv % 8:
        raise ValueError(f"chunk_kv={chunk_kv} must divide Sk={Sk} and be 8-aligned")
    block_kv = min(block_kv, chunk_kv)
    chunks = Sk // chunk_kv
    if scale is None:
        scale = D ** -0.5
    if math.frexp(scale)[0] == 0.5:
        # a power of two is exact in q's own type; any other scale stays on the f32 scores
        q, scale = q * jnp.asarray(scale, q.dtype), None

    qf = q.reshape(B * H // heads, heads, Sq, D)
    kf = k.reshape(B * H // heads, heads, Sk, D)
    vf = v.reshape(B * H // heads, heads, Sk, Dv)

    kernel = functools.partial(
        _flash_kernel,
        kv_len=Sk,
        block_q=block_q,
        block_kv=block_kv,
        chunk_kv=chunk_kv,
        causal=causal,
        window=window,
        scale=scale,
    )

    def kv_index(g, qi, ci):
        # Clamp dead chunks onto the nearest live one: grid steps whose chunk
        # is entirely past the causal diagonal (or below the window band) run
        # zero kernel iterations, and mapping them to a repeated block index
        # makes the pallas pipeline SKIP the copy — without this, causal
        # prefill streams ~2x the live K/V bytes and windowed prefill loses
        # its O(S*W) traffic property.
        c = ci
        if causal:
            last = (jnp.minimum((qi + 1) * block_q, Sk) - 1) // chunk_kv
            c = jnp.minimum(c, last)
        if window is not None:
            first = jnp.maximum(0, qi * block_q - window + 1) // chunk_kv
            c = jnp.maximum(c, first)
        return (g, 0, c, 0)

    state_heads = heads if chunks > 1 else 1
    out = pl.pallas_call(
        kernel,
        grid=(B * H // heads, pl.cdiv(Sq, block_q), chunks),
        in_specs=[
            pl.BlockSpec((None, heads, block_q, D), lambda g, qi, ci: (g, 0, qi, 0)),
            pl.BlockSpec((None, heads, chunk_kv, D), kv_index),
            pl.BlockSpec((None, heads, chunk_kv, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((None, heads, block_q, Dv), lambda g, qi, ci: (g, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H // heads, heads, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((state_heads, block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((state_heads, block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((state_heads, block_q, Dv), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_flash_vmem_bytes(block_q, block_kv, heads, chunk_kv, D, Dv, q.dtype.itemsize, chunks) + (16 << 20),
        ),
        name="flash_attention",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, Dv)


def sharded_flash_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, H, Sk, D]
    v: jnp.ndarray,
    mesh,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """:func:`flash_attention` under a multi-device mesh.

    Mosaic kernels cannot be partitioned by the SPMD partitioner ("Mosaic
    kernels cannot be automatically partitioned"), so the call is wrapped in
    a ``shard_map``: batch over ``data`` and heads over ``model`` — the layout
    the projections already produce — and every device runs the kernel on its
    own [B/d, H/m, S, D] block with no collective.  A dim that does not
    divide its axis (a one-row prefill on a data-parallel mesh) stays whole
    on every device of that axis: duplicated work, same result, still the
    kernel.  Sequence and head_dim are never split here.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    B, H = q.shape[0], q.shape[1]
    spec = P(
        DATA_AXIS if B % mesh.shape[DATA_AXIS] == 0 else None,
        MODEL_AXIS if H % mesh.shape[MODEL_AXIS] == 0 else None,
        None,
        None,
    )
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal, window=window, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # a dim left whole is computed identically on every device of its
        # axis, which the static replication check cannot prove
        check_vma=False,
    )(q, k, v)


@jax.named_scope("attn/core")
def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,
    q_offset: int | jnp.ndarray = 0,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Dispatch: pallas flash kernel on TPU for long un-masked sequences, jnp otherwise.

    The choice is by shape on a TPU and by platform off it: decode steps
    (Sq==1), offset chunks and padded/masked batches use the jnp path on every
    platform — at those shapes the projections dominate and XLA's fused
    softmax is already bandwidth-optimal — and the CPU (tests, clients) has no
    Mosaic compiler, so the jnp path is the only one that runs there.  A
    kernel-shaped call on a TPU always reaches the kernel: bare on one device,
    through :func:`sharded_flash_attention` under the active multi-device mesh
    (``parallel.sharding.mesh_scope``).  ``window`` (sliding-window attention)
    rides the flash path: the kernel skips kv blocks below the band entirely.
    """
    D, Dv = q.shape[-1], v.shape[-1]
    kernel_shaped = (
        mask is None
        and q.shape[2] >= 2 * FLASH_BLOCK
        and q.shape[2] % FLASH_BLOCK == 0
        and k.shape[2] % FLASH_BLOCK == 0
        and (D == 64 or D % 128 == 0)
        and (Dv == 64 or Dv % 128 == 0)
        and isinstance(q_offset, int)
        and q_offset == 0
    )
    if kernel_shaped and jax.default_backend() == "tpu":
        from ..parallel.sharding import active_mesh

        mesh = active_mesh()
        if mesh is None or mesh.size == 1:
            return flash_attention(q, k, v, causal=causal, window=window, scale=scale)
        return sharded_flash_attention(
            q, k, v, mesh, causal=causal, window=window, scale=scale
        )
    return dot_product_attention(
        q, k, v, causal=causal, mask=mask, q_offset=q_offset, window=window, scale=scale
    )


# ---------------------------------------------------------------------------
# learned sparse attention: a lightning indexer's scores, an exact top-k, and
# attention over what was selected (models/mla_moe.py, ``index_topk``)
# ---------------------------------------------------------------------------
#
# I(t, s) = sum_j w[t, j] * relu(q[t, j] . k[s]) over the indexer's heads; the
# ``topk`` positions s <= t of largest I are the keys query t attends.  Nothing
# here makes a value of [heads, queries, keys] size on a TPU: the scores are
# reduced over the indexer's heads tile by tile as they are made, the selection
# is a threshold found by counting over the reduced [keys, queries] scores, and
# the attention is a flash kernel that takes the selection as a mask.


def sparse_kernel_shaped(Sq: int, Sk: int, D: int, Dv: int) -> bool:
    """Whether a prefill call of this shape takes the two Pallas kernels on a
    TPU (:func:`index_scores_t`, :func:`masked_flash_attention`); anything else
    (the CPU, toy shapes) takes the plain path."""
    return (
        jax.default_backend() == "tpu"
        and Sq % 256 == 0
        and Sk % 512 == 0
        and D % 128 == 0
        and Dv % 128 == 0
    )


def _index_score_kernel(
    starts_ref,  # scalar prefetch [B]: position of each row's first query
    q_ref,  # [Hi, block_q, Di]
    w_ref,  # [Hi, 1, block_q] f32
    k_ref,  # [block_k, Di]
    o_ref,  # [block_k, block_q] f32: keys on the sublanes, queries on the lanes
):
    """One (row, query tile, key tile) program: ``sum over heads of w * relu(k .
    q^T)`` accumulated in registers a head after another, so the per-head
    scores never leave VMEM.  Transposed on purpose: with the queries on the
    lanes a head's weights are one row that broadcasts over the sublanes for
    free, and the selection downstream reduces over the major axis.  A tile
    wholly above the causal diagonal is written as zeros and costs nothing."""
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_k, block_q = o_ref.shape
    last_query = starts_ref[b] + (qi + 1) * block_q - 1
    live = ki * block_k <= last_query

    @pl.when(live)
    def _score():
        k = k_ref[...]

        def head(h, acc):
            s = jax.lax.dot_general(k, q_ref[h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            return acc + jnp.maximum(s, 0.0) * w_ref[h]

        o_ref[...] = jax.lax.fori_loop(0, q_ref.shape[0], head, jnp.zeros(o_ref.shape, jnp.float32))

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


@jax.named_scope("attn/index_score")
def index_scores_t(
    q: jnp.ndarray,  # [B, C, Hi, Di] index queries (rotated)
    w: jnp.ndarray,  # [B, C, Hi] f32 head weights (scaled)
    k: jnp.ndarray,  # [B, S, Di] index keys of each row's logical view
    starts: jnp.ndarray,  # [B] int32: position of each row's first query
    *,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """The indexer's scores, TRANSPOSED: ``[B, S, C]`` float32 (Pallas; the
    plain form is :func:`index_scores`).  Entries above the causal diagonal
    are unspecified (whole dead tiles are zeros): the selection masks them."""
    B, C, Hi, Di = q.shape
    S = k.shape[1]
    block_q, block_k = min(block_q, C), min(block_k, S)
    if C % block_q or S % block_k:
        raise ValueError(f"index_scores_t needs queries and keys in whole tiles, got C={C}, S={S}")
    qt = q.transpose(0, 2, 1, 3)  # [B, Hi, C, Di]
    wt = w.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]  # [B, Hi, 1, C]
    return pl.pallas_call(
        _index_score_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # keys innermost: a query tile's 64 heads stay in VMEM while its key tiles stream by
            grid=(B, C // block_q, S // block_k),
            in_specs=[
                pl.BlockSpec((None, Hi, block_q, Di), lambda b, qi, ki, st: (b, 0, qi, 0)),
                pl.BlockSpec((None, Hi, 1, block_q), lambda b, qi, ki, st: (b, 0, 0, qi)),
                pl.BlockSpec((None, block_k, Di), lambda b, qi, ki, st: (b, ki, 0)),
            ],
            out_specs=pl.BlockSpec((None, block_k, block_q), lambda b, qi, ki, st: (b, ki, qi)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=4 * Hi * block_q * Di * q.dtype.itemsize + 8 * block_q * block_k * 4 + (16 << 20),
        ),
        name="index_scores",
        interpret=interpret,
    )(starts.astype(jnp.int32), qt, wt, k)


@jax.named_scope("attn/index_score")
def index_scores(q: jnp.ndarray, w: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """The plain form -> ``[B, C, S]`` float32: every head's scores at once
    (``[B, C, Hi, S]``), which a decode step (``C`` = 1) and the CPU can
    afford."""
    s = jnp.einsum("bchd,bsd->bchs", q, k, preferred_element_type=jnp.float32)
    return jnp.einsum("bchs,bch->bcs", jnp.maximum(s, 0.0), w.astype(jnp.float32))


def _sortable(scores: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 that orders as the floats do (-0.0 just under +0.0)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(0x80000000)


@jax.named_scope("attn/select")
def topk_mask(scores: jnp.ndarray, k: int, ok: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Exact top-``k`` along ``axis`` as a mask: True at the ``k`` largest
    ``scores`` among the entries ``ok`` marks (all of them where fewer are
    marked), ties at the k-th value going to the lowest index, as
    ``jax.lax.top_k`` breaks them.

    No sort: the k-th largest value is found by counting, one bit of the
    score a pass (32 passes of a compare and a sum over the array; a sort of
    16,384 keys a query is an order of magnitude more traffic).  The tie rule
    needs a running count along ``axis`` and is computed only in a call where
    some k-th value is in fact shared."""
    axis = axis % scores.ndim
    if scores.shape[axis] <= k:
        return ok
    key = jnp.where(ok, _sortable(scores), jnp.uint32(0))

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand, axis=axis, keepdims=True, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    shape = tuple(1 if a == axis else n for a, n in enumerate(scores.shape))
    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(shape, jnp.uint32))  # 0 where fewer than k are marked
    above = (key >= kth) & ok
    shared = jnp.sum(above, axis=axis, keepdims=True, dtype=jnp.int32) > k

    def with_ties(_):
        gt = (key > kth) & ok
        eq = (key == kth) & ok
        room = k - jnp.sum(gt, axis=axis, keepdims=True, dtype=jnp.int32)
        return gt | (eq & (jnp.cumsum(eq, axis=axis, dtype=jnp.int32) <= room))

    return jax.lax.cond(jnp.any(shared), with_ties, lambda _: above, None)


def _masked_flash_kernel(
    live_ref,  # scalar prefetch [B]: keys at or past it are dead for every query of the row
    q_ref,  # [heads, block_q, D]
    kt_ref,  # [heads, D, chunk_kv]: the keys TRANSPOSED, positions on the lanes
    v_ref,  # [heads, chunk_kv, Dv]
    mask_ref,  # [block_q, chunk_kv] int8, shared by every head
    o_ref,  # [heads, block_q, Dv]
    m_scr,  # [heads, block_q, 128]
    l_scr,  # [heads, block_q, 128]
    acc_scr,  # [heads, block_q, Dv]
    *,
    groups: int,
    block_kv: int,
    chunk_kv: int,
    scale: float,
):
    """:func:`_flash_kernel` under an arbitrary mask: a tile of the mask is
    loaded once and serves every head of the program; tiles at or past the
    row's live keys are never visited.  A query may have no kept key in the
    tiles seen so far (its selection lies further on), so the probabilities
    are zeroed under the mask and not only the scores.  The keys come
    transposed (``q . kT`` is a plain matmul): that is how a view built a page
    at a time lies in memory (models/mla_moe.py), and no copy re-lays it."""
    heads, dv = q_ref.shape[0], v_ref.shape[-1]
    b = pl.program_id(0) // groups
    ci = pl.program_id(2)
    c0 = ci * chunk_kv
    n_tiles = jnp.clip((live_ref[b] - c0 + block_kv - 1) // block_kv, 0, chunk_kv // block_kv)

    @pl.when(ci == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def tile(t, carry):
        off = pl.multiple_of(t * block_kv, block_kv)
        keep = mask_ref[:, pl.ds(off, block_kv)].astype(jnp.int32) != 0
        for h in range(heads):
            v = v_ref[h, pl.ds(off, block_kv), :]
            s = jnp.dot(q_ref[h], kt_ref[h, :, pl.ds(off, block_kv)], preferred_element_type=jnp.float32)
            s = jnp.where(keep, s * scale, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(keep, jnp.exp(s - _lanes(m_new, block_kv)), 0.0)
            m_scr[h] = m_new
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = _lanes(alpha, dv) * acc_scr[h] + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_tiles, tile, 0)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _finalize():
        for h in range(heads):
            o_ref[h] = (acc_scr[h] / _lanes(jnp.maximum(l_scr[h], 1e-30), dv)).astype(o_ref.dtype)


def masked_flash_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    kt: jnp.ndarray,  # [B, H, D, Sk]: the keys transposed
    v: jnp.ndarray,  # [B, H, Sk, Dv]
    mask: jnp.ndarray,  # [B, Sq, Sk] int8 (non-zero = keep), the same for every head
    live: jnp.ndarray,  # [B] int32: no key at or past it is kept by any query of the row
    *,
    scale: float,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    chunk_kv: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked online-softmax attention over the pairs ``mask`` keeps (causality
    included: the kernel adds none): bf16 operands, f32 scores and sums, as
    :func:`flash_attention`; the ``[Sq, Sk]`` mask is int8 in HBM and no value
    of ``[heads, Sq, Sk]`` size exists.  A query that keeps nothing reads 0."""
    B, H, Sq, D = q.shape
    Sk, Dv = kt.shape[3], v.shape[3]
    # the causal rule's key tile and heads a program; a whole chunk of queries (up to 1,024) as ONE query
    # tile: nothing above a diagonal is skipped here, and every further query tile streams the keys again
    _, tile_kv, heads = flash_tiles(Sq, Sk, D, Dv, H, itemsize=q.dtype.itemsize)
    tile_q = next(t for t in (1024, 512, 256, Sq) if Sq % t == 0)
    block_q, block_kv = min(block_q or tile_q, Sq), min(block_kv or tile_kv, Sk)
    chunk_kv = chunk_kv or _flash_chunk(Sk)
    if Sq % block_q or chunk_kv % block_kv or Sk % chunk_kv:
        raise ValueError(f"masked_flash_attention needs whole tiles, got Sq={Sq}/{block_q}, Sk={Sk}/{chunk_kv}/{block_kv}")
    groups = H // heads
    qf, kf, vf = (x.reshape(B * groups, heads, *x.shape[2:]) for x in (q, kt, v))

    def last_chunk(g, live_ref):  # a chunk past the live keys repeats the last live one: no copy
        return jnp.maximum(live_ref[g // groups] - 1, 0) // chunk_kv

    def k_index(g, qi, ci, live_ref):
        return (g, 0, 0, jnp.minimum(ci, last_chunk(g, live_ref)))

    def v_index(g, qi, ci, live_ref):
        return (g, 0, jnp.minimum(ci, last_chunk(g, live_ref)), 0)

    def mask_index(g, qi, ci, live_ref):
        return (g // groups, qi, jnp.minimum(ci, last_chunk(g, live_ref)))

    out = pl.pallas_call(
        functools.partial(_masked_flash_kernel, groups=groups, block_kv=block_kv, chunk_kv=chunk_kv, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * groups, Sq // block_q, Sk // chunk_kv),
            in_specs=[
                pl.BlockSpec((None, heads, block_q, D), lambda g, qi, ci, lv: (g, 0, qi, 0)),
                pl.BlockSpec((None, heads, D, chunk_kv), k_index),
                pl.BlockSpec((None, heads, chunk_kv, Dv), v_index),
                pl.BlockSpec((None, block_q, chunk_kv), mask_index),
            ],
            out_specs=pl.BlockSpec((None, heads, block_q, Dv), lambda g, qi, ci, lv: (g, 0, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
                pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
                pltpu.VMEM((heads, block_q, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * groups, heads, Sq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_flash_vmem_bytes(block_q, block_kv, heads, chunk_kv, D, Dv, q.dtype.itemsize, 2)
            + 2 * block_q * chunk_kv + (16 << 20),
        ),
        name="masked_flash_attention",
        interpret=interpret,
    )(live.astype(jnp.int32), qf, kf, vf, mask)
    return out.reshape(B, H, Sq, Dv)


@jax.named_scope("attn/sparse_core")
def sparse_attention(q, kt, v, keep: jnp.ndarray, live: jnp.ndarray, *, scale: float) -> jnp.ndarray:
    """Attention of ``q`` [B,H,Sq,D] over keys ``kt`` [B,H,D,Sk] (transposed)
    and values ``v`` [B,H,Sk,Dv] at the pairs ``keep`` [B,Sq,Sk] marks (the
    selection, causality included): the masked flash kernel where the call is
    kernel-shaped on a TPU, the plain masked softmax elsewhere (rows that keep
    nothing come back zero on both)."""
    if sparse_kernel_shaped(q.shape[2], v.shape[2], q.shape[3], v.shape[3]):
        return masked_flash_attention(q, kt, v, keep.astype(jnp.int8), live, scale=scale)
    o = dot_product_attention(q, kt.swapaxes(2, 3), v, mask=keep[:, None], scale=scale)
    return jnp.where(keep.any(-1)[:, None, :, None], o, 0.0).astype(o.dtype)


def select_widths(S: int, topk: int, tile: int) -> tuple[int, ...]:
    """The view lengths a prefill's selection can run over, ascending, the last
    one ``S``: the multiples of a step that lie above ``topk`` (a view within
    ``topk`` keeps everything and counts nothing), the step the smallest ``tile
    * 2**i`` that leaves at most 7 of them (2,048 ... 16,384 by 2,048 at
    ``S`` 16,384, ``topk`` 2,048 and tiles of 512)."""
    step = tile
    while True:
        widths = sorted({min(S, n * step) for n in range(topk // step + 1, -(-S // step) + 1)})
        if len(widths) <= 7:
            return tuple(widths)
        step *= 2


def sparse_select(
    q_idx: jnp.ndarray,  # [B, C, Hi, Di]
    w_idx: jnp.ndarray,  # [B, C, Hi] f32
    k_idx: jnp.ndarray,  # [B, S, Di]
    qpos: jnp.ndarray,  # [B, C] int32 position of each query
    ok: jnp.ndarray,  # [B, C, S] bool: the keys a query may attend at all (causal, written, real query)
    topk: int,
    live: jnp.ndarray,  # [B] int32: ``ok`` marks no key at or past it for any query of the row
):
    """-> (keep [B, C, S] bool, pairs scanned): for each query the ``topk`` keys
    of largest index score among those ``ok`` marks (all of them where there
    are fewer), and the (query, position) pairs the counting ran over.

    The scores and the counting follow the keys that are live, in static steps
    (:func:`select_widths`): of the view's ``S`` positions only the first
    ``width >= max(live)`` are scored and counted, one branch of a conditional a
    width, and where ``max(live) <= topk`` nothing is, since every ``ok`` key is
    kept.  The positions cut off are not ``ok``: they entered the counts as
    zeros and were never kept, so ``keep`` is the whole view's, bit for bit."""
    B, C, Hi, Di = q_idx.shape
    S = k_idx.shape[1]
    if S <= topk:
        return ok, jnp.int32(0)
    kernel = sparse_kernel_shaped(C, S, Di, Di)
    widths = select_widths(S, topk, 512 if kernel else 8)  # the index kernel's key tile; a sublane tile

    def over(width):
        def branch(q_idx, w_idx, k_idx, qpos, ok):
            k, ok = k_idx[:, :width], ok[..., :width]
            if kernel:
                scores = index_scores_t(q_idx, w_idx, k, qpos[:, 0])  # [B, width, C]: the selection reduces over the major axis
                with jax.named_scope("attn/select"):
                    ok_t = ok.transpose(0, 2, 1)
                keep_t = topk_mask(scores, topk, ok_t, axis=1)
                with jax.named_scope("attn/select"):
                    keep = keep_t.transpose(0, 2, 1)
            else:
                keep = topk_mask(index_scores(q_idx, w_idx, k), topk, ok, axis=2)
            with jax.named_scope("attn/select"):
                return jnp.pad(keep, ((0, 0), (0, 0), (0, S - width)))

        return branch

    # branch 0 keeps every ``ok`` key; branch i counts over widths[i - 1], the first that holds every live key
    n = jnp.sum(jnp.max(live) > jnp.asarray((topk,) + widths[:-1], jnp.int32))
    keep = jax.lax.switch(n, [lambda *args: args[-1]] + [over(w) for w in widths], q_idx, w_idx, k_idx, qpos, ok)
    return keep, jnp.asarray((0,) + widths, jnp.int32)[n] * (B * C)


def sparse_decode_select(scores: jnp.ndarray, ok: jnp.ndarray, topk: int):
    """A decode step's selection, as POSITIONS: ``scores``/``ok`` [B, S] ->
    (idx [B, K] int32, picked [B, K] bool), ``K = min(topk, S)``; where fewer
    than ``K`` keys are ``ok`` the rest of ``idx`` is marked not picked."""
    with jax.named_scope("attn/select"):
        vals, idx = jax.lax.top_k(jnp.where(ok, scores, -jnp.inf), min(topk, scores.shape[-1]))
        return idx.astype(jnp.int32), vals > -jnp.inf


@jax.named_scope("attn/sparse_core")
def sparse_latent_decode_attention(
    q: jnp.ndarray,  # [B, H, W] absorbed query | rotary query | zeros
    pool: jnp.ndarray,  # [L, P, page, W] the whole latent pool, the step's rows written
    layer: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, NB]
    idx: jnp.ndarray,  # [B, K] selected positions
    picked: jnp.ndarray,  # [B, K] bool
    *,
    scale: float,
    value_width: int,
) -> jnp.ndarray:
    """:func:`latent_decode_attention` over SELECTED rows: the ``K`` rows each
    slot's indexer picked are gathered from the pool where they lie (by layer,
    page and offset: no page is read whole, no layer of the pool copied) and
    attended in the absorbed form -> ``[B, H, value_width]``."""
    L, P, page, W = pool.shape
    phys = jnp.take_along_axis(block_tables, idx // page, axis=1)
    picked = picked & (phys >= 0) & (phys < P)
    rows = pool[layer, jnp.clip(phys, 0, P - 1), idx % page].astype(q.dtype)  # [B, K, W]
    sc = jnp.einsum("bhw,bkw->bhk", q, rows, preferred_element_type=jnp.float32) * scale
    sc = jnp.where(picked[:, None, :], sc, NEG_INF)
    probs = jnp.where(picked[:, None, :], jax.nn.softmax(sc, axis=-1), 0.0).astype(q.dtype)
    return jnp.einsum("bhk,bkw->bhw", probs, rows[..., :value_width])


# ---------------------------------------------------------------------------
# The sparse decode step over the plan's pages: index scores and an exact
# selection by counting, as Pallas calls; the attention is the latent decode
# kernel under the selection (``latent_decode_update_attend(..., keep=...)``)
# ---------------------------------------------------------------------------


def _paged_index_score_kernel(
    # scalar prefetch (SMEM)
    items_ref, n_ref, bt_ref, pos_ref, layer_ref,
    # inputs
    q_ref,  # [B, Hi, Di] VMEM: the step's index queries per slot
    w_ref,  # [B, Hi, 128] f32 VMEM: a head's weight on every lane
    new_ref,  # [B, 1, Di] f32 VMEM: the step's index key per slot
    pool_hbm,  # [L, P, page, Di] HBM: never loaded whole
    # outputs
    s_ref,  # [B, NB, page] f32 VMEM: -inf wherever no key can be selected
    pool_out,  # the same buffer as pool_hbm (input_output_aliases)
    # scratch
    buf,  # [2, page, Di] double-buffered page
    rsem,  # DMA [2 (buffer)]
    wsem,  # DMA [1]
    *,
    nb: int,
    page: int,
    sub: int,
):
    """:func:`_walk_plan_pages` over the pool of index keys (the step's own key
    patched into its page): a page is scored against the slot's 64 index
    queries in one matmul and reduced over the heads in VMEM; one ``[1, page]``
    row of scores leaves the kernel."""
    s_ref[...] = jnp.full(s_ref.shape, -jnp.inf, jnp.float32)

    def score(slot, j, pos, s):
        q = q_ref[slot]  # [Hi, Di]
        sc = jax.lax.dot_general(
            q, buf[s].astype(q.dtype), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Hi, page]
        row = jnp.sum(jnp.maximum(sc, 0.0) * w_ref[slot][:, :1], axis=0, keepdims=True)  # [1, page]
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s_ref[slot, pl.ds(j, 1), :] = jnp.where(kpos <= pos, row, -jnp.inf)

    _walk_plan_pages(items_ref, n_ref[0], bt_ref, pos_ref, layer_ref[0], new_ref, pool_hbm, pool_out, buf, rsem, wsem,
                     nb=nb, page=page, sub=sub, visit=score)


@jax.named_scope("attn/index_score")
def paged_index_scores(
    q: jnp.ndarray,  # [B, Hi, Di] the step's index queries (rotated)
    w: jnp.ndarray,  # [B, Hi] f32 head weights (scaled)
    key_new: jnp.ndarray,  # [B, Di] the step's index key per slot
    pool: jnp.ndarray,  # [L, P, page, Di] the whole pool of index keys, every layer
    layer: jnp.ndarray,  # scalar int32
    block_tables: jnp.ndarray,  # [B, NB] int32
    positions: jnp.ndarray,  # [B] int32
    plan: tuple[jnp.ndarray, jnp.ndarray],  # paged_decode_plan(...)
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A decode step's index-key write and index scores over the pages on the
    plan, as ONE Pallas call -> ``(scores [B, NB, page] f32, pool)``.

    :func:`latent_decode_update_attend`'s contract on the second pool: it stays
    in HBM and comes back aliased, a slot writes its key at ``(block_table[b,
    pos // page], pos % page)`` and reads the pages its table names over ``[0,
    pos]``, the step's own key among them; slots and blocks off the plan read
    and write nothing.  ``scores[b, j, o]`` is :func:`index_scores` of position
    ``j * page + o`` (``sum_h w_h relu(k . q_h)``, the per-head products in
    float32 from bfloat16 operands, summed over the heads in float32), and
    ``-inf`` at every position the slot cannot select: past ``pos``, on a block
    without a page, of a slot that is not active.  Neither a copy of the keys
    nor a per-head score leaves VMEM."""
    B, Hi, Di = q.shape
    L, P, page, _ = pool.shape
    NB = block_tables.shape[1]
    sub = _packed_rows(pool.dtype)
    if page % sub or Di % 128 or Hi % 8:
        raise ValueError(
            f"index score kernel needs page % {sub} == 0, keys in whole lane tiles and heads % 8 == 0, "
            f"got page={page}, Di={Di}, Hi={Hi}"
        )
    items, n_items = plan
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_paged_index_score_kernel, nb=NB, page=page, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[vmem, vmem, vmem, hbm],
            out_specs=[vmem, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, page, Di), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, NB, page), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        input_output_aliases={8: 1},  # counts the five scalar-prefetch arguments
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * B * (NB * page * 4 + Hi * (Di * 2 + 128 * 4)) + 4 * page * Di * 2 + (16 << 20),
        ),
        name="paged_index_scores",
        interpret=interpret,
    )(
        items, n_items, block_tables.reshape(-1).astype(jnp.int32),
        positions.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
        q, jnp.broadcast_to(w.astype(jnp.float32)[:, :, None], (B, Hi, 128)),
        key_new.astype(jnp.float32)[:, None, :], pool,
    )


_INT_MIN = -(2**31)


def _topk_select_kernel(
    active_ref,  # scalar prefetch [B] int32
    s_ref,  # [NB, page] f32: one slot's scores, -inf where nothing can be selected
    keep_ref,  # [NB, page] int32
    *,
    k: int,
):
    """One slot's exact top-``k``, by counting (:func:`topk_mask`'s method on a
    slot's scores in VMEM): the k-th largest value a bit a pass, 32 compares
    and sums over the ``[NB, page]`` tile; then, among the entries equal to it,
    the position up to which they fit, 14 more of the same, so that ties go to
    the lowest positions as ``jax.lax.top_k`` gives them.  Nothing is sorted
    and a slot that is not active costs a store of zeros."""
    nb, page = s_ref.shape
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(live))
    def _idle():
        keep_ref[...] = jnp.zeros(keep_ref.shape, jnp.int32)

    @pl.when(live)
    def _select():
        s = s_ref[...]
        ok = s > -jnp.inf
        bits = pltpu.bitcast(s, jnp.int32)
        # an int32 that orders as the floats do (-0.0 just under +0.0, as _sortable); below them all where not ok
        key = jnp.where(ok, jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits), jnp.int32(_INT_MIN))

        def count(m):
            return jnp.sum(m.astype(jnp.int32), keepdims=True)  # [1, 1]

        def value_bit(i, prefix):  # the k-th largest key, built from its top bit down, in the offset (unsigned) domain
            cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
            return jnp.where(count(key >= (cand ^ jnp.int32(_INT_MIN))) >= k, cand, prefix)

        kth = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((1, 1), jnp.int32)) ^ jnp.int32(_INT_MIN)
        above = key > kth  # never a key that is not ok: kth is at least theirs
        tied = (key == kth) & ok
        room = k - count(above)
        at = jax.lax.broadcasted_iota(jnp.int32, (nb, page), 0) * page + jax.lax.broadcasted_iota(jnp.int32, (nb, page), 1)
        n_bits = max(1, (nb * page - 1).bit_length())

        def position_bit(i, prefix):  # the largest position below which fewer than `room` of the tied lie
            cand = prefix | jnp.left_shift(jnp.int32(1), n_bits - 1 - i)
            return jnp.where(count(tied & (at < cand)) < room, cand, prefix)

        last = jax.lax.fori_loop(0, n_bits, position_bit, jnp.zeros((1, 1), jnp.int32))
        keep_ref[...] = (above | (tied & (at <= last))).astype(jnp.int32)


@jax.named_scope("attn/select")
def topk_select_paged(
    scores: jnp.ndarray,  # [B, NB, page] f32 from paged_index_scores: -inf = cannot be selected
    active: jnp.ndarray,  # [B] bool
    topk: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """A decode step's selection as a mask over the slot's pages -> ``keep [B,
    NB, page]`` int32: non-zero at the ``topk`` largest finite ``scores`` of a
    slot (all of them where it has fewer), ties at the k-th value going to the
    lowest positions: the set :func:`sparse_decode_select` returns, key for
    key.  Exact and not a sort (:func:`_topk_select_kernel`); a slot that is
    not active keeps nothing and is not worked on."""
    B, NB, page = scores.shape
    return pl.pallas_call(
        functools.partial(_topk_select_kernel, k=int(topk)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, NB, page), lambda b, act: (b, 0, 0))],
            out_specs=pl.BlockSpec((None, NB, page), lambda b, act: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, NB, page), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="topk_select",
        interpret=interpret,
    )(active.astype(jnp.int32), scores)
