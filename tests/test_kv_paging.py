"""Paged, prefix-shared KV memory plane (docs/KV_PAGING.md).

Four layers of evidence, all CPU so tier-1 gates the tentpole without
hardware:

- allocator unit + property tests (host-side page bookkeeping: alloc/free,
  COW refcounts, LRU eviction under the byte budget, out-of-pages behavior);
- op-level: the block-table gather decode attention equals plain masked
  attention over the same rows, including fp8 pools and shuffled page
  placement;
- engine-level: what the paged engine serves for greedy + sampled traffic,
  ragged lengths, fp8 KV, and the chunked-prefill path is what
  ``llama.forward`` says of the same sequences;
- the serving contract: prefix sharing survives a sharer freeing mid-decode,
  crash-only restart rebuilds a clean pool, and the scheduler sheds on KV
  pressure with its own 429 reason.
"""

import asyncio
import os
import random
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops.attention import (
    gqa_dot_product_attention,
    paged_gqa_decode_attention,
)
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine
from django_assistant_bot_tpu.serving.kv_pool import PageAllocator
from django_assistant_bot_tpu.serving.scheduler import (
    RequestScheduler,
    SchedulerConfig,
    SchedulerRejected,
)
from paged import Paged


# --------------------------------------------------------------- allocator
def test_allocator_alloc_free_roundtrip():
    al = PageAllocator(8, 64)
    a = al.alloc(3)
    b = al.alloc(5)
    assert sorted(a + b) == list(range(8))
    assert al.alloc(1) is None  # exhausted -> None, nothing allocated
    al.decref(a)
    c = al.alloc(3)
    assert sorted(c) == sorted(a)  # freed pages come back
    assert al.pages_free == 0 + (8 - 5 - 3)


def test_allocator_refcounts_shared_pages_survive_owner():
    al = PageAllocator(8, 64, max_shared_entries=4)
    pages = al.alloc(2)
    assert al.register([1] * 100, 100, pages)  # 100 tokens -> 2 pages of 64
    al.decref(pages)  # owner frees; registry still holds its refs
    assert al.pages_free == 6
    hit = al.lookup([1] * 120, 100)
    assert hit is not None and hit.length == 100 and hit.full_pages == 1
    # evicting the entry releases the last refs
    al.reset()
    assert al.pages_free == 8


def test_allocator_lru_eviction_under_byte_budget():
    # page_bytes=10, budget 25 -> at most 2 single-page entries fit
    al = PageAllocator(
        8, 64, page_bytes=10, max_shared_bytes=25, max_shared_entries=8,
        min_prefix_tokens=1,
    )
    owners = []
    for i in range(3):
        p = al.alloc(1)
        owners.append(p)
        assert al.register([i] * 40, 40, p)
    assert al.evictions == 1  # the first entry LRU-evicted past the budget
    assert al.lookup([0] * 50, 40) is None
    assert al.lookup([2] * 50, 40) is not None


def test_allocator_on_demand_eviction_feeds_alloc():
    al = PageAllocator(4, 64, max_shared_entries=8, min_prefix_tokens=1)
    p = al.alloc(2)
    assert al.register([9] * 80, 80, p)
    al.decref(p)  # only the registry holds them now
    assert al.pages_free == 2
    assert al.available() == 4  # 2 free + 2 evictable
    got = al.alloc(4)  # forces the entry out
    assert got is not None and len(got) == 4
    assert al.evictions == 1
    assert al.lookup([9] * 90, 80) is None


def test_allocator_eviction_during_alloc_spares_pinned_pages():
    """The admit sequence pins a hit's pages (incref) BEFORE alloc: alloc's
    on-demand eviction may then drop the entry, but the pinned pages must
    neither free nor be handed back as 'fresh' pages of the same request."""
    al = PageAllocator(6, 64, max_shared_entries=4, min_prefix_tokens=1)
    p = al.alloc(2)
    al.register([7] * 80, 80, p)
    al.decref(p)  # registry-only-held now
    held = al.alloc(3)  # free list down to 1
    hit = al.lookup([7] * 100, 80)
    al.incref(hit.pages)  # the pin
    # needs 2, free holds 1: eviction fires but the PINNED pages survive it —
    # they are neither freed nor handed back, so the alloc correctly fails
    # (the engine then falls back to a full prefill without the hit)
    assert al.alloc(2) is None
    assert al.evictions == 1
    with al._lock:
        assert all(page in al._refs for page in hit.pages)
    al.decref(list(hit.pages))  # unpin: NOW the pages free
    got = al.alloc(2)
    assert got is not None and set(got) >= set(hit.pages) - set(held)
    al.decref(got)
    al.decref(held)
    assert al.pages_free == 6


def test_engine_falls_back_to_full_prefill_when_hit_blocks_alloc():
    """Engine corner: the hit's pinned pages are exactly what eviction would
    need — admission must drop the hit and run a full prefill (correct
    output, no wedged queue head) instead of waiting forever."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(13))
    rng = np.random.default_rng(14)
    prefix = rng.integers(1, 255, 150).tolist()  # 3 pages of 64 (2 full + 1)
    p_a = prefix + rng.integers(1, 255, 20).tolist()  # 178-token demand: 3 pages
    p_b = prefix + rng.integers(1, 255, 60).tolist()  # 218-token demand: 4 pages

    def run(prefix_cache):
        eng = GenerationEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=256,
            kv_page_size=64, prefix_cache_size=prefix_cache,
            prefix_min_tokens=16, kv_pages=4,
        ).start()
        try:
            ra = eng.submit(
                p_a, max_tokens=8, temperature=0.0, prefix_len=len(prefix)
            ).result(timeout=300)
            rb = eng.submit(
                p_b, max_tokens=8, temperature=0.0, prefix_len=len(prefix)
            ).result(timeout=300)
            return (ra.token_ids, rb.token_ids), eng.kv_stats()
        finally:
            eng.stop()

    ref, _ = run(0)
    got, stats = run(8)
    assert got == ref
    # the hit could not be used (4-page demand vs 1 free + its own pinned
    # pages): the registry entry was evicted to make room for a full prefill
    assert stats["kv_evictions"] >= 1


def test_allocator_out_of_pages_is_atomic():
    al = PageAllocator(4, 64)
    held = al.alloc(3)
    assert al.alloc(2) is None
    assert al.pages_free == 1  # the failed alloc took nothing
    al.decref(held)


def test_allocator_longest_prefix_match_and_lru_touch():
    al = PageAllocator(16, 4, max_shared_entries=8, min_prefix_tokens=1)
    short = al.alloc(1)
    al.register([1, 2, 3], 3, short)
    long_pages = al.alloc(2)
    al.register([1, 2, 3, 4, 5], 5, long_pages)
    hit = al.lookup([1, 2, 3, 4, 5, 6, 7], 5)
    assert hit.length == 5  # longest match wins
    hit = al.lookup([1, 2, 3, 9, 9], 3)
    assert hit.length == 3


def test_allocator_property_fuzz_invariants():
    """Pinned-seed fuzz: random alloc/decref/register/lookup/evict traffic
    must keep the bookkeeping invariants — no page both free and referenced,
    free + used == total, failed allocs change nothing.  The seed is
    overridable (DABT_KV_FUZZ_SEED) so CI can pin it."""
    seed = int(os.environ.get("DABT_KV_FUZZ_SEED", "0"))
    rng = random.Random(seed)
    al = PageAllocator(
        32, 16, page_bytes=7, max_shared_bytes=70, max_shared_entries=5,
        min_prefix_tokens=1,
    )
    held = []  # lists of pages we hold refs on
    for _step in range(2000):
        op = rng.random()
        if op < 0.4:
            n = rng.randint(1, 6)
            before = al.pages_free
            got = al.alloc(n)
            if got is None:
                assert al.pages_free < n  # truly couldn't satisfy; took nothing
            else:
                held.append(got)
        elif op < 0.7 and held:
            al.decref(held.pop(rng.randrange(len(held))))
        elif op < 0.85 and held:
            pages = held[rng.randrange(len(held))]
            toks = rng.randrange(1 << 20)
            length = len(pages) * al.page_size - rng.randint(0, al.page_size - 1)
            al.register([toks] * length, length, pages)
        else:
            al.lookup([rng.randrange(4)] * rng.randint(1, 40), 8)
        # invariants
        free = al.pages_free
        with al._lock:
            refd = set(al._refs)
            free_set = set(al._free)
        assert not (refd & free_set)
        assert len(free_set) == free
        assert len(refd) + free == al.n_pages
        for pages in held:
            for p in pages:
                assert p in refd
    for pages in held:
        al.decref(pages)


# ---------------------------------------------------------------- op level
@pytest.mark.parametrize("dtype", [None, jnp.float8_e4m3fn])
def test_paged_attention_matches_masked_gqa(dtype):
    """Rows cut into pages at shuffled physical places -> the output of plain
    masked attention over the rows (the pool's own dtype upcast whole), to
    f32 reduction order: 2e-6."""
    rng = np.random.default_rng(1)
    B, H, KH, S, D, page = 5, 8, 2, 256, 16, 64
    NB = S // page
    q = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32))
    k = rng.normal(size=(B, KH, S, D)).astype(np.float32)
    v = rng.normal(size=(B, KH, S, D)).astype(np.float32)
    positions = jnp.asarray([0, 63, 64, 130, 255], jnp.int32)
    kd = jnp.asarray(k).astype(dtype) if dtype else jnp.asarray(k)
    vd = jnp.asarray(v).astype(dtype) if dtype else jnp.asarray(v)
    mask = (jnp.arange(S)[None, :] <= positions[:, None])[:, None, None, :]
    plain = gqa_dot_product_attention(
        q, kd.astype(jnp.float32), vd.astype(jnp.float32), mask=mask
    )

    # scatter the rows' pages into a shuffled pool; extra pages hold garbage
    P = B * NB + 3
    perm = rng.permutation(B * NB)
    pool_k = rng.normal(size=(P, KH, page, D)).astype(np.float32)
    pool_v = rng.normal(size=(P, KH, page, D)).astype(np.float32)
    bt = np.full((B, NB), P, np.int32)
    for b in range(B):
        for j in range(NB):
            phys = int(perm[b * NB + j])
            pool_k[phys] = k[b, :, j * page : (j + 1) * page]
            pool_v[phys] = v[b, :, j * page : (j + 1) * page]
            bt[b, j] = phys
    pk = jnp.asarray(pool_k).astype(dtype) if dtype else jnp.asarray(pool_k)
    pv = jnp.asarray(pool_v).astype(dtype) if dtype else jnp.asarray(pool_v)
    paged = paged_gqa_decode_attention(
        q, pk, pv, jnp.asarray(bt), positions
    )
    np.testing.assert_allclose(np.asarray(plain), np.asarray(paged), atol=2e-6)


def test_paged_attention_masks_unallocated_blocks():
    """Logical blocks past a row's allocation gather garbage (clamped page 0)
    — NaN poison there must never reach the output."""
    rng = np.random.default_rng(2)
    B, H, KH, page, NB, D = 2, 4, 2, 32, 4, 8
    P = 4
    q = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32))
    pool_k = rng.normal(size=(P, KH, page, D)).astype(np.float32)
    pool_v = rng.normal(size=(P, KH, page, D)).astype(np.float32)
    pool_k[0] = np.nan  # page 0 is what sentinel gathers clamp onto
    pool_v[0] = np.nan
    bt = np.full((B, NB), P, np.int32)  # everything unallocated...
    bt[0, 0], bt[1, 0] = 1, 2  # ...except each row's first block
    positions = jnp.asarray([10, 20], jnp.int32)
    out = paged_gqa_decode_attention(
        q, jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(bt), positions
    )
    assert not np.any(np.isnan(np.asarray(out)))


def test_decode_step_paged_shuffled_pages_matches_forward():
    """Model level: a ragged batch whose pages lie shuffled through the pool,
    one row inactive.  Active rows' logits are ``llama.forward``'s over the
    same sequence (f32: 2e-4), lengths advance for them alone, and the
    inactive row's pages are not written."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(3)
    B, S, page = 4, 256, 64
    lengths = [3, 63, 64, 200]
    seqs = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lengths]
    ids = np.zeros((B, max(lengths)), np.int32)
    for b, seq in enumerate(seqs):
        ids[b, : len(seq)] = seq
    kv = Paged(cfg, batch=B, max_len=S, page=page, dtype=jnp.float32)
    kv.bt = jnp.asarray(rng.permutation(B * (S // page)).astype(np.int32)).reshape(B, -1)
    kv.prefill(params, ids, lengths)
    before = np.asarray(kv.cache.k[:, kv.bt[1]])
    toks = [7, 11, 13, 17]
    active = jnp.asarray([True, False, True, True])
    logits = kv.decode(params, toks, active=active)
    for b in (0, 2, 3):
        want = llama.forward(params, cfg, jnp.asarray([seqs[b] + [toks[b]]], jnp.int32))[0, -1]
        np.testing.assert_allclose(np.asarray(logits[b]), np.asarray(want), atol=2e-4, rtol=2e-4)
    assert np.asarray(kv.cache.lengths).tolist() == [4, 63, 65, 201]
    np.testing.assert_array_equal(np.asarray(kv.cache.k[:, kv.bt[1]]), before)


# ------------------------------------------------------- engine vs forward
def _drive(eng, futs, limit=4000):
    """Single-threaded deterministic engine loop (no engine thread): every
    request is queued before the first admission, so every run sees the
    identical wave structure and tick schedule."""
    steps = 0
    while not all(f.done() for f in futs):
        eng._reap_dead_slots()
        eng._admit()
        if eng._chunking is not None:
            eng._chunk_step()
        if eng.num_active > 0:
            eng._issue_tick()
        while eng._inflight and (
            len(eng._inflight) > eng.lookahead or eng.num_active == 0
        ):
            eng._process_tick()
        steps += 1
        assert steps < limit, "engine made no progress"


def _run_engine(cfg, params, prompts, *, kv_dtype=None, chunk_size=512):
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=256,
        chunk_size=chunk_size, kv_page_size=64, prefix_cache_size=0,
        kv_cache_dtype=kv_dtype,
    )
    eng._running = True
    futs = [
        eng.submit(
            p, max_tokens=12, temperature=(0.9 if i % 2 else 0.0), top_p=0.9
        )
        for i, p in enumerate(prompts)
    ]
    _drive(eng, futs)
    eng._running = False
    return [f.result(timeout=0).token_ids for f in futs]


def _assert_forward_agrees(cfg, params, prompts, outs, tol, top_k=50):
    """Teacher-forced: ``llama.forward`` over prompt + served tokens.  A greedy
    row's (even index) served token lies within ``tol`` of the forward's best
    logit at every position — its own argmax, but for near-ties; a sampled
    row's lies inside the forward's top-k."""
    for i, (prompt, toks) in enumerate(zip(prompts, outs)):
        assert len(toks) == 12
        logits = np.asarray(llama.forward(params, cfg, jnp.asarray([prompt + toks], jnp.int32))[0])
        for t, tok in enumerate(toks):
            row = logits[len(prompt) + t - 1]
            if i % 2 == 0:
                assert row.max() - row[tok] <= tol, (i, t, float(row.max() - row[tok]))
            else:
                assert row[tok] >= np.sort(row)[-top_k] - tol, (i, t)


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("kv_dtype", [None, "fp8"])
def test_engine_paged_matches_forward(quantize, kv_dtype):
    """The acceptance criterion: greedy + sampled traffic over ragged prompt
    lengths, int8 and bf16 weights, bf16 and fp8 KV, against the forward over
    the same weights.  fp8 K/V: the forward keeps no cache to round, so the
    greedy token may differ where the best two logits lie within what e4m3's
    2^-4 relative step on keys and values moves them: 0.15."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    if quantize:
        from django_assistant_bot_tpu.ops.quant import quantize_decoder_params

        params = quantize_decoder_params(params)
    # seed 70: no sampled row draws the end-of-sequence id within its 12
    # tokens (under seed 7 the 100-token row did at bf16 / fp8 once the wave
    # rode two prefill programs, not three: the engine's key advances once a
    # program), so every row is held to all 12
    rng = np.random.default_rng(70)
    prompts = [rng.integers(1, 255, n).tolist() for n in (9, 33, 65, 100)]
    outs = _run_engine(cfg, params, prompts, kv_dtype=kv_dtype)
    _assert_forward_agrees(cfg, params, prompts, outs, tol=0.15 if kv_dtype else 1e-3)


def test_engine_paged_chunked_prefill_matches_forward():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(1))
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 255, 200).tolist()]
    outs = _run_engine(cfg, params, prompts, chunk_size=64)
    _assert_forward_agrees(cfg, params, prompts, outs, tol=1e-3)


# --------------------------------------------------------- prefix sharing
def _prefix_engine(cfg, params, prefix_cache, **kw):
    return GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=256,
        kv_page_size=64, prefix_cache_size=prefix_cache,
        prefix_min_tokens=16, **kw,
    )


def test_paged_prefix_share_matches_uncached_reference():
    """Shared-prefix traffic (the reference's per-bot system prompt shape):
    cached pages + COW boundary clone must reproduce the no-cache outputs,
    with hits and COW clones actually recorded."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(2))
    rng = np.random.default_rng(9)
    prefix = rng.integers(1, 255, 70).tolist()  # 1 full 64-page + a partial
    prompts = [prefix + rng.integers(1, 255, 20).tolist() for _ in range(3)]

    def run(prefix_cache):
        eng = _prefix_engine(cfg, params, prefix_cache).start()
        try:
            out = [
                eng.submit(
                    p, max_tokens=8, temperature=0.0, prefix_len=len(prefix)
                ).result(timeout=300).token_ids
                for p in prompts  # serial: first registers, later ones hit
            ]
            return out, eng.kv_stats()
        finally:
            eng.stop()

    ref, _ = run(0)
    got, stats = run(8)
    assert got == ref
    assert stats["prefix_hits"] == 2
    assert stats["kv_cow_copies"] == 2  # the 70-token prefix has a partial page
    assert stats["kv_shared_pages"] == 2
    assert stats["kv_shared_page_frac"] > 0


def test_paged_prefix_sharer_survives_other_freeing():
    """One sharer finishes (and releases its refs) while another keeps
    decoding over the same shared pages — the survivor's output must stay on
    the uncached reference path, and the registry keeps the pages alive."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(2))
    rng = np.random.default_rng(10)
    prefix = rng.integers(1, 255, 70).tolist()
    p_long = prefix + rng.integers(1, 255, 20).tolist()
    p_short = prefix + rng.integers(1, 255, 20).tolist()

    ref_eng = _prefix_engine(cfg, params, 0).start()
    try:
        ref = ref_eng.submit(
            p_long, max_tokens=24, temperature=0.0, prefix_len=len(prefix)
        ).result(timeout=300).token_ids
    finally:
        ref_eng.stop()

    eng = _prefix_engine(cfg, params, 8).start()
    try:
        eng.submit(
            p_long[: len(prefix) + 1], max_tokens=2, temperature=0.0,
            prefix_len=len(prefix),
        ).result(timeout=300)  # registers the prefix
        f_long = eng.submit(
            p_long, max_tokens=24, temperature=0.0, prefix_len=len(prefix)
        )
        f_short = eng.submit(
            p_short, max_tokens=2, temperature=0.0, prefix_len=len(prefix)
        )
        f_short.result(timeout=300)  # finishes first, decrefs its pages
        assert f_long.result(timeout=300).token_ids == ref
        free_after = eng.kv_stats()["kv_pages_free"]
        assert free_after > 0  # the short sharer's private pages came back
    finally:
        eng.stop()


def test_paged_pool_accounting_returns_to_empty():
    """After every request finishes, only registry-held pages stay out of the
    free list — no leaks from the admit/finish/reap paths."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(2))
    rng = np.random.default_rng(11)
    eng = _prefix_engine(cfg, params, 0).start()
    try:
        futs = [
            eng.submit(rng.integers(1, 255, 30).tolist(), max_tokens=5,
                       temperature=0.0)
            for _ in range(6)
        ]
        for f in futs:
            f.result(timeout=300)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = eng.kv_stats()
            if st["kv_pages_used"] == 0:
                break
            time.sleep(0.02)
        assert eng.kv_stats()["kv_pages_used"] == 0
    finally:
        eng.stop()


# ------------------------------------------------- restart + KV admission
def test_restart_rebuilds_clean_pool():
    """Crash-only _restart: allocator reset (every page free, registry
    emptied), block tables unallocated — and the engine still serves."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(3))
    rng = np.random.default_rng(12)
    prefix = rng.integers(1, 255, 70).tolist()
    eng = _prefix_engine(cfg, params, 8).start()
    try:
        eng.submit(
            prefix + [5, 6, 7], max_tokens=3, temperature=0.0,
            prefix_len=len(prefix),
        ).result(timeout=300)
        assert eng.kv_stats()["kv_shared_pages"] > 0
        with eng._iter_lock:
            eng._restart(RuntimeError("injected"))
        st = eng.kv_stats()
        assert st["kv_pages_used"] == 0
        assert st["kv_shared_pages"] == 0
        assert np.all(eng._block_tables == eng._kv_sentinel)
        r = eng.submit([1, 2, 3], max_tokens=3, temperature=0.0).result(
            timeout=300
        )
        assert len(r.token_ids) == 3
    finally:
        eng.stop()


def test_scheduler_kv_pressure_policy_deterministic():
    """Policy level, no engine/timing: a request that cannot start now
    (demand > obtainable pages minus queued reservations) and whose projected
    KV wait exceeds admit_max_wait_s sheds with reason=kv_pressure, counted
    separately from queue_full; either condition alone admits."""
    sched = RequestScheduler(
        SchedulerConfig(max_queue=64, admit_max_wait_s=1.0), slots=2
    )
    avail = {"pages": 0}
    sched.bind_kv(lambda: avail["pages"], 4)
    for _ in range(100):
        sched.note_service(5.0)  # one pool drain ~ 5 s >> the 1 s ceiling
    adm = sched.try_admit("interactive", None, kv_pages=2)
    assert not adm.ok
    assert adm.reason == "kv_pressure" and adm.retry_after_s > 0
    assert sched.shed["kv_pressure"] == 1
    assert sched.shed.get("queue_full", 0) == 0
    # pages obtainable -> admitted despite the projected wait
    avail["pages"] = 4
    adm = sched.try_admit("interactive", None, kv_pages=2)
    assert adm.ok
    assert sched.stats()["queued_kv_pages"] == 2
    # zero-demand requests never consult the KV test
    avail["pages"] = 0
    adm = sched.try_admit("interactive", None, kv_pages=0)
    assert adm.reason != "kv_pressure"  # (may still shed on depth est-wait)


def test_engine_sheds_on_kv_pressure_end_to_end():
    """Engine level: pool-sized requests in flight (pinned slow via the fault
    injector so they cannot finish under the test), the next submit sheds
    synchronously with reason=kv_pressure."""
    from django_assistant_bot_tpu.serving.faults import FaultInjector

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(4))
    sched = RequestScheduler(
        SchedulerConfig(max_queue=64, admit_max_wait_s=1.0)
    )
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=256,
        kv_page_size=128, prefix_cache_size=0,
        scheduler=sched,
        faults=FaultInjector({"slow_tick": {"every": 1, "delay_s": 0.02}}),
    ).start()
    try:
        holds = [
            eng.submit([b] * 100, max_tokens=200, temperature=0.0)
            for b in (1, 2)
        ]  # 2 pages each -> the whole 4-page pool
        deadline = time.monotonic() + 30
        while eng.kv_stats()["kv_pages_free"] > 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        # pump the service EMA only now (the holds are slotted, the queue is
        # empty) so the depth-based est-wait test stays quiet and the shed
        # below is attributable to KV pressure alone
        for _ in range(100):
            sched.note_service(5.0)
        with pytest.raises(SchedulerRejected) as ei:
            eng.submit([3] * 100, max_tokens=200, temperature=0.0)
        assert ei.value.reason == "kv_pressure"
        assert sched.shed["kv_pressure"] == 1
        for f in holds:
            f.cancel()
    finally:
        eng.stop()


def test_scheduler_kv_pressure_still_queues_modest_backlog():
    """The KV test must NOT shed ordinary queueing: small-demand requests
    behind a busy engine queue as before (the default factor allows one full
    pool drain of backlog)."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(4))
    sched = RequestScheduler(SchedulerConfig(max_queue=64))
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=1, max_seq_len=256,
        kv_page_size=64, prefix_cache_size=0, scheduler=sched,
    ).start()
    try:
        futs = [
            eng.submit([1, 2, 3, i], max_tokens=8, temperature=0.0)
            for i in range(4)
        ]
        for f in futs:
            f.result(timeout=300)
        assert sched.shed.get("kv_pressure", 0) == 0
    finally:
        eng.stop()


def test_kv_pressure_429_reason_on_the_wire():
    """The shed reason reaches the HTTP 429 body (the operator-visible
    contract)."""
    from aiohttp.test_utils import TestClient, TestServer

    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec
    from django_assistant_bot_tpu.serving.server import create_app

    registry = ModelRegistry(
        {
            "tiny-chat": ModelSpec(
                name="tiny-chat", kind="decoder", tiny=True, max_slots=2,
                max_seq_len=256, sched_admit_max_wait_s=1.0,
                faults={"slow_tick": {"every": 1, "delay_s": 0.02}},
            )
        }
    )

    async def drive():
        eng = registry.get_generator("tiny-chat")
        client = TestClient(TestServer(create_app(registry)))
        await client.start_server()
        try:
            holds = [
                eng.submit([b] * 100, max_tokens=200, temperature=0.0)
                for b in (1, 2)
            ]
            deadline = time.monotonic() + 30
            while eng.kv_stats()["kv_pages_free"] > 0:
                assert time.monotonic() < deadline
                await asyncio.sleep(0.01)
            for _ in range(100):
                eng.scheduler.note_service(5.0)
            r = await client.post(
                "/dialog/",
                json={
                    "model": "tiny-chat",
                    "messages": "x" * 120,
                    "max_tokens": 200,
                },
            )
            assert r.status == 429
            body = await r.json()
            assert body["reason"] == "kv_pressure"
            assert "Retry-After" in r.headers
            for f in holds:
                f.cancel()
        finally:
            await client.close()

    try:
        asyncio.new_event_loop().run_until_complete(drive())
    finally:
        registry.stop()


# ------------------------------------------------------------- knobs/shims
def test_engine_kv_knob_validation():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(6))
    tok = ByteTokenizer()
    eng = GenerationEngine(
        cfg, params, tok, max_slots=2, max_seq_len=256, kv_page_size=64
    )
    assert eng.kv_page_size == 64
    assert eng._kv_pool.n_pages == 2 * (256 // 64)  # a whole context per slot
    # speculative engines run the same pool (the tree verify commits the
    # accepted path through the block table)
    eng = GenerationEngine(
        cfg, params, tok, max_slots=2, max_seq_len=256, speculative=2
    )
    assert eng.kv_page_size == 128
    ks = eng.kv_stats()
    assert ks["kv_cache_kind"] == "kv" and ks["kv_pages_total"] == 4
    assert not [k for k in ks if k.startswith("kv_layout")]
    with pytest.raises(ValueError, match="kv_pages"):
        GenerationEngine(
            cfg, params, tok, max_slots=2, max_seq_len=256,
            kv_page_size=64, kv_pages=2,  # < one max-length request
        )


def test_modelspec_prefix_cache_size_shim():
    from django_assistant_bot_tpu.serving.registry import ModelSpec

    spec = ModelSpec.from_dict(
        "m", {"kind": "decoder", "tiny": True, "prefix_cache_size": 3}
    )
    assert spec.prefix_cache == 3
    # explicit new-name knob wins over the deprecated alias
    spec = ModelSpec.from_dict(
        "m",
        {"kind": "decoder", "tiny": True, "prefix_cache_size": 3,
         "prefix_cache": 5},
    )
    assert spec.prefix_cache == 5


@pytest.mark.parametrize("key,value", [("kv_layout", "paged"), ("decode_kv_chunk", 128), ("max_slot", 8)])
def test_modelspec_names_the_unknown_key_and_the_model(key, value):
    """A model entry is an operator's file: a setting that is gone, or a
    misspelt one, is refused with the key and the model named, not with the
    dataclass's TypeError."""
    from django_assistant_bot_tpu.serving.registry import ModelSpec

    with pytest.raises(ValueError, match=rf"model chat-7b: .*'{key}'"):
        ModelSpec.from_dict("chat-7b", {"kind": "decoder", "tiny": True, key: value})


def test_one_kv_layout_and_no_bench_py():
    """The contiguous cache and ``bench.py`` went together: no module of the
    package says ``kv_layout``, and nothing in the package, the tests or the
    repository's root imports ``bench``."""
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parents[1]
    pkg = sorted((root / "django_assistant_bot_tpu").rglob("*.py"))
    assert pkg
    word = "kv_" + "layout"  # spelled apart: this file is scanned below too
    assert not [str(p) for p in pkg if word in p.read_text()]
    imports = re.compile(r"^\s*(import bench\b|from bench import)", re.M)
    files = pkg + sorted((root / "tests").glob("*.py")) + sorted(root.glob("*.py"))
    assert not [str(p) for p in files if imports.search(p.read_text())]
    assert not (root / "bench.py").exists()


def test_tick_stats_and_healthz_carry_kv_gauges():
    from aiohttp.test_utils import TestClient, TestServer

    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec
    from django_assistant_bot_tpu.serving.server import create_app

    registry = ModelRegistry(
        {
            "tiny-chat": ModelSpec(
                name="tiny-chat", kind="decoder", tiny=True, max_slots=2,
                max_seq_len=256,
            )
        }
    )

    async def drive():
        client = TestClient(TestServer(create_app(registry)))
        await client.start_server()
        try:
            r = await client.get("/healthz")
            body = await r.json()
            kv = body["generators"]["tiny-chat"]["kv"]
            assert kv["kv_cache_kind"] == "kv"
            for key in ("kv_pages_used", "kv_pages_free", "kv_shared_page_frac",
                        "kv_evictions", "kv_cow_copies"):
                assert key in kv
            # which decode K/V implementation is live: a label, not a rate.
            # The CPU has no Mosaic compiler, so here it is the plain path;
            # a TPU serving a kernel-shaped geometry must say "kernel"
            assert body["generators"]["tiny-chat"]["decode"]["decode_kv_path"] == "xla"
        finally:
            await client.close()

    try:
        asyncio.new_event_loop().run_until_complete(drive())
        eng = registry.get_generator("tiny-chat")
        assert eng.tick_stats()["kv"]["kv_pages_total"] == 4
        assert eng.tick_stats()["decode_kv_path"] == "xla"
    finally:
        registry.stop()
