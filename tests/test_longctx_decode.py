"""Length-aware decode attention: the paged read skips every page past the
batch's longest live position, and must be a pure optimization — the same
logits as the full forward across ragged per-slot lengths, page-boundary
transitions mid-decode, sliding windows, and the fp8-KV per-page dequant path.
All CPU (f32), so tier-1 gates it without hardware.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine
from paged import Paged

_FP8 = jnp.float8_e4m3fn
_WINDOWED = dict(sliding_window=48, window_layer_start=1)


@pytest.mark.parametrize(
    "lengths, page, steps, kv_dtype, cfg_kw, atol",
    [
        # ragged lengths on and either side of a page boundary
        pytest.param([3, 63, 64, 200], 64, 1, None, {}, 2e-4, id="ragged"),
        # greedy decode across a page boundary: the read window grows by a page mid-run
        pytest.param([60, 61], 64, 8, None, {}, 2e-4, id="boundary_mid_decode"),
        # fp8 pool, dequantised per page; forward keeps no cache to round, so the
        # tolerance is e4m3's 2^-4 relative step on K and V carried to the logits
        pytest.param([5, 64, 100], 32, 1, _FP8, {}, 0.15, id="fp8_kv_per_page_dequant"),
        # windowed layers: band masking inside the live pages, pages below the band skipped
        pytest.param([10, 120, 200], 64, 1, None, _WINDOWED, 2e-4, id="windowed"),
    ],
)
def test_decode_step_paged_matches_forward(lengths, page, steps, kv_dtype, cfg_kw, atol):
    """decode_step_paged over prefilled pages == llama.forward over the same
    sequences, row by row and step by step."""
    cfg = dataclasses.replace(DecoderConfig.tiny(), **cfg_kw)
    params = llama.init(cfg, jax.random.key(len(lengths) + page))
    rng = np.random.default_rng(page + steps)
    B, S = len(lengths), 256
    seqs = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lengths]
    ids = np.zeros((B, max(lengths)), np.int32)
    for b, seq in enumerate(seqs):
        ids[b, : len(seq)] = seq
    kv = Paged(cfg, batch=B, max_len=S, page=page, dtype=kv_dtype or jnp.float32)
    logits = kv.prefill(params, ids, lengths)
    for step in range(steps + 1):
        for b, seq in enumerate(seqs):
            want = llama.forward(params, cfg, jnp.asarray([seq], jnp.int32))[0, -1]
            np.testing.assert_allclose(
                np.asarray(logits[b]), np.asarray(want), atol=atol, rtol=atol,
                err_msg=f"row {b} at step {step}",
            )
            if kv_dtype is None:
                assert int(jnp.argmax(logits[b])) == int(jnp.argmax(want)), (b, step)
        if step == steps:
            break
        for b, seq in enumerate(seqs):
            seq.append(int(jnp.argmax(logits[b])))
        logits = kv.decode(params, [seq[-1] for seq in seqs])
    assert kv.cache.k.dtype == (kv_dtype or jnp.float32)
    assert np.asarray(kv.cache.lengths).tolist() == [len(seq) for seq in seqs]


def test_engine_bucketed_greedy_matches_forward_and_reports_frac():
    """End-to-end: an engine with the bucketed read produces the same greedy
    tokens as the repeated full forward, and tick_stats reports
    kv_read_frac < 1 for a short-context batch (the acceptance criterion)."""
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(4))
    tok = ByteTokenizer()
    eng = GenerationEngine(
        cfg, params, tok, max_slots=2, max_seq_len=256, kv_page_size=64,
        prefix_cache_size=0,
    ).start()
    try:
        prompt = tok.encode("bucketed decode")
        n_new = 5
        seq = np.asarray([prompt], np.int32)
        expected = []
        for _ in range(n_new):
            logits = llama.forward(params, cfg, jnp.asarray(seq))
            nxt = int(jnp.argmax(logits[0, -1]))
            expected.append(nxt)
            seq = np.concatenate([seq, [[nxt]]], axis=1)
        result = eng.submit(prompt, max_tokens=n_new, temperature=0.0).result(
            timeout=120
        )
        assert result.token_ids == expected
        stats = eng.tick_stats()
        assert stats["ticks"] >= 1
        # prompt + 5 tokens ≈ 20 positions of a 256-token context in 64-wide
        # pages -> 1 of 4 pages read
        assert 0 < stats["kv_read_frac"] < 1
    finally:
        eng.stop()


def test_engine_kv_page_size_validation_and_auto():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(5))
    tok = ByteTokenizer()
    # auto at 256 ctx -> 128 (the largest of 512 ... 8 leaving >= 2 pages)
    eng = GenerationEngine(cfg, params, tok, max_slots=1, max_seq_len=256)
    assert eng.kv_page_size == 128
    assert eng.tick_stats()["kv_read_frac"] == 1.0  # no tick issued yet
    for bad in (100, 256, -64):  # does not divide; one page only; nonsense
        with pytest.raises(ValueError, match=r"max_seq_len=256.*kv_page_size=" + str(bad)):
            GenerationEngine(
                cfg, params, tok, max_slots=1, max_seq_len=256, kv_page_size=bad
            )


@pytest.mark.parametrize("max_seq_len,page", [
    (32, 16), (64, 32), (96, 32), (128, 64), (256, 128), (1024, 512), (2048, 512),
])
def test_engine_page_picked_with_nothing_named(max_seq_len, page):
    """The page decides ``decode_kv_path`` and the pool's shape, so what an
    engine picks with no ``kv_page_size`` named stays what it was when the
    pick went through ``decode_kv_chunk`` (values read off that tree)."""
    cfg = dataclasses.replace(DecoderConfig.tiny(), max_seq_len=max_seq_len)
    eng = GenerationEngine(
        cfg, llama.init(cfg, jax.random.key(5)), ByteTokenizer(), max_slots=1,
        max_seq_len=max_seq_len, prefix_cache_size=0,
    )
    assert eng.max_seq_len == max_seq_len
    assert eng.kv_page_size == page
    assert eng.kv_stats()["kv_pages_total"] == max_seq_len // page


def test_kv_read_frac_counts_pages():
    """``kv_read_frac`` is pages covering the longest live slot over pages of
    a full context, whatever the page: 2 pages of 64, a 10-token request lies
    in the first, so every tick reads half (it read 1.0 while the estimate
    went by ``decode_kv_chunk``, which had no width for a 128-token context)."""
    cfg = DecoderConfig.tiny()
    eng = GenerationEngine(
        cfg, llama.init(cfg, jax.random.key(4)), ByteTokenizer(), max_slots=2,
        max_seq_len=128, prefix_cache_size=0,
    ).start()
    try:
        assert eng.kv_page_size == 64
        eng.submit([1, 2, 3, 4], max_tokens=6, temperature=0.0).result(timeout=120)
        stats = eng.tick_stats()
        assert stats["ticks"] >= 1 and stats["kv_read_frac"] == 0.5
    finally:
        eng.stop()


def test_probe_decode_fill_len_leaves_engine_serviceable():
    """A fill-pinned probe must reset lengths and leave the engine able to
    serve real traffic."""
    import asyncio

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(6))
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=128,
        kv_page_size=64, prefix_cache_size=0,
    ).start()
    try:
        step_s = eng.probe_decode(iters=2, fill_len=100)
        assert step_s > 0
        assert np.asarray(eng._cache.lengths).max() == 0  # reset after probe
        r = asyncio.run(
            eng.generate([{"role": "user", "content": "hi"}], max_tokens=3,
                         temperature=0.0)
        )
        assert len(r.token_ids) == 3
    finally:
        eng.stop()
