"""The shapes of the prefill programs (``serving/engine.py`` ``prefill_shapes``
and ``plan_prefill``): the rule as properties, and a warmed tiny engine driven
across every bucket edge: it compiles nothing after warm-up, dispatches only
warmed shapes, and serves the tokens ``llama.forward`` gives whatever program a
prompt rode."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops.attention import FLASH_BLOCK
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine
from django_assistant_bot_tpu.serving.engine import plan_prefill, prefill_shapes
from django_assistant_bot_tpu.serving.registry import ModelSpec

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "configs")
GEOMETRIES = [(1024, 8), (1024, 1), (512, 4), (64, 2), (32, 2)]


def _pairs(shapes):
    return [(rows, bucket) for bucket, row_counts in shapes.items() for rows in row_counts]


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("chunk,wave", GEOMETRIES)
def test_every_length_gets_a_bucket_padded_by_less_than_a_block(chunk, wave):
    shapes = prefill_shapes(chunk, wave)
    assert list(shapes) == sorted(shapes) and max(shapes) == chunk
    for n in range(1, chunk + 1):
        ((rows, bucket, members),) = plan_prefill(shapes, [n])
        assert (rows, members) == (1, [0]) and n <= bucket <= chunk
        if n > FLASH_BLOCK:
            assert bucket - n < FLASH_BLOCK
    # from two blocks up a bucket is whole blocks (the flash kernel's shape), but for the chunk itself
    assert all(b % FLASH_BLOCK == 0 for b in shapes if 2 * FLASH_BLOCK <= b < chunk)


@pytest.mark.parametrize("chunk,wave", GEOMETRIES)
def test_a_program_holds_at_most_a_chunk_and_a_wave(chunk, wave):
    shapes = prefill_shapes(chunk, wave)
    for rows, bucket in _pairs(shapes):
        assert rows * bucket <= chunk
        assert 1 <= rows <= wave
    for bucket, row_counts in shapes.items():
        assert row_counts[0] == 1
        assert (2 in row_counts) == (2 * bucket <= chunk and wave >= 2)


@pytest.mark.parametrize("chunk,wave", GEOMETRIES)
def test_a_wave_is_planned_in_admission_order_on_warmed_shapes(chunk, wave):
    shapes = prefill_shapes(chunk, wave)
    rng = np.random.default_rng(chunk * 31 + wave)
    for _ in range(200):
        lengths = [int(n) for n in rng.integers(1, chunk + 1, size=int(rng.integers(1, wave + 1)))]
        plan = plan_prefill(shapes, lengths)
        assert sorted(i for _, _, members in plan for i in members) == list(range(len(lengths)))
        for rows, bucket, members in plan:
            assert rows in shapes[bucket] and 1 <= len(members) <= rows
            assert members == sorted(members)
            # each row rides its own bucket: the smallest that holds it
            assert all(bucket == min(b for b in shapes if lengths[i] <= b) for i in members)
    # a wave of two is never padded to four: two rows where two fit, else one and one
    for bucket in shapes:
        got = [rows for rows, _, _ in plan_prefill(shapes, [bucket] * min(2, wave))]
        assert got == ([1] if wave == 1 else [2] if 2 * bucket <= chunk else [1, 1])


def test_a_group_takes_the_cheapest_programs_of_the_set():
    shapes = prefill_shapes(1024, 8)
    rows_of = lambda n, length: [rows for rows, _, _ in plan_prefill(shapes, [length] * n)]
    assert rows_of(3, 128) == [2, 1]  # 384 positions, not 8 x 128
    assert rows_of(5, 64) == [8]  # one read of the weights, not three
    assert rows_of(6, 128) == [2, 2, 2] and rows_of(7, 128) == [8]  # 67.7 < 77.0 < 82.9 ms on the chip
    assert rows_of(8, 100) == [8]
    assert rows_of(3, 200) == [2, 1] and rows_of(4, 200) == [4]
    assert rows_of(3, 500) == [2, 1]  # 2 x 512 is a chunk: no 4 x 512
    # groups by bucket: 300 and 450 tokens do not share a 512 program
    assert [(r, b) for r, b, _ in plan_prefill(shapes, [300, 450])] == [(1, 384), (1, 512)]


def test_the_qwen_spec_warms_no_more_programs_than_before():
    """chunk 1,024, 8 slots, nothing named: 17 programs (the parent's rule:
    6 buckets x {1, 4, 8} = 18), none above one chunk's positions."""
    with open(os.path.join(CONFIGS, "qwen2.5-7b-instruct.json")) as f:
        serving = json.load(f)["serving"]
    assert "prefill_buckets" not in serving and "prefill_wave" not in serving
    shapes = prefill_shapes(serving["chunk_size"], serving["max_slots"])
    assert list(shapes) == [64, 128, 256, 384, 512, 640, 768, 896, 1024]
    assert len(_pairs(shapes)) == 17 <= 18
    assert shapes == {64: (1, 2, 8), 128: (1, 2, 8), 256: (1, 2, 4), 384: (1, 2), 512: (1, 2),
                      640: (1,), 768: (1,), 896: (1,), 1024: (1,)}


def test_a_named_spec_is_obeyed_word_for_word():
    with open(os.path.join(CONFIGS, "a.x-k1-ep16.json")) as f:
        serving = json.load(f)["serving"]
    shapes = prefill_shapes(serving["chunk_size"], serving["prefill_wave"], serving["prefill_buckets"])
    assert _pairs(shapes) == [(1, 512), (1, 1024)]
    # the wave still caps the rows, and the chunk is always the last bucket
    assert prefill_shapes(96, 4, (32, 64)) == {32: (1, 2), 64: (1,), 96: (1,)}
    assert len(dataclasses.fields(ModelSpec)) == 68


# ------------------------------------------------------- a warmed tiny engine
EDGES = (127, 128, 129, 255, 256, 257, 383, 384, 385, 511, 512)
NEW = 2
PROGRAMS = ("_prefill", "_insert", "_prefill_suffix", "_activate_fn", "_copy_pages", "_decode_tick")


@pytest.fixture(scope="module")
def warmed():
    cfg = dataclasses.replace(DecoderConfig.tiny(), max_seq_len=1024)
    params = llama.init(cfg, jax.random.key(3))
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=8, max_seq_len=1024, chunk_size=512,
        prefix_cache_size=4, prefix_min_tokens=16, lookahead=1,
    )
    eng.warmup()
    eng._running = True  # lockstep: the tests crank _loop_iteration themselves
    yield eng, cfg, params
    eng.stop(drain_timeout_s=5.0)


def _compiled(eng):
    return {name: getattr(eng, name)._cache_size() for name in PROGRAMS}


def _greedy(cfg, params, prompt, n_new=NEW):
    seq = list(prompt)
    for _ in range(n_new):
        logits = llama.forward(params, cfg, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def _serve(eng, prompts, **kw):
    """One admission wave: every prompt is queued before the loop turns."""
    futs = [eng.submit(p, max_tokens=NEW, temperature=0.0, **kw) for p in prompts]
    for _ in range(2000):
        if all(f.done() for f in futs):
            return [f.result(timeout=5) for f in futs]
        eng._loop_iteration()
    raise AssertionError("requests did not finish within the crank budget")


def test_warm_up_compiles_every_shape_and_traffic_compiles_none(warmed):
    eng, cfg, params = warmed
    assert eng.prefill_shapes == prefill_shapes(512, 8) == {
        64: (1, 2, 8), 128: (1, 2, 4), 256: (1, 2), 384: (1,), 512: (1,)}
    before = _compiled(eng)
    # (the insert is a module's function: its count is shared with every engine this process has built)
    assert before["_prefill"] == before["_prefill_suffix"] == 10 <= before["_insert"]
    rng = np.random.default_rng(35)
    seen = set()
    for wave in range(1, 9):
        lengths = [int(n) for n in rng.choice(EDGES, size=wave)]
        prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)] for n in lengths]
        results = _serve(eng, prompts)
        plan = plan_prefill(eng.prefill_shapes, lengths)
        for rows, bucket, members in plan:
            seen.add(f"{rows}x{bucket}")
            for i in members:
                tm = results[i].timings
                assert (tm["prefill_bucket"], tm["wave_rows"], tm["wave_rows_padded"]) == (bucket, len(members), rows)
        for prompt, res in zip(prompts, results):
            assert res.token_ids == _greedy(cfg, params, prompt), (len(prompt), wave)
    assert _compiled(eng) == before  # no program was built after warm-up
    counts = eng.tick_stats()["prefill_shapes"]
    assert set(counts) == {f"{r}x{b}" for r, b in _pairs(eng.prefill_shapes)}  # the warmed set, whole
    assert {k for k, n in counts.items() if n} == seen and len(seen) >= 5


def test_a_suffix_wave_rides_warmed_shapes_too(warmed):
    eng, cfg, params = warmed
    rng = np.random.default_rng(36)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab_size, size=200)]
    owner = prefix + [int(t) for t in rng.integers(1, cfg.vocab_size, size=30)]
    (first,) = _serve(eng, [owner], prefix_len=len(prefix))
    assert first.timings["prefix_hit_tokens"] == 0
    before, hits = _compiled(eng), eng.prefix_hits
    counts0 = eng.tick_stats()["prefill_shapes"]
    # suffixes of 57, 63 (bucket 64: two rows) and 129 tokens (bucket 256: one)
    tails = [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)] for n in (57, 129, 63)]
    results = _serve(eng, [prefix + t for t in tails], prefix_len=len(prefix))
    assert eng.prefix_hits == hits + 3
    assert [(r.timings["prefill_bucket"], r.timings["wave_rows"], r.timings["wave_rows_padded"])
            for r in results] == [(64, 2, 2), (256, 1, 1), (64, 2, 2)]
    assert all(r.timings["prefix_hit_tokens"] == len(prefix) for r in results)
    for tail, res in zip(tails, results):
        assert res.token_ids == _greedy(cfg, params, prefix + tail)
    assert _compiled(eng) == before
    counts = eng.tick_stats()["prefill_shapes"]
    assert {k: counts[k] - counts0[k] for k in counts if counts[k] != counts0[k]} == {"2x64": 1, "1x256": 1}
