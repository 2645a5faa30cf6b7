"""True continuous batching (ROADMAP item 2): piggybacked chunked prefill,
spec x fused unification, and fp8 in-dot attention.

The non-negotiable property is BIT-IDENTICAL output with piggybacked prefill
on vs off — folding a prefill chunk into the fused decode tick may only change
when decode tokens are dispatched, never which tokens come out.  The tests
crank :meth:`GenerationEngine._loop_iteration` directly (no engine thread) so
the admission/tick interleaving — and therefore the rng stream — is identical
across the A/B engines by construction.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops.attention import (
    gqa_dot_product_attention,
    paged_gqa_decode_attention,
)
from django_assistant_bot_tpu.ops.quant import quantize_decoder_params
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine

# documented accuracy contract for the fp8 in-dot QK product (docs/QUANT.md):
# max abs attention-output error vs the bf16-dequant reference on unit-scale
# operands.  Measured ~0.05 on CPU; the bound leaves headroom for backend
# accumulation-order drift without ever hiding a broken scale.
FP8_INDOT_MAX_ABS_ERR = 0.15


@pytest.fixture(scope="module")
def tiny():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("prefix_cache_size", 0)
    kw.setdefault("chunk_size", 16)
    kw.setdefault("lookahead", 1)
    return GenerationEngine(cfg, params, ByteTokenizer(), **kw)


def _lockstep(eng):
    """Accept submissions without the engine thread: the test cranks
    ``_loop_iteration`` itself (submit() fast-fails when not 'running')."""
    eng._running = True
    return eng


def _crank(eng, futs, iters=600):
    """Drive the engine loop body deterministically until ``futs`` resolve."""
    for _ in range(iters):
        if all(f.done() for f in futs):
            return
        eng._loop_iteration()
    raise AssertionError("requests did not finish within the crank budget")


# -------------------------------------------------- piggyback bit-identity
LONG_PROMPT = list(range(1, 41))  # 40 ids > chunk_size=16 -> 3 prefill chunks


class _TickingClock:
    """A millisecond passes at every reading: each wait for a result is then
    longer than the ledger's threshold, so every marker closes a segment and
    the device-queue seconds count clock reads, the same in every run."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-3
        return self.t

    def sleep(self, dt):
        self.t += dt


def _ab_run(cfg, params, piggyback, **kw):
    """Two ragged resident slots (one greedy, one sampled) decode while a
    40-token prompt admits through chunked prefill; returns every request's
    token ids plus the decode-path gauges and the ledger's device queue."""
    clk = _TickingClock()
    eng = _lockstep(
        _engine(cfg, params, prefill_piggyback=piggyback, decode_steps=2,
                clock=clk, sleep=clk.sleep, **kw)
    )
    futs = [
        eng.submit(list(range(3, 12)), max_tokens=20, temperature=0.0),
        eng.submit(list(range(5, 10)), max_tokens=18, temperature=0.8),
    ]
    for _ in range(3):  # fixed crank count: identical rng stream across A/B
        eng._loop_iteration()
    futs.append(eng.submit(LONG_PROMPT, max_tokens=6, temperature=0.7))
    _crank(eng, futs)
    out = [f.result(timeout=10).token_ids for f in futs]
    dec = dict(eng.decode_path_stats(), device_queue=eng.loop_stats()["device_queue"])
    eng.stop(drain_timeout_s=10.0)
    return out, dec


@pytest.mark.parametrize(
    "kw",
    [
        {},
        {"quantize": "int8", "kv_cache_dtype": "fp8"},
        {"quantize": "int4"},
    ],
    ids=["paged", "paged-int8-fp8kv", "paged-int4"],
)
def test_piggybacked_prefill_bit_identical_to_sequential(tiny, kw):
    """Greedy AND sampled outputs must match bit-for-bit with the chunk
    folded into the decode tick vs the sequential chunk-then-tick path,
    across weight/KV formats — and the gauges must prove each
    path actually ran (piggybacked chunks on, displaced ticks off)."""
    cfg, params = tiny
    kw = dict(kw)
    q = kw.pop("quantize", None)
    if q:
        params = quantize_decoder_params(params, fmt=q)
    on, dec_on = _ab_run(cfg, params, True, **kw)
    off, dec_off = _ab_run(cfg, params, False, **kw)
    assert on == off
    assert dec_on["prefill_piggyback"] is True
    assert dec_on["prefill_chunks_piggybacked"] >= 2  # all but the final chunk
    assert dec_off["prefill_piggyback"] is False
    assert dec_off["prefill_chunks_piggybacked"] == 0
    # the sequential path displaced decode ticks (a chunk's whole program in
    # front of a tick: the ledger's `chunk+tick` segments, and `chunk` for the
    # final, activation-feeding one); the piggybacked one only by that last
    # chunk, the others rode a tick (`piggyback` segments)
    q_on, q_off = dec_on["device_queue"], dec_off["device_queue"]
    displaced = lambda q: q["chunk+tick"]["s"] + q["chunk"]["s"]
    assert displaced(q_off) > displaced(q_on) > 0
    assert q_off["chunk+tick"]["n"] >= 2 and q_off["piggyback"]["n"] == 0
    assert q_on["chunk+tick"]["n"] == 0 and q_on["chunk"]["n"] == 1
    assert q_on["piggyback"]["n"] == dec_on["prefill_chunks_piggybacked"]
    assert q_on["markers"]["not_waited"] == q_off["markers"]["not_waited"] == 0


def test_piggyback_gauges_and_knob_defaults(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    assert eng._piggyback_tick is not None  # default-on
    dec = eng.decode_path_stats()
    assert dec["prefill_piggyback"] is True
    assert dec["prefill_chunks_piggybacked"] == 0
    # nothing dispatched yet: no chunk displaced a tick, none rode one; the
    # ledger lists the keys from the start
    dq = eng.loop_stats()["device_queue"]
    assert dq["chunk+tick"]["s"] == dq["chunk"]["s"] == dq["piggyback"]["s"] == 0.0
    assert dec["attn_fp8"] is False
    eng.stop(drain_timeout_s=5.0)
    # speculative engines never piggyback (the spec tick has its own shape)
    eng2 = _engine(cfg, params, speculative=3, spec_width=2)
    assert eng2._piggyback_tick is None
    eng2.stop(drain_timeout_s=5.0)


# ----------------------------------------------------- scheduler charging
def test_prefill_chunks_charged_to_service_model(tiny):
    """note_service must charge chunked-prefill dispatches as service units:
    an identical decode workload admitted through 3 prefill chunks must be
    charged exactly 3 more tokens than its single-shot-prefill twin —
    otherwise long-prompt traffic skews predicted queue waits optimistic."""
    from django_assistant_bot_tpu.serving.scheduler import (
        RequestScheduler,
        SchedulerConfig,
    )

    cfg, params = tiny

    def _charge(prompt):
        sched = RequestScheduler(SchedulerConfig())
        calls = []
        orig = sched.note_service
        sched.note_service = lambda seconds, tokens=0: (
            calls.append(tokens),
            orig(seconds, tokens),
        )[1]
        eng = _lockstep(_engine(cfg, params, scheduler=sched, decode_steps=1))
        fut = eng.submit(prompt, max_tokens=2, temperature=0.0)
        _crank(eng, [fut])
        fut.result(timeout=10)
        eng.stop(drain_timeout_s=10.0)
        assert len(calls) == 1
        return calls[0]

    short = _charge(list(range(1, 11)))  # 10 ids <= chunk_size: one prefill
    long_ = _charge(LONG_PROMPT)  # 3 chunks
    assert long_ == short + 3


# ------------------------------------------------------------ spec x fused
@pytest.mark.parametrize("steps", [2, 4])
def test_spec_fused_greedy_identity(tiny, steps):
    """decode_steps composes with speculation: N scanned verify passes per
    dispatch must still produce BIT-IDENTICAL greedy output to the plain
    engine, and the draft/accept counters must prove the fast path ran."""
    cfg, params = tiny
    tok = ByteTokenizer()
    jobs = [
        (tok.encode("ab ab ab ab ab ab"), 20, 0.0),
        (tok.encode("the cat sat on the cat sat on"), 16, 0.0),
        (tok.encode("xyz"), 8, 0.0),
    ]

    def run(**kw):
        eng = _engine(cfg, params, chunk_size=64, **kw).start()
        try:
            futs = [
                eng.submit(ids, max_tokens=mt, temperature=t)
                for ids, mt, t in jobs
            ]
            out = [f.result(timeout=600).token_ids for f in futs]
            stats = eng.tick_stats()
        finally:
            eng.stop(drain_timeout_s=60.0)
        return out, stats

    plain, _ = run(decode_steps=steps)
    spec, stats = run(
        decode_steps=steps, speculative=3, spec_width=2, spec_probe_every=1
    )
    assert spec == plain
    assert stats["spec_drafted"] > 0
    assert stats["decode_steps"] == steps


def test_spec_default_verify_depth_is_one(tiny):
    """Removing the old mutual exclusion must NOT silently multiply existing
    speculative deployments: without an explicit decode_steps a spec engine
    runs ONE verify pass per tick (burst is not inherited)."""
    cfg, params = tiny
    eng = _engine(cfg, params, burst=8, speculative=3, spec_width=2)
    assert eng.burst == 1
    eng.stop(drain_timeout_s=5.0)
    eng2 = _engine(cfg, params, decode_steps=2, speculative=3, spec_width=2)
    assert eng2.burst == 2
    eng2.stop(drain_timeout_s=5.0)


# ------------------------------------------------------------- fp8 in-dot
def _fp8_pool(page=16, B=2, H=4, KH=2, S=64, D=16):
    """A bf16 query and fp8 K/V rows, the rows also cut into a page pool (page
    j of row b at index b*nb+j): ``q, (k8, v8), k_pool, v_pool, bt, positions``."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, KH, S, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KH, S, D)) * 0.5, jnp.float32)
    k8 = k.astype(jnp.float8_e4m3fn)
    v8 = v.astype(jnp.float8_e4m3fn)
    positions = jnp.asarray([S - 1, S // 3], jnp.int32)
    nb = S // page

    def pool(x8):
        return jnp.asarray(
            np.asarray(x8.astype(jnp.float32))
            .reshape(B, KH, nb, page, D)
            .transpose(0, 2, 1, 3, 4)
            .reshape(B * nb, KH, page, D)
        ).astype(jnp.float8_e4m3fn)

    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    return q, (k8, v8), pool(k8), pool(v8), bt, positions


def _max_abs_err(got, ref):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))


def test_fp8_indot_within_bound_of_masked_gqa():
    """In-dot fp8 against plain masked attention over the upcast rows."""
    q, (k8, v8), k_pool, v_pool, bt, positions = _fp8_pool()
    S = k8.shape[2]
    mask = (jnp.arange(S)[None, :] <= positions[:, None])[:, None, None, :]
    ref = gqa_dot_product_attention(q, k8.astype(q.dtype), v8.astype(q.dtype), mask=mask)
    got = paged_gqa_decode_attention(q, k_pool, v_pool, bt, positions, fp8_dot=True)
    assert 0.0 < _max_abs_err(got, ref) < FP8_INDOT_MAX_ABS_ERR


def test_fp8_indot_paged_within_bound():
    q, _, k_pool, v_pool, bt, positions = _fp8_pool()
    ref = paged_gqa_decode_attention(q, k_pool, v_pool, bt, positions)
    got = paged_gqa_decode_attention(
        q, k_pool, v_pool, bt, positions, fp8_dot=True
    )
    assert 0.0 < _max_abs_err(got, ref) < FP8_INDOT_MAX_ABS_ERR


def test_fp8_indot_rejects_non_fp8_kv():
    q, _, k_pool, v_pool, bt, positions = _fp8_pool()
    with pytest.raises(ValueError, match="fp8"):
        paged_gqa_decode_attention(
            q,
            k_pool.astype(jnp.bfloat16),
            v_pool.astype(jnp.bfloat16),
            bt,
            positions,
            fp8_dot=True,
        )


def test_attn_fp8_engine_knob_validation(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="fp8"):
        _engine(cfg, params, attn_fp8=True)  # no fp8 KV cache
    from django_assistant_bot_tpu.serving.registry import ModelSpec

    with pytest.raises(ValueError, match="attn_fp8"):
        from django_assistant_bot_tpu.serving.registry import ModelRegistry

        ModelRegistry(
            specs={
                "m": ModelSpec(
                    name="m", kind="decoder", tiny=True, attn_fp8=True
                )
            }
        )


def test_attn_fp8_engine_end_to_end(tiny):
    """An fp8-in-dot engine serves a mixed batch and reports the knob; the
    lossy path must still be deterministic with itself (same seed, same
    lockstep crank -> same ids)."""
    cfg, params = tiny

    def run():
        eng = _lockstep(_engine(cfg, params, kv_cache_dtype="fp8", attn_fp8=True))
        futs = [
            eng.submit(list(range(2, 14)), max_tokens=12, temperature=0.0),
            eng.submit(LONG_PROMPT, max_tokens=6, temperature=0.9),
        ]
        _crank(eng, futs)
        out = [f.result(timeout=10).token_ids for f in futs]
        dec = eng.decode_path_stats()
        eng.stop(drain_timeout_s=10.0)
        return out, dec

    a, dec = run()
    b, _ = run()
    assert a == b
    assert dec["attn_fp8"] is True
    assert all(len(ids) >= 1 for ids in a)


# ------------------------------------------------------------------- chaos
def test_tick_raise_mid_piggyback_restart_leaves_page_pool_clean(tiny):
    """An engine-fatal fault fired inside a piggybacked dispatch (prefill
    chunk + decode tick in one program): crash-only restart must reset the
    page plane, salvage the token-less mid-prefill request, and fail the
    mid-decode one cleanly."""
    from django_assistant_bot_tpu.serving.faults import FaultInjected, FaultInjector

    cfg, params = tiny
    inj = FaultInjector({})
    eng = _lockstep(_engine(cfg, params, decode_steps=2, faults=inj, max_slots=2))
    f0 = eng.submit(list(range(3, 12)), max_tokens=40, temperature=0.0)
    for _ in range(5):
        eng._loop_iteration()
    assert eng.num_active == 1
    f1 = eng.submit(LONG_PROMPT, max_tokens=4, temperature=0.0)
    for _ in range(50):
        st = eng._chunking
        if st is not None and st.step < len(st.starts) - 1:
            break  # mid-chunked-prefill with piggybacked steps remaining
        eng._loop_iteration()
    assert eng._chunking is not None
    assert eng._prefill_chunks_piggybacked >= 1
    inj.arm("tick_raise")
    # the next iteration's dispatch IS the piggybacked one — supervise it the
    # way _loop does (crash-only restart), minus the backoff sleep
    with pytest.raises(FaultInjected) as ei:
        eng._loop_iteration()
    with eng._iter_lock:
        eng._restart(ei.value)
    assert eng.engine_restarts == 1
    # pool clean immediately after the restart: every page freed, every
    # block table unallocated, no chunked-prefill state left behind
    assert eng._chunking is None
    assert all(not pages for pages in eng._slot_pages)
    kv = eng.kv_stats()
    assert kv["kv_pages_used"] == 0
    assert kv["kv_pages_free"] == eng._kv_pool.n_pages
    # token-less mid-prefill request was salvaged: it must complete on the
    # rebuilt pool; the mid-decode one fails cleanly with the fault
    for _ in range(600):
        if f0.done() and f1.done():
            break
        eng._loop_iteration()
    assert f1.result(timeout=10).token_ids
    with pytest.raises(Exception):
        f0.result(timeout=10)
    kv = eng.kv_stats()
    assert kv["kv_pages_used"] == 0
    eng.stop(drain_timeout_s=10.0)
