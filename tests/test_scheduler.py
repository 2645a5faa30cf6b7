"""Admission-controlled request scheduler (serving/scheduler.py).

Policy units run against stub requests (no device); integration tests drive a
tiny CPU engine and the HTTP server: priority ordering, weighted fair share,
deadline expiry freeing a live decode slot, shed-threshold/429 mapping with
``Retry-After``, request validation (422), and /healthz queue stats.
"""

import asyncio
import dataclasses
import math
import time
from concurrent.futures import Future
from typing import Optional

import pytest

import jax

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.serving import (
    ByteTokenizer,
    DeadlineExceeded,
    GenerationEngine,
    ModelRegistry,
    RequestScheduler,
    SchedulerConfig,
    SchedulerRejected,
)
from django_assistant_bot_tpu.serving.server import create_app


@dataclasses.dataclass
class StubRequest:
    future: Future
    submitted_at: float
    priority: str = "interactive"
    tenant: str = "default"
    deadline_at: Optional[float] = None
    admitted: bool = False


def _stub(priority="interactive", tenant="default", deadline_at=None, admitted=False):
    """Direct-enqueue stub: admitted=False (depth counted at enqueue), matching
    requests that bypass try_admit."""
    return StubRequest(
        future=Future(),
        submitted_at=time.monotonic(),
        priority=priority,
        tenant=tenant,
        deadline_at=deadline_at,
        admitted=admitted,
    )


def _admit_and_enqueue(s, priority="interactive", tenant="default"):
    adm = s.try_admit(priority)
    assert adm.ok
    req = _stub(priority, tenant, admitted=True)
    s.enqueue(req)
    return req


# --------------------------------------------------------------- policy units
def test_priority_classes_share_by_weight():
    """interactive:background at 8:1 — under contention interactive takes ~8
    of every 9 pops, and background is never starved outright."""
    s = RequestScheduler(
        SchedulerConfig(class_weights={"interactive": 8, "background": 1})
    )
    for _ in range(18):
        _admit_and_enqueue(s, "background")
    for _ in range(18):
        _admit_and_enqueue(s, "interactive")
    order = [s.pop().priority for _ in range(18)]
    # the first 18 pops drain ~16 interactive vs ~2 background
    assert order.count("interactive") >= 16
    assert order.count("background") >= 1  # weighted share, not strict priority
    # everything eventually drains
    rest = [s.pop() for _ in range(18)]
    assert all(r is not None for r in rest)
    assert s.pop() is None


def test_tenant_weighted_fair_share_interleaves():
    """One chatty tenant cannot monopolize: with equal weights, pops alternate
    a:b:... regardless of arrival order; a 3x-weighted tenant gets ~3x slots."""
    s = RequestScheduler(SchedulerConfig(class_weights={"background": 1}))
    for _ in range(8):
        _admit_and_enqueue(s, "background", "a")
    for _ in range(8):
        _admit_and_enqueue(s, "background", "b")
    first_six = [s.pop().tenant for _ in range(6)]
    assert first_six.count("a") == 3 and first_six.count("b") == 3

    s = RequestScheduler(
        SchedulerConfig(
            class_weights={"background": 1}, tenant_weights={"big": 3.0, "small": 1.0}
        )
    )
    for _ in range(12):
        _admit_and_enqueue(s, "background", "big")
    for _ in range(12):
        _admit_and_enqueue(s, "background", "small")
    first_eight = [s.pop().tenant for _ in range(8)]
    assert first_eight.count("big") == 6 and first_eight.count("small") == 2


def test_queue_bound_sheds_with_retry_after():
    s = RequestScheduler(SchedulerConfig(max_queue=2, admit_max_wait_s=None))
    assert s.try_admit("background").ok
    assert s.try_admit("background").ok
    adm = s.try_admit("background")
    assert not adm.ok
    assert adm.reason == "queue_full"
    assert adm.retry_after_s > 0
    assert s.stats()["shed"] == {"queue_full": 1}
    # raising form carries the hint the server maps to Retry-After
    err = SchedulerRejected(adm.reason, adm.retry_after_s)
    assert err.retry_after_s == adm.retry_after_s


def test_estimated_wait_admission_test():
    s = RequestScheduler(
        SchedulerConfig(max_queue=100, admit_max_wait_s=1.0, service_time_init=2.0),
        slots=1,
    )
    _admit_and_enqueue(s, "interactive")  # empty queue: est wait 0, admitted
    # depth 1 * 2s EMA / 1 slot = 2s estimated wait > 1s ceiling
    adm = s.try_admit("interactive")
    assert not adm.ok and adm.reason == "estimated_wait"
    # an infeasible deadline sheds immediately rather than expiring later
    s.cfg.admit_max_wait_s = None
    adm = s.try_admit("interactive", deadline_s=0.5)
    assert not adm.ok and adm.reason == "deadline_infeasible"
    # service-time EMA folds real finishes in and un-sheds
    for _ in range(60):
        s.note_service(0.001)
    assert s.try_admit("interactive", deadline_s=0.5).ok


def test_per_token_service_model_drives_estimated_wait():
    """note_service with tokens engages the per-token model (rate EMA x
    tokens-per-request EMA) — fused N-step ticks deliver residency in
    tick-quantized quanta, and normalizing by the steps the slot actually
    sat through keeps predicted queue waits honest (docs/SCHEDULING.md)."""
    s = RequestScheduler(
        SchedulerConfig(max_queue=100, service_time_init=2.0), slots=1
    )
    # legacy calls keep the raw per-request EMA behavior byte-for-byte
    s.note_service(1.0)
    st = s.stats()
    assert st["service_per_token_ema_ms"] is None
    assert st["service_model_s"] == st["service_ema_s"]
    # token-fed calls: first sample seeds rate=0.1 s/tok, tokens=10
    s.note_service(1.0, tokens=10)
    st = s.stats()
    assert st["service_per_token_ema_ms"] == pytest.approx(100.0)
    assert st["service_tokens_ema"] == pytest.approx(10.0)
    assert st["service_model_s"] == pytest.approx(1.0)
    # the est-wait model consumes the per-token product, not the raw EMA:
    # depth 1 * model / 1 slot
    _admit_and_enqueue(s, "interactive")
    assert s.est_wait_s() == pytest.approx(st["service_model_s"], rel=1e-6)
    # a short request padded to a full fused tick (0.8 s residency for 8
    # charged steps) keeps the same per-token rate — the model stays ~1 s
    # while the raw per-request EMA is dragged toward the padded residency
    for _ in range(50):
        s.note_service(0.8, tokens=8)
    st = s.stats()
    assert st["service_per_token_ema_ms"] == pytest.approx(100.0, rel=0.02)
    assert st["service_model_s"] == pytest.approx(0.8, rel=0.05)


def test_deadline_expiry_reaped_at_queue_head():
    s = RequestScheduler(SchedulerConfig())
    dead = _stub(deadline_at=time.monotonic() - 0.01)
    live = _stub()
    s.enqueue(dead)
    s.enqueue(live)
    assert s.pop() is live
    with pytest.raises(DeadlineExceeded):
        dead.future.result(timeout=1)
    assert s.stats()["expired_queued"] == {"interactive": 1}
    assert s.queue_depth == 0


def test_reap_drops_dead_entries_mid_queue():
    """reap() (called every engine-loop iteration) fails expired/cancelled
    entries ANYWHERE in the queues — not only at the fair-share head when a
    slot frees — and releases their depth."""
    s = RequestScheduler(SchedulerConfig())
    live_a = _stub()
    dead = _stub(deadline_at=time.monotonic() - 0.01)
    gone = _stub()
    gone.future.cancel()
    live_b = _stub()
    for r in (live_a, dead, gone, live_b):  # dead entries sit BEHIND a live head
        s.enqueue(r)
    assert s.reap() == 2
    with pytest.raises(DeadlineExceeded):
        dead.future.result(timeout=1)
    assert s.queue_depth == 2
    assert s.pop() is live_a and s.pop() is live_b  # order preserved


def test_cancelled_entry_reaped_without_charge():
    s = RequestScheduler(SchedulerConfig())
    gone = _stub()
    gone.future.cancel()
    live = _stub()
    s.enqueue(gone)
    s.enqueue(live)
    assert s.pop() is live
    assert s.stats()["cancelled_queued"] == {"interactive": 1}


def test_degradation_band_clamps_max_tokens():
    s = RequestScheduler(
        SchedulerConfig(max_queue=4, degrade_at=0.5, degrade_max_tokens=16)
    )
    assert s.try_admit("background").clamp_max_tokens is None
    assert not s.degraded()
    adm = s.try_admit("background")  # depth hits 2 = 0.5 * 4
    assert adm.ok and adm.clamp_max_tokens == 16
    assert s.degraded()


def test_direct_enqueue_counts_depth():
    """Requests bypassing try_admit (internal paths writing the engine queue
    directly) must still be depth-accounted."""
    s = RequestScheduler(SchedulerConfig())
    s.enqueue(_stub(admitted=False))
    assert s.queue_depth == 1
    s.pop()
    assert s.queue_depth == 0


def test_wait_stats_percentiles():
    s = RequestScheduler(SchedulerConfig())
    now = time.monotonic()
    for age_s in (0.010, 0.020, 0.100):
        r = _stub()
        r.submitted_at = now - age_s
        s.enqueue(r)
        s.pop(now)
    w = s.wait_stats()["interactive"]
    assert w["n"] == 3
    assert 5 <= w["p50_ms"] <= 50
    assert w["p95_ms"] >= w["p50_ms"]


# --------------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def sched_engine():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    sched = RequestScheduler(SchedulerConfig(max_queue=64, admit_max_wait_s=None))
    eng = GenerationEngine(
        cfg,
        params,
        ByteTokenizer(),
        max_slots=1,
        max_seq_len=256,
        scheduler=sched,
    ).start()
    yield eng
    eng.stop()


def test_engine_interactive_overtakes_background_queue(sched_engine):
    """With one busy slot and a queued background backlog, interactive
    requests jump the queue: they complete before all but the already-running
    background work."""
    eng = sched_engine
    done: list = []

    def tag(name):
        return lambda fut: done.append(name)

    bg = []
    for i in range(5):
        f = eng.submit(
            [1, 2, 3, i + 1], max_tokens=12, temperature=0.0,
            priority="background", tenant="ingest",
        )
        f.add_done_callback(tag(f"bg{i}"))
        bg.append(f)
    ia = []
    for i in range(2):
        f = eng.submit(
            [7, 8, 9, i + 1], max_tokens=6, temperature=0.0,
            priority="interactive", tenant="dialog",
        )
        f.add_done_callback(tag(f"int{i}"))
        ia.append(f)
    for f in bg + ia:
        f.result(timeout=120)
    # both interactive requests finish before the final two background ones
    # (only already-started bg work may precede them)
    assert max(done.index("int0"), done.index("int1")) < min(
        done.index("bg3"), done.index("bg4")
    )


def test_engine_deadline_frees_live_slot_mid_decode(sched_engine):
    """An expired deadline fails the future with DeadlineExceeded AND frees
    the slot promptly (within ~a decode tick) — the request stops burning
    decode work and the next request proceeds."""
    from django_assistant_bot_tpu.serving.faults import FaultInjector

    eng = sched_engine
    # warm: the programs compile here, not inside the deadline below
    eng.submit([1, 2, 3], max_tokens=200, temperature=0.0).result(timeout=120)
    for _ in range(100):
        eng.scheduler.note_service(0.001)  # the deadline passes the admission test
    before = eng.reclaimed_slots
    # a warm jit cache decodes 200 tokens in an unknown share of any deadline
    # taken from the wall clock, racing the expiry this test exists to observe
    # — injected per-tick latency (serving/faults.py slow_tick) pins the
    # request's residency past its deadline: 25 ticks of 8 steps, 30 ms each
    eng._faults = FaultInjector({"slow_tick": {"every": 1, "delay_s": 0.03}})
    try:
        fut = eng.submit([1, 2, 3], max_tokens=200, temperature=0.0, deadline_s=0.25)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=120)
        deadline = time.monotonic() + 10
        while eng.num_active > 0 and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        eng._faults = None
    assert eng.num_active == 0
    assert eng.reclaimed_slots == before + 1
    # engine still healthy
    r = eng.submit([4, 5], max_tokens=3, temperature=0.0).result(timeout=120)
    assert len(r.token_ids) == 3
    stats = eng.tick_stats()
    assert stats["reclaimed_slots"] == before + 1
    assert stats["sched"]["expired_running"].get("interactive", 0) >= 1


def test_engine_queued_deadline_expires_while_slots_saturated(sched_engine):
    """A QUEUED request's deadline fires at ~the deadline even though every
    slot is busy — the engine reaps queue entries each loop iteration instead
    of waiting for a free slot to surface them."""
    eng = sched_engine
    # shrink the service-time EMA so the deadline passes the admission
    # feasibility test (the point here is queue-side expiry, not admission)
    for _ in range(100):
        eng.scheduler.note_service(0.001)
    # a warm jit cache can finish the 220-token blocker inside the 50ms
    # deadline, racing the expiry this test exists to observe — injected
    # per-tick latency (serving/faults.py slow_tick) pins the blocker's
    # residency deterministically past the queued request's deadline
    from django_assistant_bot_tpu.serving.faults import FaultInjector

    eng._faults = FaultInjector({"slow_tick": {"every": 1, "delay_s": 0.01}})
    try:
        blocker = eng.submit([1, 2, 3], max_tokens=220, temperature=0.0)
        queued = eng.submit(
            [4, 5, 6], max_tokens=10, temperature=0.0, deadline_s=0.05
        )
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            queued.result(timeout=30)
        # failed promptly (well before the blocker's full decode), not on dequeue
        assert time.monotonic() - t0 < 5.0
        blocker.result(timeout=120)  # the running request is unaffected
    finally:
        eng._faults = None


def test_engine_submit_sheds_past_bound():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(1))
    sched = RequestScheduler(SchedulerConfig(max_queue=1, admit_max_wait_s=None))
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=1, max_seq_len=96, scheduler=sched
    )
    # NOT started: everything submitted stays queued, so the bound is exact
    try:
        eng._running = True  # let submit() enqueue without an engine thread
        eng.submit([1, 2], max_tokens=4)
        with pytest.raises(SchedulerRejected) as ei:
            eng.submit([1, 2], max_tokens=4)
        assert ei.value.retry_after_s > 0
    finally:
        eng._running = False
        eng.stop()


# ----------------------------------------------------------- HTTP integration
@pytest.fixture(scope="module")
def sched_registry():
    registry = ModelRegistry.from_config(
        {
            "sched-chat": {
                "kind": "decoder",
                "tiny": True,
                "dtype": "float32",
                "max_slots": 1,
                "max_seq_len": 128,
                "sched_max_queue": 1,
                "sched_admit_max_wait_s": None,
            },
            "tiny-emb": {"kind": "encoder", "tiny": True, "dtype": "float32"},
        }
    )
    yield registry
    registry.stop()


def _drive(registry, fn):
    async def runner():
        from aiohttp.test_utils import TestClient, TestServer

        app = create_app(registry)
        # the module fixture owns the registry; closing one test's client
        # must not stop the shared engines (create_app's on_cleanup would)
        app.on_cleanup.clear()
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


def test_dialog_validation_422(sched_registry):
    async def body(client):
        base = {"model": "sched-chat", "messages": [{"role": "user", "content": "x"}]}
        bad = [
            {"temperature": math.nan},
            {"temperature": -0.5},
            {"temperature": 99.0},
            {"temperature": "hot"},
            {"top_p": 0.0},
            {"top_p": 2.0},
            {"top_p": math.inf},
            {"max_tokens": 0},
            {"max_tokens": -5},
            {"max_tokens": 1 << 20},
            {"max_tokens": 3.7},
            {"max_tokens": True},
            {"priority": "vip"},
            {"tenant": ""},
            {"tenant": "x" * 200},
            {"deadline_s": -1},
            {"deadline_s": math.nan},
            {"deadline_s": 7200},
        ]
        for extra in bad:
            resp = await client.post("/dialog/", json={**base, **extra})
            assert resp.status == 422, (extra, await resp.text())
        # valid edge values still pass
        resp = await client.post(
            "/dialog/",
            json={
                **base,
                "temperature": 0.0,
                "top_p": 1.0,
                "max_tokens": 2,
                "priority": "background",
                "tenant": "ws1",
                "deadline_s": 30,
            },
        )
        assert resp.status == 200, await resp.text()

    _drive(sched_registry, body)


def test_dialog_shed_maps_to_429_with_retry_after_and_healthz(sched_registry):
    """Overload: 1 slot + queue bound 1 -> concurrent burst sheds with 429 +
    Retry-After; /healthz exposes depth/shed counters and per-class waits."""

    async def body(client):
        async def one(i):
            return await client.post(
                "/dialog/",
                json={
                    "model": "sched-chat",
                    "messages": [{"role": "user", "content": f"q{i}"}],
                    "max_tokens": 64,
                    "priority": "background",
                },
            )
        resps = await asyncio.gather(*(one(i) for i in range(10)))
        statuses = [r.status for r in resps]
        assert statuses.count(200) >= 1
        shed = [r for r in resps if r.status == 429]
        assert shed, statuses
        for r in shed:
            assert int(r.headers["Retry-After"]) >= 1
            data = await r.json()
            assert data["retry_after_s"] > 0 and data["reason"]
        health = await (await client.get("/healthz")).json()
        g = health["generators"]["sched-chat"]
        sched = g["sched"]
        assert sched["max_queue"] == 1
        assert sum(sched["shed"].values()) >= len(shed)
        assert "queue_depth" in sched and "wait" in sched
        assert any(w["n"] > 0 for w in sched["wait"].values())
        assert "reclaimed_slots" in g
        emb = health["embedders"]["tiny-emb"]
        assert {"queue_depth", "max_queue", "shed", "dropped_cancelled"} <= set(emb)

    _drive(sched_registry, body)


# ------------------------------------------------------- embedding coalescer
def test_embedding_queue_bound_sheds():
    from django_assistant_bot_tpu.models import EncoderConfig, encoder
    from django_assistant_bot_tpu.serving import EmbeddingEngine

    cfg = EncoderConfig.tiny()
    params = encoder.init(cfg, jax.random.key(0))
    eng = EmbeddingEngine(cfg, params, ByteTokenizer(), max_queue=1)
    eng._running = True  # no coalescer thread: the queue must fill
    try:
        async def drive():
            t1 = asyncio.ensure_future(eng.embed(["a"]))
            await asyncio.sleep(0.01)
            with pytest.raises(SchedulerRejected):
                await eng.embed(["b"])
            t1.cancel()
            return True

        assert asyncio.run(drive())
        assert eng.shed == 1
    finally:
        eng._running = False
        eng.stop()


def test_embedding_coalescer_drops_cancelled_futures():
    from django_assistant_bot_tpu.models import EncoderConfig, encoder
    from django_assistant_bot_tpu.serving import EmbeddingEngine

    cfg = EncoderConfig.tiny()
    params = encoder.init(cfg, jax.random.key(0))
    eng = EmbeddingEngine(cfg, params, ByteTokenizer())
    cancelled: Future = Future()
    cancelled.cancel()
    live: Future = Future()
    eng._queue.put((["dead text"], cancelled))
    eng._queue.put((["live text"], live))
    eng.start()
    try:
        embs = live.result(timeout=60)
        assert len(embs) == 1 and len(embs[0]) == cfg.hidden_size
        assert eng.dropped_cancelled == 1
    finally:
        eng.stop()


# ------------------------- predictive admission (queue-wait histogram, PR 11)
def test_histogram_quantile_interpolates_and_caps():
    from django_assistant_bot_tpu.serving import Histogram

    h = Histogram((0.1, 1.0, 10.0))
    assert h.quantile(0.95) == 0.0  # empty = cold, callers gate on .count
    for _ in range(90):
        h.observe(0.05)  # le 0.1 bucket
    for _ in range(10):
        h.observe(5.0)  # (1.0, 10.0] bucket
    q50 = h.quantile(0.5)
    assert 0.0 < q50 <= 0.1
    q95 = h.quantile(0.95)
    assert 1.0 < q95 <= 10.0
    # +Inf bucket values report the largest finite bound (a deliberate
    # under-estimate: predictions must stay actionable)
    h2 = Histogram((0.1, 1.0))
    h2.observe(99.0)
    assert h2.quantile(0.99) == 1.0


def test_warm_wait_histogram_floors_estimated_wait_and_retry():
    """The point-EMA model underestimates the tail; once the bound queue-wait
    histogram is warm, the estimated wait (and the 429 Retry-After derived
    from it) is floored by the configured quantile of realized waits."""
    from django_assistant_bot_tpu.serving import Histogram

    s = RequestScheduler(
        SchedulerConfig(
            max_queue=100,
            admit_max_wait_s=5.0,
            service_time_init=0.01,  # the EMA model predicts ~nothing
            admit_wait_quantile=0.95,
            admit_hist_min_samples=8,
        ),
        slots=1,
    )
    h = Histogram((0.1, 1.0, 10.0, 30.0))
    s.bind_wait_hist(h)
    # cold histogram: the EMA model alone drives the estimate
    _admit_and_enqueue(s)
    assert s.stats()["est_wait_source"] == "ema"
    assert s.est_wait_s() < 0.1
    # warm it with a heavy observed tail (queue waits ~8s)
    for _ in range(16):
        h.observe(8.0)
    st = s.stats()
    assert st["est_wait_source"] == "histogram"
    assert s.est_wait_s() > 1.0  # the measured tail floors the model
    # and the shed decision + Retry-After hint follow the SAME prediction:
    # est > admit_max_wait_s -> shed, with retry ~= the predicted wait
    adm = s.try_admit("interactive")
    assert not adm.ok and adm.reason == "estimated_wait"
    assert adm.retry_after_s == pytest.approx(s.est_wait_s(), rel=0.35)
    # empty queue: nothing ahead of the request, no histogram floor applies
    s2 = RequestScheduler(
        SchedulerConfig(admit_hist_min_samples=8), slots=1
    )
    s2.bind_wait_hist(h)
    assert s2.est_wait_s() == 0.0


def test_engine_binds_queue_wait_histogram_into_scheduler():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    sched = RequestScheduler(SchedulerConfig(admit_hist_min_samples=4))
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
        scheduler=sched,
    )
    assert sched._wait_hist is eng.obs.queue_wait_s
    # obs=False: no histogram exists, the EMA path stays
    sched2 = RequestScheduler(SchedulerConfig())
    GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
        scheduler=sched2, obs=False,
    )
    assert sched2._wait_hist is None


def test_degrade_override_clamps_and_reports():
    """The autoscaler's load-shaping actuator: set_degrade forces the band on
    (max_tokens clamp at admission + degraded() True, which the engine reads
    as 'skip speculative verify forwards') independent of queue pressure."""
    s = RequestScheduler(SchedulerConfig(max_queue=100, degrade_at=1.0))
    assert not s.degraded()
    s.set_degrade(64)
    assert s.degraded()
    adm = s.try_admit("interactive")
    assert adm.ok and adm.clamp_max_tokens == 64
    st = s.stats()
    assert st["degraded"] is True and st["degrade_forced"] is True
    s.set_degrade(None)
    assert not s.degraded()
    assert s.try_admit("interactive").clamp_max_tokens is None
    # the band clamp and the override compose: the tighter one wins
    s3 = RequestScheduler(
        SchedulerConfig(max_queue=4, degrade_at=0.25, degrade_max_tokens=128)
    )
    s3.set_degrade(32)
    _admit_and_enqueue(s3)
    adm = s3.try_admit("interactive")
    assert adm.clamp_max_tokens == 32


def test_wait_histogram_floor_is_windowed_not_lifetime():
    """A past overload's tail must roll OUT of the prediction: after two
    window rotations of fast traffic, the quantile floor tracks the recent
    regime, not the process lifetime (a stale ~8s Retry-After at light load
    was the bug)."""
    from django_assistant_bot_tpu.serving import Histogram

    window = 32
    s = RequestScheduler(
        SchedulerConfig(
            service_time_init=0.01,
            admit_wait_quantile=0.95,
            admit_hist_min_samples=8,
            admit_hist_window=window,
        ),
        slots=1,
    )
    h = Histogram((0.1, 1.0, 10.0, 30.0))
    s.bind_wait_hist(h)
    _admit_and_enqueue(s)  # depth > 0 so the floor applies
    for _ in range(16):
        h.observe(8.0)  # the overload period
    assert s.est_wait_s() > 1.0
    # two full windows of fast traffic rotate the slow tail out entirely
    # (rotation happens inside the admission-path checks, so interleave the
    # reads the way live admissions would)
    for _ in range(2 * window):
        h.observe(0.05)
        s.est_wait_s()
    assert s.est_wait_s() < 0.2
    assert s.stats()["est_wait_source"] == "histogram"  # still warm
