"""Multi-replica serving plane (serving/router.py; docs/RESILIENCE.md "Fleet
topology"): health- and prefix-affinity-aware dispatch over N supervised
engine replicas, per-replica circuit breakers, token-less re-route on replica
death, graceful drain / rolling restart, and the SIGTERM whole-server drain.

Everything runs on CPU with tiny random models; chaos is exact (armed or
fire-on-Nth fault schedules, an injectable drain clock) — no sleep-and-hope
assertions on the failover paths.
"""

import asyncio
import threading
import time

import pytest

import jax

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.serving import (
    ByteTokenizer,
    EngineRouter,
    EngineUnavailable,
    FaultInjector,
    GenerationEngine,
    ModelRegistry,
    SchedulerRejected,
)
from django_assistant_bot_tpu.serving.server import DRAIN_KEY, create_app


def _params(seed=1):
    cfg = DecoderConfig.tiny()
    return cfg, llama.init(cfg, jax.random.key(seed))


def _engines(n=2, cfg=None, params=None, **kw):
    """N replicas over ONE shared weight tree (the registry's layout)."""
    if cfg is None:
        cfg, params = _params()
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 64)
    return cfg, [
        GenerationEngine(cfg, params, ByteTokenizer(), **kw).start()
        for _ in range(n)
    ]


class _FakeClock:
    """Deterministic drain clock: time advances ONLY through sleep(), which
    also yields a bounded slice of real time so engine threads progress."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += dt
        time.sleep(min(dt, 0.005))


# ------------------------------------------------------------------ dispatch
def test_router_spreads_load_and_serves():
    _, engines = _engines(2)
    r = EngineRouter(engines)
    try:
        futs = [
            r.submit([1, 2, 3 + i], max_tokens=4, temperature=0.0)
            for i in range(8)
        ]
        for f in futs:
            assert len(f.result(timeout=120).token_ids) == 4
        stats = r.router_stats()
        # least-loaded + rotation: a healthy 2-replica fleet must not pin
        # every request onto one engine
        assert all(p["dispatched"] > 0 for p in stats["replicas"])
        assert stats["reroutes"] == 0
        assert r.supervision_stats()["healthy"] is True
    finally:
        r.stop()


def test_router_prefix_affinity_routes_to_registry_holder():
    """A prompt whose shared prefix is already registered in one replica's KV
    page pool must route there (docs/RESILIENCE.md: affinity below health) —
    and the affinity gauges must record it."""
    cfg, engines = _engines(2, prefix_min_tokens=8)
    r = EngineRouter(engines)
    try:
        prefix = list(range(1, 17))  # 16 tokens >= prefix_min_tokens
        first = r.submit(
            prefix + [40, 41, 42], max_tokens=2, temperature=0.0, prefix_len=16
        )
        first.result(timeout=120)
        holders = [
            i for i, e in enumerate(engines) if e.holds_prefix(prefix + [99], 16)
        ]
        assert len(holders) == 1  # registered exactly where it prefillled
        holder = holders[0]
        before = r.replicas[holder].dispatched
        for i in range(3):
            f = r.submit(
                prefix + [50 + i], max_tokens=2, temperature=0.0, prefix_len=16
            )
            f.result(timeout=120)
        assert r.replicas[holder].dispatched == before + 3
        assert r.affinity_hits >= 3
        # a holder skipped for drain/health reasons is a MISS: the request
        # re-prefills elsewhere and the gauge must say so, not claim a hit
        hits_before, misses_before = r.affinity_hits, r.affinity_misses
        r.replicas[holder].draining = True
        r.submit(
            prefix + [90], max_tokens=2, temperature=0.0, prefix_len=16
        ).result(timeout=120)
        r.replicas[holder].draining = False
        assert r.affinity_hits == hits_before
        assert r.affinity_misses == misses_before + 1
        # the in-process provider reads the context contract off the router
        assert r.max_seq_len == 64
    finally:
        r.stop()


def test_router_shed_propagates_when_every_replica_sheds():
    from django_assistant_bot_tpu.serving.scheduler import (
        RequestScheduler,
        SchedulerConfig,
    )

    cfg, params = _params()
    engines = [
        GenerationEngine(
            cfg,
            params,
            ByteTokenizer(),
            max_slots=2,
            max_seq_len=64,
            scheduler=RequestScheduler(SchedulerConfig(max_queue=0)),
        ).start()
        for _ in range(2)
    ]
    r = EngineRouter(engines)
    try:
        with pytest.raises(SchedulerRejected) as ei:
            r.submit([1, 2, 3], max_tokens=2)
        assert ei.value.retry_after_s > 0
        # shed is pressure, not a fault: no breaker opened
        assert all(p.breaker.state == "closed" for p in r.replicas)
    finally:
        r.stop()


def test_router_no_healthy_replica_raises_unavailable():
    _, engines = _engines(2)
    r = EngineRouter(engines)
    try:
        for e in engines:
            e._degraded_until = time.monotonic() + 30.0
        with pytest.raises(EngineUnavailable):
            r.submit([1, 2, 3], max_tokens=2)
        assert r.no_replica_available == 1
        for e in engines:
            e._degraded_until = None
        assert (
            len(r.submit([1, 2, 3], max_tokens=2, temperature=0.0)
                .result(timeout=120).token_ids)
            == 2
        )
    finally:
        r.stop()


# ------------------------------------------------------------- replica death
def _stall(engine, delay_s=0.1, fires=16):
    """Arm slow_tick so the engine's loop holds work in flight (lookahead
    keeps the sampled tokens on device, so requests stay client-token-less)."""
    inj = engine._faults
    assert inj is not None
    inj.arm("slow_tick", fires)
    with inj._lock:
        inj._sites["slow_tick"].delay_s = delay_s


def test_replica_kill_reroutes_tokenless_requests_goodput_one():
    """The acceptance contract: one of two replicas dies with queued and
    in-flight (token-less) work — every request completes on the survivor,
    the dead replica's breaker opens, and the fleet reports degraded."""
    cfg, params = _params()
    engines = [
        GenerationEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
            faults=FaultInjector({}),
        ).start()
        for _ in range(2)
    ]
    # threshold 2, not the default 3: least-loaded dispatch sends ~3 of the
    # 6 requests to the doomed replica, but on a loaded CI host one can
    # finish inside the stall window before the kill — 2 re-routed failures
    # must still open the breaker or this test flakes under load
    r = EngineRouter(engines, breaker_threshold=2, breaker_reset_s=0.2)
    try:
        for i in range(2):  # warm both replicas (compiles out of the way)
            r.submit([1, 2, 3 + i], max_tokens=2, temperature=0.0).result(
                timeout=120
            )
        _stall(engines[0])
        _stall(engines[1])
        futs = [
            r.submit([5, 6, 7 + i], max_tokens=6, temperature=0.0)
            for i in range(6)
        ]
        time.sleep(0.05)  # inside the stalled first ticks: no host tokens yet
        r.kill_replica(0)
        for f in futs:
            assert len(f.result(timeout=120).token_ids) == 6  # goodput 1.0
        assert r.reroutes > 0
        assert r.rerouted_failed == 0
        assert r.failed_past_first_token == 0
        assert r.replicas[0].breaker.state in ("open", "half_open")
        sup = r.supervision_stats()
        assert sup["healthy"] is False  # one dead replica degrades the fleet
        assert sup["replicas"][0]["healthy"] is False
        # operator restart: the fleet heals
        r.restart_replica(0)
        assert r.supervision_stats()["healthy"] is True
        assert (
            len(
                r.submit([9, 9, 9], max_tokens=3, temperature=0.0)
                .result(timeout=120)
                .token_ids
            )
            == 3
        )
    finally:
        r.stop()


def test_router_stream_past_first_delta_fails_cleanly():
    """Mirror of the single-engine restart contract at fleet level: once a
    stream has emitted a delta, a replica death fails the request (no replay
    on another replica — the client would see divergent text)."""
    _, engines = _engines(2)
    r = EngineRouter(engines, breaker_reset_s=0.2)
    r.replicas[1].draining = True  # pin dispatch onto replica0

    async def go():
        agen = r.generate_stream("hello", max_tokens=48, temperature=0.0)
        first = await agen.__anext__()
        assert first.token_id is not None
        r.kill_replica(0)
        with pytest.raises(RuntimeError):
            async for _ in agen:
                pass

    try:
        asyncio.run(go())
        assert r.failed_past_first_token == 1
        assert r.reroutes == 0
        r.replicas[1].draining = False
        res = r.submit([1, 2, 3], max_tokens=3, temperature=0.0).result(
            timeout=120
        )
        assert len(res.token_ids) == 3
    finally:
        r.stop()


def test_replica_dead_fault_site_exercises_failover():
    """The replica_dead chaos site kills the replica the dispatcher is about
    to pick — the request lands on the survivor, nothing is lost."""
    cfg, params = _params()
    engines = [
        GenerationEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64
        ).start()
        for _ in range(2)
    ]
    inj = FaultInjector(
        {"replica_dead": {"fire_on": [3]}, "replica_slow": {"fire_on": [1], "delay_s": 0.01}}
    )
    r = EngineRouter(engines, faults=inj, breaker_reset_s=0.2)
    try:
        futs = [
            r.submit([1, 2, 3 + i], max_tokens=3, temperature=0.0)
            for i in range(4)
        ]
        for f in futs:
            assert len(f.result(timeout=120).token_ids) == 3
        assert inj.stats()["replica_dead"]["fires"] == 1
        assert inj.stats()["replica_slow"]["fires"] == 1
        assert sum(not e._running for e in engines) == 1
    finally:
        r.stop()


def test_reroute_preserves_remaining_deadline():
    """A re-routed request must carry its REMAINING deadline budget, not a
    fresh one per hop (the single-engine salvage keeps the original
    deadline_at — the fleet contract matches): an exhausted budget at
    re-route time is a DeadlineExceeded, and a live one is passed through
    shrunk."""
    from concurrent.futures import Future

    from django_assistant_bot_tpu.serving.router import _Routed, _StreamShim
    from django_assistant_bot_tpu.serving.scheduler import DeadlineExceeded

    _, engines = _engines(2)
    r = EngineRouter(engines)

    def routed(deadline_s, deadline_at):
        state = _Routed(
            [1, 2, 3],
            dict(
                max_tokens=2,
                temperature=0.0,
                top_p=0.9,
                json_format=False,
                prefix_len=0,
                priority="interactive",
                tenant="default",
                deadline_s=deadline_s,
            ),
            Future(),
            _StreamShim(None),
        )
        state.deadline_at = deadline_at
        failed = Future()
        failed.set_exception(RuntimeError("generation engine stopped"))
        return state, failed

    try:
        # budget already gone: no fresh attempt, the client gets its 504
        state, failed = routed(0.2, time.monotonic() - 1.0)
        r._on_inner_done(state, r.replicas[0], failed)
        assert isinstance(state.outer.exception(timeout=10), DeadlineExceeded)
        assert r.reroutes == 0
        # budget remaining: the hop happens with the SHRUNK deadline
        state, failed = routed(100.0, time.monotonic() + 30.0)
        r._on_inner_done(state, r.replicas[0], failed)
        assert state.outer.result(timeout=120).token_ids
        assert r.reroutes == 1
        assert state.kwargs["deadline_s"] <= 30.0
    finally:
        r.stop()


# -------------------------------------------------------------------- drain
def test_rolling_restart_under_live_traffic_sheds_nothing():
    """The zero-downtime acceptance contract: drain + restart every replica
    while requests keep flowing — every future completes, zero requests shed
    attributable to the drain, and both engine loops really restarted."""
    cfg, params = _params()
    engines = [
        GenerationEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
            faults=FaultInjector({}),
        ).start()
        for _ in range(2)
    ]
    clock = _FakeClock()
    r = EngineRouter(engines, clock=clock, sleep=clock.sleep)
    try:
        for i in range(2):
            r.submit([1, 2, 3 + i], max_tokens=2, temperature=0.0).result(
                timeout=120
            )
        threads_before = [e._thread for e in engines]
        _stall(engines[0], delay_s=0.05, fires=8)
        _stall(engines[1], delay_s=0.05, fires=8)
        futs = [
            r.submit([5, 6, 7 + i], max_tokens=4, temperature=0.0)
            for i in range(6)
        ]
        reports = []
        rr = threading.Thread(
            target=lambda: reports.extend(r.rolling_restart(deadline_s=1e9))
        )
        rr.start()
        # live traffic THROUGH the rolling restart
        while rr.is_alive():
            futs.append(r.submit([8, 9], max_tokens=2, temperature=0.0))
            time.sleep(0.01)
        rr.join(timeout=120)
        for f in futs:
            assert f.exception(timeout=120) is None
        assert len(reports) == 2
        assert all(rep["drained"] for rep in reports)
        assert all(rep["forced_failures"] == 0 for rep in reports)
        assert r.drain_shed == 0
        assert r.drains == 2
        # both loops are NEW threads (a real restart, not a no-op)
        assert all(
            e._thread is not old for e, old in zip(engines, threads_before)
        )
        assert r.supervision_stats()["healthy"] is True
    finally:
        r.stop()


def test_drain_deadline_forces_and_counts_shed():
    """A deadline of zero with work in flight force-restarts: the drain
    reports the forced failures honestly, and every victim follows the
    fleet contract — token-less requests re-route to the survivor (no
    client-visible failure), requests past their first token fail cleanly."""
    cfg, params = _params()
    engines = [
        GenerationEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
            faults=FaultInjector({}),
        ).start()
        for _ in range(2)
    ]
    clock = _FakeClock()
    r = EngineRouter(engines, clock=clock, sleep=clock.sleep, breaker_reset_s=0.2)
    try:
        for i in range(2):
            r.submit([1, 2, 3 + i], max_tokens=2, temperature=0.0).result(
                timeout=120
            )
        r.replicas[1].draining = True  # pin the trace onto replica0
        # a second a tick: the three requests need three or four ticks, so
        # replica0 holds work for seconds, however late this thread gets to
        # drain() under the suite's six workers (at 0.2 s the trace could be
        # over first, and the drain then reads "drained")
        _stall(engines[0], delay_s=1.0, fires=8)
        futs = [
            r.submit([5, 6, 7 + i], max_tokens=4, temperature=0.0)
            for i in range(3)
        ]
        r.replicas[1].draining = False
        time.sleep(0.02)
        report = r.drain(0, deadline_s=0.0)
        assert report["drained"] is False
        assert report["forced_failures"] > 0
        assert r.drain_shed == report["forced_failures"]
        ok = failed = 0
        for f in futs:
            if f.exception(timeout=120) is None:
                ok += 1
            else:
                failed += 1
        # token-less victims survived via re-route; only requests already
        # past their first client-visible token may fail — and each such
        # failure is accounted for
        assert failed == r.failed_past_first_token
        assert r.rerouted_failed == 0
        assert ok + failed == len(futs)
        assert ok > 0  # at least the queued (token-less) work survived
    finally:
        r.stop()


def test_drain_rejects_concurrent_drain_of_same_replica():
    _, engines = _engines(1)
    r = EngineRouter(engines)
    try:
        r.replicas[0].draining = True
        with pytest.raises(RuntimeError, match="already draining"):
            r.drain(0)
        r.replicas[0].draining = False
    finally:
        r.stop()


# ----------------------------------------------------- registry + HTTP plane
@pytest.fixture()
def replica_registry():
    registry = ModelRegistry.from_config(
        {
            "tiny-chat": {
                "kind": "decoder",
                "tiny": True,
                "max_slots": 2,
                "max_seq_len": 64,
                "replicas": 2,
                "router_breaker_reset_s": 0.2,
            }
        }
    )
    yield registry
    registry.stop()


def test_registry_builds_router_only_past_one_replica():
    registry = ModelRegistry.from_config(
        {"tiny-chat": {"kind": "decoder", "tiny": True, "max_slots": 2,
                       "max_seq_len": 64}}
    )
    try:
        # replicas=1 (default): the plain engine, byte-identical serving path
        assert isinstance(registry.get_generator("tiny-chat"), GenerationEngine)
    finally:
        registry.stop()
    with pytest.raises(ValueError, match="replicas"):
        ModelRegistry.from_config(
            {"emb": {"kind": "encoder", "tiny": True, "replicas": 2}}
        )


def _run_with_client(registry, go, **app_kw):
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        client = TestClient(TestServer(create_app(registry, **app_kw)))
        await client.start_server()
        try:
            await go(client)
        finally:
            await client.close()

    asyncio.run(main())


def test_router_registry_serves_and_healthz_aggregates(replica_registry):
    router = replica_registry.get_generator("tiny-chat")
    assert isinstance(router, EngineRouter)
    assert len(router.replicas) == 2

    async def go(client):
        resp = await client.post(
            "/dialog/",
            json={
                "model": "tiny-chat",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 2,
            },
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["response"]["usage"]["completion_tokens"] >= 1

        resp = await client.get("/healthz")
        data = await resp.json()
        assert data["status"] == "ok"
        g = data["generators"]["tiny-chat"]
        assert g["router"]["n_replicas"] == 2
        assert len(g["router"]["replicas"]) == 2
        assert len(g["supervision"]["replicas"]) == 2
        assert g["kv"]["kv_pages_total"] > 0 and len(g["kv"]["replicas"]) == 2
        assert g["decode"]["decode_kv_path"] == "xla"  # CPU replicas: the plain path

        # one dead replica of two: the fleet reports degraded with the dead
        # replica identifiable, but /dialog/ keeps serving from the survivor
        router.kill_replica(0)
        resp = await client.get("/healthz")
        data = await resp.json()
        assert data["status"] == "degraded"
        per = data["generators"]["tiny-chat"]["supervision"]["replicas"]
        assert [p["healthy"] for p in per].count(False) == 1
        resp = await client.post(
            "/dialog/",
            json={
                "model": "tiny-chat",
                "messages": [{"role": "user", "content": "still here?"}],
                "max_tokens": 2,
            },
        )
        assert resp.status == 200
        router.restart_replica(0)
        resp = await client.get("/healthz")
        assert (await resp.json())["status"] == "ok"

    _run_with_client(replica_registry, go)


def test_server_graceful_drain_finishes_inflight_then_503s():
    """The SIGTERM contract (cli serve --drain-deadline-s): once draining,
    admission 503s with Retry-After and /healthz says so; on shutdown the
    server waits for accepted work, so in-flight futures complete instead of
    dying with the process."""
    registry = ModelRegistry.from_config(
        {
            "tiny-chat": {
                "kind": "decoder",
                "tiny": True,
                "max_slots": 2,
                "max_seq_len": 64,
                "faults": {"slow_tick": {"every": 1, "delay_s": 0.05,
                                         "max_fires": 10}},
            }
        }
    )
    eng = registry.get_generator("tiny-chat")
    held = {}

    async def go(client):
        # work accepted BEFORE the drain begins
        held["fut"] = eng.submit([1, 2, 3], max_tokens=4, temperature=0.0)
        client.app[DRAIN_KEY]["draining"] = True
        resp = await client.post(
            "/dialog/",
            json={
                "model": "tiny-chat",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 2,
            },
        )
        assert resp.status == 503
        assert "Retry-After" in resp.headers
        resp = await client.post(
            "/embeddings/", json={"model": "x", "texts": ["a"]}
        )
        assert resp.status == 503
        resp = await client.get("/healthz")
        assert (await resp.json())["status"] == "draining"
        client.app[DRAIN_KEY]["draining"] = False
        # client.close() tears the server down: on_shutdown flips the drain
        # flag and waits for registry.idle() before on_cleanup stops engines

    try:
        _run_with_client(registry, go, drain_deadline_s=30.0)
        fut = held["fut"]
        assert fut.done()
        assert fut.exception() is None
        assert len(fut.result().token_ids) == 4
    finally:
        registry.stop()


# ------------------------------------------------- a context no page divides
def test_context_no_page_divides_is_refused_at_boot():
    """Speculative engines serve from the page pool like every other, and a
    prime-length context — no page size divides it — is refused at boot with
    the number named, where it once silently served from another cache."""
    cfg, params = _params()
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
        speculative=2,
    )
    assert eng.kv_page_size == 32
    assert eng.tick_stats()["kv"]["kv_pages_total"] == 4

    with pytest.raises(ValueError, match=r"max_seq_len=61 .*kv_page_size=0"):
        GenerationEngine(cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=61)
