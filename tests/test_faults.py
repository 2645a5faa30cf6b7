"""Resilience plane: deterministic fault injection + the recovery paths it
exercises (docs/RESILIENCE.md).

Covers: injector determinism and inertness-when-off, request-poison quarantine
vs engine-fatal crash-only restart (queued work preserved, no-token requests
re-submitted, streams past first delta failed cleanly), the restart circuit
(degraded engine -> EngineUnavailable -> HTTP 503 + Retry-After, /healthz
status + loop heartbeat), provider failover with per-backend circuit breakers,
and the HTTP client's connection-error/503/Retry-After retry policy.

Everything runs on CPU with tiny random models and exact fire-on-Nth (or
armed) fault schedules — no sleep-and-hope timing, no network.
"""

import asyncio
import time
from email.utils import format_datetime

import pytest

import jax

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.serving import (
    ByteTokenizer,
    EngineUnavailable,
    FaultInjected,
    FaultInjector,
    GenerationEngine,
    ModelRegistry,
    RequestPoisoned,
)
from django_assistant_bot_tpu.serving.faults import (
    global_injector,
    reset_global_injector,
    set_global_injector,
)
from django_assistant_bot_tpu.serving.server import create_app


def _tiny_engine(seed=1, faults=None, **kw):
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(seed))
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 64)
    return GenerationEngine(cfg, params, ByteTokenizer(), faults=faults, **kw)


# --------------------------------------------------------------- the injector
def test_injector_fire_on_is_exact():
    inj = FaultInjector({"tick_raise": {"fire_on": [2, 5]}})
    pattern = [inj.should_fire("tick_raise") for _ in range(6)]
    assert pattern == [False, True, False, False, True, False]
    assert inj.stats()["tick_raise"] == {"calls": 6, "fires": 2}


def test_injector_every_and_max_fires():
    inj = FaultInjector({"slow_tick": {"every": 3, "max_fires": 2, "delay_s": 0.0}})
    pattern = [inj.should_fire("slow_tick") for _ in range(12)]
    assert pattern == [False, False, True, False, False, True] + [False] * 6


def test_injector_probability_deterministic_per_seed():
    spec = {"conn_reset": {"p": 0.3}}
    # same seed -> identical pattern over many calls
    i1, i2 = FaultInjector(spec, seed=7), FaultInjector(spec, seed=7)
    p1 = [i1.should_fire("conn_reset") for _ in range(200)]
    p2 = [i2.should_fire("conn_reset") for _ in range(200)]
    assert p1 == p2
    assert 20 < sum(p1) < 120  # the stream actually fires at roughly p
    # a different seed produces a different pattern
    i3 = FaultInjector(spec, seed=8)
    assert [i3.should_fire("conn_reset") for _ in range(200)] != p1


def test_injector_site_isolation():
    """One site's call pattern must not perturb another's draws."""
    solo = FaultInjector({"timeout": {"p": 0.5}}, seed=3)
    both = FaultInjector({"timeout": {"p": 0.5}, "http_5xx": {"p": 0.5}}, seed=3)
    pattern_solo = []
    pattern_both = []
    for _ in range(100):
        pattern_solo.append(solo.should_fire("timeout"))
        both.should_fire("http_5xx")  # interleaved draws on the other site
        pattern_both.append(both.should_fire("timeout"))
    assert pattern_solo == pattern_both


def test_injector_rejects_unknown_sites_and_bad_specs():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector({"tick_rise": 0.5})  # typo must not silently no-op
    with pytest.raises(ValueError, match="unknown keys"):
        FaultInjector({"tick_raise": {"fire_after": 3}})
    with pytest.raises(ValueError, match="probability"):
        FaultInjector({"tick_raise": 1.5})
    assert FaultInjector.from_spec(None) is None
    assert FaultInjector.from_spec({}) is None


def test_injector_window_models_link_state():
    """start_after_s/duration_s model a PARTITION: the site holds for the
    whole window (elapsed from first consult, on the injectable clock) and
    releases after — max_fires never truncates a window."""
    t = [0.0]
    inj = FaultInjector(
        {"net_partition": {"start_after_s": 5.0, "duration_s": 3.0, "max_fires": 1}},
        clock=lambda: t[0],
    )
    assert not inj.should_fire("net_partition")  # stamps first consult at 0
    t[0] = 4.9
    assert not inj.should_fire("net_partition")
    for now in (5.0, 6.5, 7.9):  # window holds, max_fires=1 notwithstanding
        t[0] = now
        assert inj.should_fire("net_partition")
    t[0] = 8.0  # heal: start_after + duration reached
    assert not inj.should_fire("net_partition")
    # a window needs a duration — a partition that never heals is a typo
    with pytest.raises(ValueError, match="duration_s"):
        FaultInjector({"net_partition": {"start_after_s": 1.0}})


def test_injector_edges_scope_sites_to_keys():
    """A spec's edges list scopes the site to those consult keys; other
    edges never fire (and edges must be a list, not a bare string)."""
    inj = FaultInjector({"net_drop": {"fire_on": [1, 2], "edges": ["r->a"]}})
    assert not inj.should_fire("net_drop", "r->b")
    assert inj.should_fire("net_drop", "r->a")
    assert inj.should_fire("net_drop", "r->a")
    assert not inj.should_fire("net_drop", "r->a")
    with pytest.raises(ValueError, match="edges"):
        FaultInjector({"net_drop": {"edges": "r->a"}})


def test_injector_per_edge_streams_deterministic():
    """Each edge draws from its own str-seeded RNG: the same seed replays
    the same per-edge schedule regardless of how OTHER edges' consults
    interleave — what makes a two-process chaos bench replayable."""
    spec = {"net_drop": {"p": 0.5}}
    i1, i2 = FaultInjector(spec, seed=5), FaultInjector(spec, seed=5)
    pa = [i1.should_fire("net_drop", "x->a") for _ in range(100)]
    pb = []
    for _ in range(100):
        i2.should_fire("net_drop", "x->b")  # interleaved other-edge consults
        pb.append(i2.should_fire("net_drop", "x->a"))
    assert pa == pb
    # distinct edges follow distinct (still deterministic) schedules
    i3 = FaultInjector(spec, seed=5)
    assert [i3.should_fire("net_drop", "x->b") for _ in range(100)] != pa


def test_injector_arm_with_key_and_edge_stats():
    """arm(site, key=...) auto-registers the site and arms ONE edge's
    substate; the edge appears as a site[key] row in stats() — the chaos
    bench's injected-vs-rejected accounting reads those rows."""
    inj = FaultInjector({})
    inj.arm("net_corrupt", 2, key="probe")
    assert not inj.should_fire("net_corrupt", "other")  # other edges inert
    assert inj.should_fire("net_corrupt", "probe")
    assert inj.should_fire("net_corrupt", "probe")
    assert not inj.should_fire("net_corrupt", "probe")
    st = inj.stats()
    assert st["net_corrupt[probe]"] == {"calls": 3, "fires": 2}
    assert st["net_corrupt[other]"]["fires"] == 0


def test_injector_env_gate(monkeypatch):
    reset_global_injector()
    try:
        monkeypatch.delenv("DABT_FAULTS", raising=False)
        assert global_injector() is None
        reset_global_injector()
        monkeypatch.setenv("DABT_FAULTS", '{"http_5xx": {"fire_on": [1]}}')
        monkeypatch.setenv("DABT_FAULT_SEED", "42")
        inj = global_injector()
        assert inj is not None and inj.seed == 42
        assert inj.should_fire("http_5xx") is True
        assert global_injector() is inj  # cached, not re-parsed per call
    finally:
        reset_global_injector()


def test_engine_inert_without_faults(monkeypatch):
    """The disabled path must be a bare `is None` check: with no injector
    configured, NO FaultInjector method is ever entered on the serve path."""

    def trip(self, site):
        raise AssertionError(f"injector consulted on a fault-free engine: {site}")

    monkeypatch.setattr(FaultInjector, "should_fire", trip)
    eng = _tiny_engine().start()
    try:
        assert eng._faults is None
        r = eng.submit([1, 2, 3], max_tokens=5, temperature=0.0).result(timeout=120)
        assert len(r.token_ids) == 5
        assert eng.poisoned_requests == 0 and eng.engine_restarts == 0
    finally:
        eng.stop()


# ------------------------------------------------- quarantine vs engine-fatal
def test_tick_raise_mid_trace_recovers_without_failing_queued():
    """Engine-fatal fault with queued work: the crash-only restart re-submits
    the (token-less) in-flight request and leaves queued requests untouched —
    every future completes, one restart recorded."""
    inj = FaultInjector({})
    eng = _tiny_engine(faults=inj, max_slots=1).start()
    try:
        inj.arm("tick_raise")
        futs = [
            eng.submit([1, 2, 3 + i], max_tokens=5, temperature=0.0)
            for i in range(3)
        ]
        results = [f.result(timeout=120) for f in futs]
        assert all(len(r.token_ids) == 5 for r in results)
        assert eng.engine_restarts == 1
        sup = eng.supervision_stats()
        assert sup["restarted_requests_resubmitted"] == 1
        assert sup["restarted_requests_failed"] == 0
        assert sup["healthy"] is True
    finally:
        eng.stop()


def test_tick_raise_restart_rebuilds_paged_pool_and_keeps_serving():
    """Chaos on the paged KV plane (docs/KV_PAGING.md): an engine-fatal fault
    while pages are allocated AND a prefix is registered — the crash-only
    restart resets the allocator (every page free, registry empty, block
    tables unallocated), salvaged work replays onto fresh pages, and prefix
    sharing works again after recovery."""
    inj = FaultInjector({})
    eng = _tiny_engine(
        faults=inj, max_slots=2, max_seq_len=64,
        prefix_cache_size=4, prefix_min_tokens=8,
    ).start()
    prefix = list(range(1, 13))  # 12 tokens >= prefix_min_tokens
    try:
        eng.submit(
            prefix + [20], max_tokens=3, temperature=0.0, prefix_len=len(prefix)
        ).result(timeout=120)
        assert eng.kv_stats()["kv_shared_pages"] > 0
        inj.arm("tick_raise")
        futs = [
            eng.submit(
                prefix + [30 + i], max_tokens=4, temperature=0.0,
                prefix_len=len(prefix),
            )
            for i in range(3)
        ]
        results = [f.result(timeout=120) for f in futs]
        assert all(len(r.token_ids) == 4 for r in results)
        assert eng.engine_restarts == 1
        # the pool survived the crash in a clean state and re-registered the
        # prefix from post-restart traffic
        deadline = time.monotonic() + 10
        while eng.kv_stats()["kv_pages_used"] > eng.kv_stats()["kv_shared_pages"]:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        st = eng.kv_stats()
        assert st["kv_pages_used"] == st["kv_shared_pages"] > 0
        assert eng.supervision_stats()["healthy"] is True
    finally:
        eng.stop()


def test_nan_logits_quarantines_one_slot_keeps_batch_alive():
    """Request-poison: garbage sampled ids fail ONE co-batched request; its
    batch-mate keeps decoding to a normal finish.  No engine restart."""
    inj = FaultInjector({})
    eng = _tiny_engine(faults=inj, max_slots=2).start()
    try:
        futs = [
            eng.submit([1, 2, 3], max_tokens=48, temperature=0.0),
            eng.submit([4, 5, 6], max_tokens=48, temperature=0.0),
        ]
        deadline = time.monotonic() + 30
        while eng.num_active < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert eng.num_active == 2
        inj.arm("nan_logits")
        outcomes = []
        for f in futs:
            try:
                outcomes.append(("ok", f.result(timeout=120)))
            except RequestPoisoned as e:
                outcomes.append(("poisoned", e))
        kinds = sorted(k for k, _ in outcomes)
        assert kinds == ["ok", "poisoned"]
        ok = next(r for k, r in outcomes if k == "ok")
        assert len(ok.token_ids) == 48
        assert eng.poisoned_requests == 1
        assert eng.engine_restarts == 0  # quarantine, not restart
    finally:
        eng.stop()


def test_detok_raise_quarantines_request_engine_keeps_serving():
    inj = FaultInjector({})
    eng = _tiny_engine(faults=inj).start()
    try:
        inj.arm("detok_raise")
        fut = eng.submit([1, 2, 3], max_tokens=4, temperature=0.0)
        with pytest.raises(FaultInjected, match="detok_raise"):
            fut.result(timeout=120)
        assert eng.poisoned_requests == 1
        r = eng.submit([1, 2, 3], max_tokens=4, temperature=0.0).result(timeout=120)
        assert len(r.token_ids) == 4
    finally:
        eng.stop()


def test_restart_fails_stream_past_first_delta_but_preserves_queued():
    """A streamed request that already emitted deltas cannot be replayed (the
    client would see divergent text) — on restart it fails cleanly; a queued
    request rides through untouched."""
    inj = FaultInjector({})
    eng = _tiny_engine(faults=inj, max_slots=1, max_seq_len=128).start()

    async def go():
        agen = eng.generate_stream("hello", max_tokens=64, temperature=0.0)
        first = await agen.__anext__()
        assert first.token_id is not None
        # now a queued request behind the 1-slot stream, then the fatal fault
        queued = eng.submit([9, 8, 7], max_tokens=4, temperature=0.0)
        inj.arm("tick_raise")
        with pytest.raises(FaultInjected):
            async for _ in agen:
                pass
        return queued

    try:
        queued = asyncio.run(go())
        assert len(queued.result(timeout=120).token_ids) == 4
        assert eng.engine_restarts == 1
        # the streamed request was NOT re-submitted (it was past first delta)
        assert eng.supervision_stats()["restarted_requests_resubmitted"] == 0
    finally:
        eng.stop()


def test_persistent_fault_trips_circuit_submit_fast_fails():
    """max_restarts restarts inside the window open the circuit: the engine
    goes degraded and submit() fails synchronously with EngineUnavailable
    carrying a Retry-After hint."""
    inj = FaultInjector({"tick_raise": {"every": 1}})  # every tick dies
    eng = _tiny_engine(
        faults=inj,
        max_slots=1,
        max_restarts=2,
        restart_window_s=60.0,
        restart_backoff_s=0.005,
        restart_backoff_max_s=0.02,
        degraded_cooldown_s=600.0,  # long: the trip itself is the assertion
        max_request_restarts=1,
    ).start()
    try:
        fut = eng.submit([1, 2, 3], max_tokens=4, temperature=0.0)
        deadline = time.monotonic() + 60
        while not eng.degraded() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.degraded()
        assert eng.circuit_trips == 1
        with pytest.raises(FaultInjected):
            fut.result(timeout=120)  # exhausted its max_request_restarts
        with pytest.raises(EngineUnavailable) as ei:
            eng.submit([4, 5], max_tokens=2)
        assert ei.value.retry_after_s > 0
        assert eng.supervision_stats()["healthy"] is False
    finally:
        eng.stop()


def test_circuit_half_open_recovers_after_cooldown():
    """Once the fault stops firing, the cooldown expiry half-opens the circuit
    and the engine serves again."""
    inj = FaultInjector({"tick_raise": {"every": 1, "max_fires": 3}})
    eng = _tiny_engine(
        faults=inj,
        max_slots=1,
        max_restarts=2,
        restart_backoff_s=0.005,
        restart_backoff_max_s=0.02,
        degraded_cooldown_s=0.2,
        max_request_restarts=0,
    ).start()
    try:
        fut = eng.submit([1, 2, 3], max_tokens=4, temperature=0.0)
        with pytest.raises(FaultInjected):
            fut.result(timeout=120)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                r = eng.submit([1, 2, 3], max_tokens=4, temperature=0.0).result(
                    timeout=120
                )
                break
            except (EngineUnavailable, FaultInjected):
                time.sleep(0.05)
        else:
            pytest.fail("engine never recovered after the fault stopped")
        assert len(r.token_ids) == 4
        assert not eng.degraded()
    finally:
        eng.stop()


# ------------------------------------------------------- HTTP surface mapping
@pytest.fixture()
def http_registry():
    registry = ModelRegistry.from_config(
        {"tiny-chat": {"kind": "decoder", "tiny": True, "max_slots": 2,
                       "max_seq_len": 64}}
    )
    yield registry
    registry.stop()


def test_healthz_degraded_and_503_mapping(http_registry):
    eng = http_registry.get_generator("tiny-chat")

    async def go(client):
        resp = await client.get("/healthz")
        data = await resp.json()
        assert data["status"] == "ok"
        sup = data["generators"]["tiny-chat"]["supervision"]
        assert sup["healthy"] is True
        assert "loop_heartbeat_age_s" in sup
        assert sup["engine_restarts"] == 0

        # trip the circuit: /dialog/ must map EngineUnavailable -> 503
        eng._degraded_until = time.monotonic() + 30.0
        resp = await client.post(
            "/dialog/",
            json={"model": "tiny-chat",
                  "messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 2},
        )
        assert resp.status == 503
        assert int(resp.headers["Retry-After"]) >= 1
        assert "degraded" in (await resp.json())["detail"]
        # streaming requests fast-fail with the same mapping
        resp = await client.post(
            "/dialog/",
            json={"model": "tiny-chat", "stream": True,
                  "messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 2},
        )
        assert resp.status == 503

        resp = await client.get("/healthz")
        data = await resp.json()
        assert data["status"] == "degraded"
        assert data["generators"]["tiny-chat"]["supervision"]["degraded"] is True
        eng._degraded_until = None

        # wedged-loop detection: a heartbeat older than the threshold flips
        # status even though cached stats still look green
        eng.heartbeat_degraded_s = 1e-9
        resp = await client.get("/healthz")
        assert (await resp.json())["status"] == "degraded"
        eng.heartbeat_degraded_s = 30.0

    _run_with_client(http_registry, go)


def _run_with_client(registry, go):
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        client = TestClient(TestServer(create_app(registry)))
        await client.start_server()
        try:
            await go(client)
        finally:
            await client.close()

    asyncio.run(main())


# ------------------------------------------------------------------- failover
class _StubProvider:
    """Scripted backend: each call pops an outcome — an Exception to raise or
    a text to answer."""

    def __init__(self, outcomes):
        self.outcomes = list(outcomes)
        self.calls = 0
        self.calls_attempts = []

    @property
    def context_size(self):
        return 1000

    def calculate_tokens(self, text):
        return len(text)

    async def get_response(self, messages, max_tokens=1024, json_format=False):
        from django_assistant_bot_tpu.ai.domain import AIResponse

        self.calls += 1
        out = self.outcomes.pop(0) if self.outcomes else "default"
        if isinstance(out, Exception):
            raise out
        return AIResponse(result=out, usage=None)

    async def stream_response(self, messages, max_tokens=1024, json_format=False):
        from django_assistant_bot_tpu.ai.providers.base import AIStreamChunk

        resp = await self.get_response(messages, max_tokens, json_format)
        mid = max(1, len(resp.result) // 2)
        yield AIStreamChunk(delta=resp.result[:mid])
        if resp.result == "die-mid-stream":
            raise RuntimeError("backend died mid-stream")
        yield AIStreamChunk(delta=resp.result[mid:])
        yield AIStreamChunk(done=True, response=resp)


def _chain(*provs, clock=None, **kw):
    from django_assistant_bot_tpu.ai.providers.failover import FailoverProvider

    kw.setdefault("backoff_s", 0.0)
    if clock is not None:
        kw["clock"] = clock
    return FailoverProvider(list(provs), names=[f"b{i}" for i in range(len(provs))], **kw)


def test_failover_chain_ordering_and_breaker():
    from django_assistant_bot_tpu.ai.providers.failover import AllBackendsFailed

    now = [0.0]
    bad = _StubProvider([RuntimeError("down")] * 10)
    good = _StubProvider(["answer-1", "answer-2", "answer-3"])
    fp = _chain(bad, good, clock=lambda: now[0],
                breaker_threshold=1, breaker_reset_s=100.0)

    async def go():
        r1 = await fp.get_response([{"role": "user", "content": "q"}])
        assert r1.result == "answer-1"
        assert fp.breaker_states() == {"b0": "open", "b1": "closed"}
        assert fp.calls_attempts[-1] == 2  # tried bad, then good
        # circuit open: the dead backend is skipped entirely
        r2 = await fp.get_response([{"role": "user", "content": "q"}])
        assert r2.result == "answer-2"
        assert bad.calls == 1
        assert fp.calls_attempts[-1] == 1
        # cooldown elapses -> half-open probe hits the bad backend once,
        # fails, and re-opens
        now[0] += 101.0
        r3 = await fp.get_response([{"role": "user", "content": "q"}])
        assert r3.result == "answer-3"
        assert bad.calls == 2
        assert fp.breaker_states()["b0"] == "open"
        # every backend down -> AllBackendsFailed naming each error
        dead = _chain(_StubProvider([RuntimeError("x")] * 5),
                      _StubProvider([RuntimeError("y")] * 5))
        with pytest.raises(AllBackendsFailed, match="b1"):
            await dead.get_response([{"role": "user", "content": "q"}])

    asyncio.run(go())


def test_breaker_cancelled_probe_releases_slot():
    """A half-open probe whose caller is cancelled must free the probe slot
    (neither success nor failure) — otherwise the backend blocks forever."""
    from django_assistant_bot_tpu.ai.providers.failover import CircuitBreaker

    now = [0.0]
    br = CircuitBreaker(failure_threshold=1, reset_timeout_s=10.0, clock=lambda: now[0])
    br.record_failure()
    assert br.state == "open" and not br.allow()
    now[0] += 11.0
    assert br.allow()  # admitted as the probe
    assert not br.allow()  # one probe at a time
    br.release_probe()  # probe's caller was cancelled mid-flight
    assert br.allow()  # the next request may probe
    br.record_success()
    assert br.state == "closed"


def test_failover_breaker_closes_after_successful_probe():
    now = [0.0]
    flaky = _StubProvider([RuntimeError("down"), "recovered", "recovered-2"])
    good = _StubProvider(["fallback"] * 5)
    fp = _chain(flaky, good, clock=lambda: now[0],
                breaker_threshold=1, breaker_reset_s=50.0)

    async def go():
        assert (await fp.get_response([])).result == "fallback"
        now[0] += 51.0
        assert (await fp.get_response([])).result == "recovered"
        assert fp.breaker_states()["b0"] == "closed"
        assert (await fp.get_response([])).result == "recovered-2"

    asyncio.run(go())


def test_failover_streaming_before_first_delta_only():
    bad = _StubProvider([RuntimeError("down")])
    good = _StubProvider(["streamed answer"])
    fp = _chain(bad, good)

    async def collect(provider):
        deltas, final = [], None
        async for c in provider.stream_response([{"role": "user", "content": "q"}]):
            if c.done:
                final = c.response
            else:
                deltas.append(c.delta)
        return deltas, final

    async def go():
        deltas, final = await collect(fp)
        assert "".join(deltas) == "streamed answer"
        assert final.result == "streamed answer"
        # past the first delta the response is committed: a mid-stream death
        # surfaces to the consumer instead of silently switching backends
        mid = _chain(_StubProvider(["die-mid-stream"]), good)
        with pytest.raises(RuntimeError, match="mid-stream"):
            await collect(mid)

    asyncio.run(go())


class _HangingStreamProvider:
    """First stream call hangs before its first delta (cancellation is the
    only way out); later calls stream normally.  The half-open-probe shape:
    a recovering backend that stalls its probe request."""

    def __init__(self):
        self.calls = 0
        self.calls_attempts = []

    @property
    def context_size(self):
        return 1000

    def calculate_tokens(self, text):
        return len(text)

    async def get_response(self, messages, max_tokens=1024, json_format=False):
        raise NotImplementedError

    async def stream_response(self, messages, max_tokens=1024, json_format=False):
        from django_assistant_bot_tpu.ai.domain import AIResponse
        from django_assistant_bot_tpu.ai.providers.base import AIStreamChunk

        self.calls += 1
        if self.calls == 1:
            await asyncio.Event().wait()  # hang until cancelled
        yield AIStreamChunk(delta="recovered")
        yield AIStreamChunk(
            done=True, response=AIResponse(result="recovered", usage=None)
        )


def test_failover_streaming_cancelled_half_open_probe_releases_slot():
    """Satellite of the PR 5 review fix, extended to the STREAMING path under
    concurrent consumers: the one half-open probe stream hangs pre-first-delta
    and is cancelled — the probe slot must free so the next concurrent stream
    can probe the backend (without the fix the breaker blocks forever)."""
    from django_assistant_bot_tpu.ai.providers.failover import AllBackendsFailed

    now = [0.0]
    prov = _HangingStreamProvider()
    fp = _chain(prov, clock=lambda: now[0], breaker_threshold=1,
                breaker_reset_s=10.0)

    async def consume():
        deltas = []
        async for c in fp.stream_response([{"role": "user", "content": "q"}]):
            if not c.done:
                deltas.append(c.delta)
        return deltas

    async def go():
        fp._breakers[0].record_failure()
        assert fp.breaker_states()["b0"] == "open"
        now[0] += 11.0  # cooldown elapsed: next caller is THE probe
        t1 = asyncio.create_task(consume())
        await asyncio.sleep(0.01)  # t1 claimed the probe and hangs
        # a concurrent stream cannot enter: the probe slot is held
        with pytest.raises(AllBackendsFailed, match="circuit open"):
            await consume()
        t1.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t1
        # the cancelled probe released its slot: the next stream probes,
        # commits, and closes the circuit
        assert await consume() == ["recovered"]
        assert fp.breaker_states()["b0"] == "closed"

    asyncio.run(go())


class _ParkedAwaitable:
    """Yields once and parks — lets a test drive an async generator by hand
    (no event loop) to a suspension point inside a backend await."""

    def __await__(self):
        yield self


def test_failover_streaming_abandoned_probe_releases_slot_on_generator_exit():
    """aclose() on the failover stream while it is suspended at the backend
    await delivers GeneratorExit — NOT CancelledError — at the await point;
    the probe slot must free on that path too (the streaming extension of the
    cancelled-probe fix: a consumer that abandons the generator, e.g. the SSE
    handler's finally-aclose after a disconnect, must not wedge the breaker)."""
    now = [0.0]

    class _Parked(_HangingStreamProvider):
        async def stream_response(self, messages, max_tokens=1024, json_format=False):
            self.calls += 1
            await _ParkedAwaitable()
            yield None  # pragma: no cover - never reached

    fp = _chain(_Parked(), clock=lambda: now[0], breaker_threshold=1,
                breaker_reset_s=10.0)
    br = fp._breakers[0]
    br.record_failure()
    now[0] += 11.0
    agen = fp.stream_response([{"role": "user", "content": "q"}])
    step = agen.__anext__()
    step.send(None)  # drive to the backend await: the probe slot is claimed
    assert br._probing is True
    # finalizing the abandoned consumer coroutine delivers GeneratorExit AT
    # the backend await point (what coroutine cleanup does for a consumer
    # that vanished without cancelling) — the handler must free the slot
    with pytest.raises(GeneratorExit):
        step.throw(GeneratorExit)
    assert br._probing is False  # slot released — the next request may probe
    assert br.allow() is True
    br.release_probe()


def test_failover_model_routing():
    from django_assistant_bot_tpu.ai.providers.failover import FailoverProvider
    from django_assistant_bot_tpu.ai.services.ai_service import get_ai_provider

    fp = get_ai_provider("failover:test:a|test:b")
    assert isinstance(fp, FailoverProvider)
    assert fp.breaker_states() == {"test:a": "closed", "test:b": "closed"}

    async def go():
        r = await fp.get_response([{"role": "user", "content": "ping"}])
        assert r.result == "echo: ping"

    asyncio.run(go())
    with pytest.raises(ValueError):
        get_ai_provider("failover:")


# ------------------------------------------------- HTTP client retry policy
def test_parse_retry_after_formats():
    from datetime import datetime, timedelta, timezone

    from django_assistant_bot_tpu.ai.providers.http_service import parse_retry_after

    assert parse_retry_after("2.5") == 2.5
    assert parse_retry_after("0") == 0.0
    assert parse_retry_after(None) is None
    assert parse_retry_after("soon") is None
    future = datetime.now(timezone.utc) + timedelta(seconds=30)
    got = parse_retry_after(format_datetime(future, usegmt=True))
    assert got is not None and 25.0 < got <= 31.0
    past = datetime.now(timezone.utc) - timedelta(seconds=30)
    assert parse_retry_after(format_datetime(past, usegmt=True)) == 0.0


def test_post_retries_connection_errors_and_503(monkeypatch):
    """Injected conn_reset then http_5xx: the idempotent POST retries both and
    lands on the real (healthy) server; non-idempotent requests surface the
    connection error immediately."""
    import aiohttp
    from aiohttp import web as aioweb
    from aiohttp.test_utils import TestClient, TestServer

    from django_assistant_bot_tpu.ai.providers import http_service

    monkeypatch.setattr(http_service, "RETRY_BACKOFF_BASE_S", 0.01)
    hits = {"n": 0}

    async def handler(request):
        hits["n"] += 1
        return aioweb.json_response({"ok": True})

    app = aioweb.Application()
    app.router.add_post("/echo", handler)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            async with aiohttp.ClientSession() as session:
                # attempt 1: conn_reset fires (http_5xx never consulted);
                # attempt 2: conn_reset is past its schedule, http_5xx's FIRST
                # consultation fires; attempt 3 reaches the healthy server
                inj = FaultInjector(
                    {"conn_reset": {"fire_on": [1]}, "http_5xx": {"fire_on": [1]}}
                )
                set_global_injector(inj)
                resp = await http_service._post_with_shed_retry(
                    session, str(client.make_url("/echo")), {"x": 1}
                )
                assert (await resp.json()) == {"ok": True}
                assert hits["n"] == 1  # two injected failures, one real hit
                assert inj.stats()["conn_reset"]["fires"] == 1
                assert inj.stats()["http_5xx"]["fires"] == 1

                # non-idempotent: a connection error must NOT be retried
                set_global_injector(
                    FaultInjector({"conn_reset": {"fire_on": [1]}})
                )
                with pytest.raises(ConnectionResetError):
                    await http_service._post_with_shed_retry(
                        session, str(client.make_url("/echo")), {"x": 2}, idempotent=False
                    )
                assert hits["n"] == 1
        finally:
            set_global_injector(None)
            reset_global_injector()
            await client.close()

    asyncio.run(go())


def test_post_retries_real_503_with_http_date_retry_after(monkeypatch):
    """A real 503 + HTTP-date Retry-After (RFC 9110) is honored, then the
    recovered server answers; a 400 never retries."""
    from datetime import datetime, timezone

    from aiohttp import ClientResponseError, ClientSession
    from aiohttp import web as aioweb
    from aiohttp.test_utils import TestClient, TestServer

    from django_assistant_bot_tpu.ai.providers import http_service

    monkeypatch.setattr(http_service, "RETRY_BACKOFF_BASE_S", 0.01)
    hits = {"flaky": 0, "bad": 0}

    async def flaky(request):
        hits["flaky"] += 1
        if hits["flaky"] == 1:
            return aioweb.json_response(
                {"detail": "degraded"},
                status=503,
                headers={
                    "Retry-After": format_datetime(
                        datetime.now(timezone.utc), usegmt=True
                    )
                },
            )
        return aioweb.json_response({"ok": True})

    async def bad(request):
        hits["bad"] += 1
        return aioweb.json_response({"detail": "nope"}, status=400)

    app = aioweb.Application()
    app.router.add_post("/flaky", flaky)
    app.router.add_post("/bad", bad)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            async with ClientSession() as session:
                resp = await http_service._post_with_shed_retry(
                    session, str(client.make_url("/flaky")), {}
                )
                assert (await resp.json()) == {"ok": True}
                assert hits["flaky"] == 2
                with pytest.raises(ClientResponseError):
                    await http_service._post_with_shed_retry(
                        session, str(client.make_url("/bad")), {}
                    )
                assert hits["bad"] == 1  # 4xx is not retriable
        finally:
            await client.close()

    asyncio.run(go())
