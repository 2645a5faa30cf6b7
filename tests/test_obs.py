"""Observability plane tests (serving/obs.py + wiring; docs/OBSERVABILITY.md).

Four groups:

- unit: the fixed-bucket Histogram, the exposition renderer and the small
  in-repo Prometheus parser/validator (the one CI's chaos smoke uses);
- tracing: trace_id propagation end to end (submit kwarg, generated ids,
  span structure from the host timestamps the tick path already stamps);
- HTTP: ``X-Request-Id`` accepted and echoed on EVERY ``/dialog/`` response
  shape (JSON, SSE terminal event, 422/429/503/504 error bodies), plus the
  ``GET /metrics`` endpoint — including the scrape-under-duress regression
  net: /metrics and /healthz must answer promptly and parse while one
  replica is dead, mid-drain, and mid-restart (the router-lock/scheduler-
  lock deadlock family from PR 7);
- flight recorder: a chaos ``tick_raise`` restart must dump a well-formed
  JSON artifact containing the injected-fault event and the resubmitted
  request's trace_id.
"""

from __future__ import annotations

import asyncio
import glob
import io
import json
import logging
import time
from types import SimpleNamespace

import jax
import pytest

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.serving import (
    ByteTokenizer,
    EngineUnavailable,
    FaultInjector,
    GenerationEngine,
    GenerationResult,
    Histogram,
    ModelRegistry,
    SchedulerRejected,
    new_trace_id,
    parse_prometheus_text,
    render_prometheus,
)
from django_assistant_bot_tpu.serving.obs import (
    JsonLogFormatter,
    setup_json_logging,
)
from django_assistant_bot_tpu.serving.scheduler import DeadlineExceeded
from django_assistant_bot_tpu.serving.server import create_app


def _engine(tmp_path=None, **kw):
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 64)
    if tmp_path is not None:
        kw.setdefault("obs_dump_dir", str(tmp_path))
    return GenerationEngine(cfg, params, ByteTokenizer(), **kw)


# ---------------------------------------------------------------------- units
def test_histogram_buckets_cumulative_and_sum():
    h = Histogram((0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    buckets, total, n = h.snapshot()
    assert n == 5 and abs(total - 56.05) < 1e-9
    assert buckets == [(0.1, 1), (1.0, 3), (10.0, 4), (float("inf"), 5)]
    # boundary values land in their own bucket (le is inclusive)
    h2 = Histogram((1.0,))
    h2.observe(1.0)
    assert h2.snapshot()[0][0] == (1.0, 1)


def test_parser_roundtrips_renderer_output():
    h = Histogram((0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)
    from django_assistant_bot_tpu.serving.obs import _Exposition

    x = _Exposition()
    x.add("t_total", "counter", "a counter", 7, {"model": "m"})
    x.add("g", "gauge", 'label with "quotes" and \\', 1.5, {"k": 'v"w\\x'})
    x.add_histogram("lat_seconds", "a histogram", h, {"model": "m"})
    fams = parse_prometheus_text(x.render())
    assert fams["t_total"]["samples"] == [("t_total", {"model": "m"}, 7.0)]
    # label escaping survives the roundtrip
    assert fams["g"]["samples"][0][1] == {"k": 'v"w\\x'}
    lat = fams["lat_seconds"]
    assert lat["type"] == "histogram"
    counts = {n: v for n, _, v in lat["samples"] if n.endswith("_count")}
    assert counts == {"lat_seconds_count": 2.0}


def test_parser_rejects_malformed_exposition():
    with pytest.raises(ValueError, match="no preceding TYPE"):
        parse_prometheus_text("orphan_metric 1\n")
    bad_noncumulative = (
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\nh_bucket{le="+Inf"} 5\n'
        "h_sum 1\nh_count 5\n"
    )
    with pytest.raises(ValueError, match="non-cumulative"):
        parse_prometheus_text(bad_noncumulative)
    bad_no_inf = "# TYPE h histogram\n" 'h_bucket{le="1"} 5\nh_sum 1\nh_count 5\n'
    with pytest.raises(ValueError, match="\\+Inf"):
        parse_prometheus_text(bad_no_inf)
    bad_count = (
        "# TYPE h histogram\n"
        'h_bucket{le="+Inf"} 4\nh_sum 1\nh_count 5\n'
    )
    with pytest.raises(ValueError, match="_count"):
        parse_prometheus_text(bad_count)
    with pytest.raises(ValueError, match="unparseable"):
        parse_prometheus_text("# TYPE g gauge\ng not-a-number\n")


def test_json_log_formatter_line_shape():
    fmt = JsonLogFormatter()
    rec = logging.LogRecord(
        "serving", logging.INFO, __file__, 1, "request finished", (), None
    )
    rec.trace_id = "abc123"
    rec.model = "tiny-chat"
    rec.replica = "tiny-chat/r0"
    line = json.loads(fmt.format(rec))
    assert line["event"] == "request finished"
    assert line["trace_id"] == "abc123"
    assert line["model"] == "tiny-chat"
    assert line["replica"] == "tiny-chat/r0"
    assert line["level"] == "info" and "ts" in line


def test_setup_json_logging_gate(monkeypatch):
    monkeypatch.delenv("DABT_LOG_JSON", raising=False)
    assert setup_json_logging() is False  # plain-text default untouched
    stream = io.StringIO()
    root = logging.getLogger()
    handler = logging.StreamHandler(stream)
    old_formatters = [(h, h.formatter) for h in root.handlers]
    root.addHandler(handler)
    try:
        monkeypatch.setenv("DABT_LOG_JSON", "1")
        assert setup_json_logging() is True
        logging.getLogger("obs-test").warning(
            "shed", extra={"trace_id": "t1", "reason": "queue_full"}
        )
        line = json.loads(stream.getvalue().strip().splitlines()[-1])
        assert line == {
            "ts": line["ts"],
            "level": "warning",
            "logger": "obs-test",
            "event": "shed",
            "trace_id": "t1",
            "reason": "queue_full",
        }
    finally:
        root.removeHandler(handler)
        for h, f in old_formatters:
            h.setFormatter(f)


# -------------------------------------------------------------------- tracing
def test_trace_id_propagates_and_spans_close(tmp_path):
    eng = _engine(tmp_path, name="traced").start()
    try:
        r = eng.submit(
            [1, 2, 3], max_tokens=4, temperature=0.0, trace_id="req-1"
        ).result(timeout=300)
        tr = eng.obs.trace("req-1")
        assert tr is not None and tr["engine"] == "traced"
        names = [s["name"] for s in tr["spans"]]
        # real spans under one root; the zero-length admit/deliver markers
        # are gone (deliver is the server's: absent without a socket)
        assert names == ["request", "encode", "queue_wait", "prefill", "decode", "detok"]
        assert tr["completion_tokens"] == len(r.token_ids)
        # span arithmetic: encode + queue_wait + prefill + decode + detok == total
        spans = {s["name"]: s for s in tr["spans"]}
        assert spans["request"]["parent"] is None
        assert all(s["parent"] == "request" for s in tr["spans"][1:])
        parts = sum(
            spans[n].get("dur_s", 0.0)
            for n in ("encode", "queue_wait", "prefill", "decode", "detok")
        )
        assert abs(parts - tr["total_s"]) < 1e-3
        assert spans["decode"]["tokens"] == tr["completion_tokens"]
        assert tr["timings"] == r.timings
        # generated ids when the caller sends none; unique per request
        f1 = eng.submit([4, 5], max_tokens=2, temperature=0.0)
        f2 = eng.submit([6, 7], max_tokens=2, temperature=0.0)
        f1.result(timeout=300), f2.result(timeout=300)
        ids = [t["trace_id"] for t in eng.obs.traces()]
        assert len(ids) == len(set(ids)) == 3
        assert all(ids)
    finally:
        eng.stop()


def test_obs_off_engine_serves_without_recorder(tmp_path):
    eng = _engine(tmp_path, obs=False).start()
    try:
        assert eng.obs is None
        r = eng.submit([1, 2, 3], max_tokens=3, temperature=0.0).result(timeout=300)
        assert len(r.token_ids) == 3
    finally:
        eng.stop()


def test_metrics_histogram_counts_match_known_trace(tmp_path):
    """The acceptance-criteria count check: N finished requests -> exactly N
    TTFT and N queue-wait observations in the scraped exposition."""
    eng = _engine(tmp_path, name="counted").start()
    try:
        n = 5
        futs = [
            eng.submit([1 + i, 2, 3], max_tokens=3, temperature=0.0)
            for i in range(n)
        ]
        for f in futs:
            f.result(timeout=300)
        reg = SimpleNamespace(generators={"counted": eng}, embedders={})
        fams = parse_prometheus_text(render_prometheus(reg))
        for fam in ("dabt_ttft_seconds", "dabt_queue_wait_seconds"):
            counts = [
                v for name, _, v in fams[fam]["samples"] if name.endswith("_count")
            ]
            assert counts == [float(n)], (fam, counts)
        # tick histogram saw at least one tick per generated token wave
        tick_counts = [
            v
            for name, _, v in fams["dabt_tick_seconds"]["samples"]
            if name.endswith("_count")
        ]
        assert tick_counts[0] >= 1
        assert fams["dabt_traces_total"]["samples"][0][2] == float(n)
    finally:
        eng.stop()


# ------------------------------------------------------------ flight recorder
def test_chaos_restart_dumps_wellformed_artifact(tmp_path, monkeypatch):
    """A chaos tick_raise restart must leave a parseable JSON artifact whose
    event ring contains the injected-fault event AND the resubmitted
    request's trace_id — diagnosable from the artifact alone."""
    # pin the dump location: DABT_FLIGHT_DIR (set by CI's chaos smoke step)
    # takes precedence over obs_dump_dir, and this test globs tmp_path
    monkeypatch.setenv("DABT_FLIGHT_DIR", str(tmp_path))
    eng = _engine(tmp_path, name="chaos").start()
    inj = FaultInjector({})
    eng._faults = inj
    try:
        eng.submit([1, 2, 3], max_tokens=2, temperature=0.0).result(timeout=300)
        inj.arm("tick_raise")
        r = eng.submit(
            [4, 5, 6], max_tokens=3, temperature=0.0, trace_id="chaos-req"
        ).result(timeout=300)
        assert len(r.token_ids) == 3  # crash-only restart completed the trace
        assert eng.engine_restarts == 1
    finally:
        eng.stop()
    dumps = sorted(glob.glob(str(tmp_path / "flight-chaos-*.json")))
    assert dumps, "restart produced no flight-recorder dump"
    with open(dumps[0]) as fh:
        artifact = json.load(fh)
    assert artifact["reason"] == "restart"
    assert artifact["recorder"] == "chaos"
    events = artifact["events"]
    fault = [e for e in events if e["event"] == "fault_fire"]
    assert fault and fault[0]["site"] == "tick_raise"
    resub = [e for e in events if e["event"] == "resubmit"]
    assert any(e["trace_id"] == "chaos-req" for e in resub)
    restart = [e for e in events if e["event"] == "restart"]
    assert restart and "FaultInjected" in restart[0]["error"]
    # every event is stamped and ordered
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)


# ----------------------------------------------------------------------- HTTP
class _StubEngine:
    """Engine-shaped stub for deterministic HTTP response-shape tests."""

    def __init__(self):
        self.raise_exc = None
        self.seen_trace_ids = []
        self.tokenizer = ByteTokenizer()
        self.max_seq_len = 64
        self.num_active = 0
        self.steps = 0
        self.reclaimed_slots = 0

    async def generate(self, messages, **kw):
        self.seen_trace_ids.append(kw.get("trace_id"))
        if self.raise_exc is not None:
            raise self.raise_exc
        return GenerationResult(
            token_ids=[1, 2],
            text="ok",
            prompt_tokens=3,
            completion_tokens=2,
            length_limited=False,
        )

    async def generate_stream(self, messages, **kw):
        from django_assistant_bot_tpu.serving.streaming import StreamChunk

        self.seen_trace_ids.append(kw.get("trace_id"))
        if self.raise_exc is not None:
            raise self.raise_exc
        yield StreamChunk(index=0, token_id=1, text="o")
        yield StreamChunk(
            index=1,
            token_id=None,
            text="k",
            done=True,
            finish_reason="stop",
            result=GenerationResult(
                token_ids=[1, 2],
                text="ok",
                prompt_tokens=3,
                completion_tokens=2,
                length_limited=False,
            ),
        )


class _StubRegistry:
    def __init__(self, eng):
        self.eng = eng
        self.generators = {}
        self.embedders = {}
        self.specs = {}

    def get_generator(self, model):
        return self.eng if model == "stub" else None

    def get_embedder(self, model):
        return None

    def idle(self):
        return True

    def stop(self):
        pass


@pytest.fixture()
def stub_client():
    from aiohttp.test_utils import TestClient, TestServer

    loop = asyncio.new_event_loop()
    eng = _StubEngine()
    app = create_app(_StubRegistry(eng))
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    yield loop, client, eng, app
    loop.run_until_complete(client.close())
    loop.close()


def _dialog_body(**kw):
    body = {
        "model": "stub",
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4,
    }
    body.update(kw)
    return body


def test_request_id_echoed_on_every_dialog_shape(stub_client):
    loop, client, eng, app = stub_client

    async def go():
        hdr = {"X-Request-Id": "client-id-1"}
        # 200 JSON: header + body echo, and the id IS the engine trace_id
        resp = await client.post("/dialog/", json=_dialog_body(), headers=hdr)
        assert resp.status == 200
        assert resp.headers["X-Request-Id"] == "client-id-1"
        assert (await resp.json())["request_id"] == "client-id-1"
        assert eng.seen_trace_ids[-1] == "client-id-1"

        # no client id -> server generates one (and still echoes it)
        resp = await client.post("/dialog/", json=_dialog_body())
        rid = resp.headers["X-Request-Id"]
        assert rid and (await resp.json())["request_id"] == rid
        assert eng.seen_trace_ids[-1] == rid

        # hostile header shapes are replaced, never echoed verbatim
        resp = await client.post(
            "/dialog/", json=_dialog_body(), headers={"X-Request-Id": "x" * 500}
        )
        assert resp.headers["X-Request-Id"] != "x" * 500

        # 422 (bad body)
        resp = await client.post(
            "/dialog/", json={"model": "stub"}, headers=hdr
        )
        assert resp.status == 422
        assert resp.headers["X-Request-Id"] == "client-id-1"
        assert (await resp.json())["request_id"] == "client-id-1"

        # 400 (unknown model)
        resp = await client.post(
            "/dialog/", json=_dialog_body(model="nope"), headers=hdr
        )
        assert resp.status == 400
        assert (await resp.json())["request_id"] == "client-id-1"

        # 429 (shed): the formerly-uncorrelatable case
        eng.raise_exc = SchedulerRejected("queue_full", 1.5)
        resp = await client.post("/dialog/", json=_dialog_body(), headers=hdr)
        assert resp.status == 429
        assert resp.headers["X-Request-Id"] == "client-id-1"
        body = await resp.json()
        assert body["request_id"] == "client-id-1"
        assert body["reason"] == "queue_full"

        # 503 (engine degraded)
        eng.raise_exc = EngineUnavailable("degraded", retry_after_s=2.0)
        resp = await client.post("/dialog/", json=_dialog_body(), headers=hdr)
        assert resp.status == 503
        assert (await resp.json())["request_id"] == "client-id-1"

        # 504 (deadline)
        eng.raise_exc = DeadlineExceeded("too slow")
        resp = await client.post("/dialog/", json=_dialog_body(), headers=hdr)
        assert resp.status == 504
        assert (await resp.json())["request_id"] == "client-id-1"

        # SSE: header + terminal event carry the id
        eng.raise_exc = None
        resp = await client.post(
            "/dialog/", json=_dialog_body(stream=True), headers=hdr
        )
        assert resp.status == 200
        assert resp.headers["X-Request-Id"] == "client-id-1"
        text = (await resp.read()).decode()
        terminal = [
            json.loads(line[len("data: "):])
            for line in text.splitlines()
            if line.startswith("data: {")
        ][-1]
        assert terminal["done"] is True
        assert terminal["request_id"] == "client-id-1"

        # draining 503 echoes too
        from django_assistant_bot_tpu.serving.server import DRAIN_KEY

        app[DRAIN_KEY]["draining"] = True
        try:
            resp = await client.post("/dialog/", json=_dialog_body(), headers=hdr)
            assert resp.status == 503
            assert (await resp.json())["request_id"] == "client-id-1"
        finally:
            app[DRAIN_KEY]["draining"] = False

    loop.run_until_complete(go())


def test_provider_sends_request_id_and_server_echoes(stub_client):
    loop, client, eng, app = stub_client

    async def go():
        from django_assistant_bot_tpu.ai.providers.http_service import (
            GPUServiceProvider,
        )

        base = str(client.make_url(""))
        prov = GPUServiceProvider(base, "stub")
        resp = await prov.get_response([{"role": "user", "content": "hi"}])
        assert resp.result == "ok"
        assert prov.last_request_id
        # the provider's generated id reached the engine as the trace_id
        assert eng.seen_trace_ids[-1] == prov.last_request_id

    loop.run_until_complete(go())


# ------------------------------------------------- scrape under duress (slow)
@pytest.fixture(scope="module")
def duress_fleet(tmp_path_factory):
    """2-replica tiny fleet behind the real server app (module-scoped: the
    engines compile once and every duress scenario reuses them)."""
    from aiohttp.test_utils import TestClient, TestServer

    tmp = tmp_path_factory.mktemp("flight")
    loop = asyncio.new_event_loop()
    registry = ModelRegistry.from_config(
        {
            "tiny-chat": {
                "kind": "decoder",
                "tiny": True,
                "max_slots": 2,
                "max_seq_len": 64,
                "replicas": 2,
                "obs_dump_dir": str(tmp),
                "router_breaker_reset_s": 0.2,
            }
        }
    )
    client = TestClient(TestServer(create_app(registry)), loop=loop)
    loop.run_until_complete(client.start_server())
    yield loop, client, registry
    loop.run_until_complete(client.close())
    loop.close()


def _scrape_promptly(loop, client, budget_s=10.0):
    """GET /metrics and /healthz; both must answer within the budget and the
    exposition must parse.  Returns the parsed families."""
    t0 = time.monotonic()

    async def go():
        m = await client.get("/metrics")
        assert m.status == 200
        text = await m.text()
        h = await client.get("/healthz")
        assert h.status == 200
        return text, await h.json()

    text, health = loop.run_until_complete(asyncio.wait_for(go(), budget_s))
    assert time.monotonic() - t0 < budget_s
    return parse_prometheus_text(text), health


def test_metrics_scrape_under_duress(duress_fleet):
    loop, client, registry = duress_fleet
    router = registry.get_generator("tiny-chat")

    async def warm():
        resp = await client.post(
            "/dialog/",
            json={
                "model": "tiny-chat",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 2,
            },
        )
        assert resp.status == 200

    loop.run_until_complete(asyncio.wait_for(warm(), 300))

    # healthy: both replicas up, per-replica labels present
    fams, health = _scrape_promptly(loop, client)
    healthy = {
        labels["replica"]: v
        for _, labels, v in fams["dabt_engine_healthy"]["samples"]
    }
    assert set(healthy) == {"tiny-chat/r0", "tiny-chat/r1"}
    assert all(v == 1.0 for v in healthy.values())
    assert health["status"] == "ok"
    assert "dabt_router_reroutes_total" in fams

    # one replica DEAD: scrape still prompt + parseable, health degrades
    router.kill_replica(0)
    deadline = time.monotonic() + 30
    while router.replicas[0].engine._thread.is_alive():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    fams, health = _scrape_promptly(loop, client)
    healthy = {
        labels["replica"]: v
        for _, labels, v in fams["dabt_engine_healthy"]["samples"]
    }
    assert healthy["tiny-chat/r0"] == 0.0 and healthy["tiny-chat/r1"] == 1.0
    assert health["status"] == "degraded"

    # MID-RESTART of the dead replica (on a worker thread, scraping racing it)
    import threading

    t = threading.Thread(target=router.restart_replica, args=(0,))
    t.start()
    try:
        fams, _ = _scrape_promptly(loop, client)
        assert "dabt_engine_healthy" in fams
    finally:
        t.join(timeout=60)
    assert not t.is_alive()
    fams, health = _scrape_promptly(loop, client)
    assert health["status"] == "ok"

    # MID-DRAIN: replica marked draining; scrape sees the flag and stays prompt
    router.replicas[1].draining = True
    try:
        fams, _ = _scrape_promptly(loop, client)
        draining = {
            labels["replica"]: v
            for _, labels, v in fams["dabt_replica_draining"]["samples"]
        }
        assert draining["tiny-chat/r1"] == 1.0
    finally:
        router.replicas[1].draining = False

    # traffic still serves after the duress tour
    loop.run_until_complete(asyncio.wait_for(warm(), 300))


def test_new_trace_id_shape():
    a, b = new_trace_id(), new_trace_id()
    assert a != b
    assert len(a) == 16 and all(c in "0123456789abcdef" for c in a)
