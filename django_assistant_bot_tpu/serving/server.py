"""TPU model server — the reference gpu_service's HTTP contract, aiohttp edition.

Endpoint parity (reference: gpu_service/main.py:75-107):

- ``POST /embeddings/`` ``{model, texts}`` -> ``{"embeddings": [[...], ...]}``
- ``POST /dialog/`` ``{model, messages, max_tokens, json_format}`` ->
  ``{"response": {"result": str, "usage": {...}, "length_limited": bool}}``
- 400 "Model is not supported" for unknown models; 500 with detail on failure.

Extras the reference lacks: ``GET /healthz`` (engine/slot stats) and ``GET /models``,
plus ``"stream": true`` on ``/dialog/`` — a ``text/event-stream`` response with
per-token delta events and a terminal usage event (wire format in
docs/STREAMING.md).  A mid-stream client disconnect cancels the engine request,
which frees its decode slot within one tick.  The non-streaming path is
byte-identical to before (the bench baseline).
One process, one mesh, engines shared across all requests — the continuous batcher
gives cross-request batching instead of gunicorn worker replicas.
"""

from __future__ import annotations

import asyncio
import json
import logging
import math
import re
import time
from typing import Any, Callable, Mapping, Optional

from aiohttp import web

from ..utils.device import device_info
from .engine import EngineUnavailable
from .kv_pool import WireIntegrityError, WireVersionError
from .obs import new_trace_id, rag_plane_snapshot, render_prometheus
from .registry import ModelRegistry
from .scheduler import DeadlineExceeded, SchedulerRejected

logger = logging.getLogger(__name__)

REGISTRY_KEY: web.AppKey[ModelRegistry] = web.AppKey("registry", ModelRegistry)
DRAIN_KEY: web.AppKey[dict] = web.AppKey("drain_state", dict)
FLEET_KEY: web.AppKey[Any] = web.AppKey("fleet_plane", object)

MAX_MAX_TOKENS = 1 << 17  # sanity ceiling; engines clamp to max_seq_len anyway
PRIORITIES = ("interactive", "background")

# client-supplied X-Request-Id values are echoed into headers and bodies:
# only token-safe shapes pass through (anything else — or nothing — gets a
# generated id), so a hostile header cannot smuggle CR/LF or grow unbounded
_REQ_ID_RE = re.compile(r"^[A-Za-z0-9._:-]{1,64}$")

# fleet idempotency keys (trace_id:attempt) share the token-safe shape but
# allow a little more length for the appended attempt ordinal
_IDEM_KEY_RE = re.compile(r"^[A-Za-z0-9._:-]{1,80}$")


def _request_id(request: web.Request) -> str:
    """The request's correlation id: the client's ``X-Request-Id`` when it is
    token-safe, else a fresh trace id.  Echoed on EVERY ``/dialog/`` response
    shape (JSON, SSE terminal event, 4xx/5xx error bodies) so a shed 429 and
    the client retry that follows correlate by one id."""
    rid = request.headers.get("X-Request-Id", "").strip()
    if rid and _REQ_ID_RE.match(rid):
        return rid
    return new_trace_id()


def _draining_response(rid: Optional[str] = None) -> web.Response:
    """Graceful shutdown in progress: stop admitting, finish in-flight work.
    New requests get an honest 503 + Retry-After instead of being accepted
    and then killed mid-generation by process exit."""
    body = {"detail": "server draining for shutdown"}
    headers = {"Retry-After": "2"}
    if rid is not None:
        body["request_id"] = rid
        headers["X-Request-Id"] = rid
    return web.json_response(body, status=503, headers=headers)


class _BadRequest(ValueError):
    """Validation failure carrying the client-facing detail message."""


def _validate_sampling(body: Mapping[str, Any]) -> tuple:
    """Pull and range-check the sampling knobs.  NaN/negative/huge values used
    to flow straight into the device sampler (NaN temperature poisons the
    whole batched softmax row); they are a 422 now."""
    temperature = body.get("temperature", 0.8)
    top_p = body.get("top_p", 0.95)
    max_tokens = body.get("max_tokens", 1024)
    if isinstance(temperature, bool) or not isinstance(temperature, (int, float)):
        raise _BadRequest("temperature must be a number")
    temperature = float(temperature)
    if not math.isfinite(temperature) or not (0.0 <= temperature <= 2.0):
        raise _BadRequest("temperature must be finite and within [0, 2]")
    if isinstance(top_p, bool) or not isinstance(top_p, (int, float)):
        raise _BadRequest("top_p must be a number")
    top_p = float(top_p)
    if not math.isfinite(top_p) or not (0.0 < top_p <= 1.0):
        raise _BadRequest("top_p must be finite and within (0, 1]")
    if isinstance(max_tokens, bool) or not isinstance(max_tokens, int):
        raise _BadRequest("max_tokens must be an integer")
    if not (1 <= max_tokens <= MAX_MAX_TOKENS):
        raise _BadRequest(f"max_tokens must be within [1, {MAX_MAX_TOKENS}]")
    return temperature, top_p, max_tokens


def _scheduling_fields(
    request: web.Request, body: Mapping[str, Any]
) -> tuple[str, str, Optional[float]]:
    """Priority class, fair-share tenant and deadline: body fields win,
    ``X-Priority`` / ``X-Tenant`` / ``X-Deadline-S`` headers are the fallback
    (so proxies can tag traffic without rewriting bodies)."""
    priority = body.get("priority", request.headers.get("X-Priority", "interactive"))
    if priority not in PRIORITIES:
        raise _BadRequest(f"priority must be one of {list(PRIORITIES)}")
    tenant = body.get("tenant", request.headers.get("X-Tenant", "default"))
    if not isinstance(tenant, str) or not tenant.strip() or len(tenant) > 128:
        raise _BadRequest("tenant must be a non-empty string of <= 128 chars")
    deadline_s = body.get("deadline_s", request.headers.get("X-Deadline-S"))
    if deadline_s is not None:
        try:
            deadline_s = float(deadline_s)
        except (TypeError, ValueError):
            raise _BadRequest("deadline_s must be a number") from None
        if not math.isfinite(deadline_s) or not (0.0 < deadline_s <= 3600.0):
            raise _BadRequest("deadline_s must be finite and within (0, 3600]")
    return priority, tenant.strip(), deadline_s


def _with_rid(body: dict, rid: Optional[str], headers: Optional[dict] = None):
    """(body, headers) with the correlation id riding both (None = no id)."""
    headers = dict(headers or {})
    if rid is not None:
        body["request_id"] = rid
        headers["X-Request-Id"] = rid
    return body, headers


def _shed_response(e: SchedulerRejected, rid: Optional[str] = None) -> web.Response:
    """Load shed -> 429 with a Retry-After back-off hint."""
    retry = max(1, math.ceil(e.retry_after_s))
    body, headers = _with_rid(
        {"detail": str(e), "reason": e.reason, "retry_after_s": e.retry_after_s},
        rid,
        {"Retry-After": str(retry)},
    )
    return web.json_response(body, status=429, headers=headers)


def _unavailable_response(
    e: EngineUnavailable, rid: Optional[str] = None
) -> web.Response:
    """Engine restart circuit open -> 503 with a Retry-After covering the
    remaining degraded cooldown (docs/RESILIENCE.md)."""
    retry = max(1, math.ceil(e.retry_after_s))
    body, headers = _with_rid(
        {"detail": str(e), "retry_after_s": e.retry_after_s},
        rid,
        {"Retry-After": str(retry)},
    )
    return web.json_response(body, status=503, headers=headers)


def _error_response(detail: str, status: int, rid: str) -> web.Response:
    body, headers = _with_rid({"detail": detail}, rid)
    return web.json_response(body, status=status, headers=headers)


def _usage(model: str, result) -> dict:
    return result.usage_dict(model)


def _sse(payload) -> bytes:
    data = payload if isinstance(payload, str) else json.dumps(payload)
    return f"data: {data}\n\n".encode("utf-8")


class _StreamLag:
    """Engine stamp of a token -> the moment its delta is handed to the
    socket, over one streamed response: what the asyncio hop, the
    detokenizer and the event loop's backlog add between tokens."""

    __slots__ = ("max_s", "sum_s", "events")

    def __init__(self) -> None:
        self.max_s = 0.0
        self.sum_s = 0.0
        self.events = 0

    def note(self, at: Optional[float], now: float) -> None:
        if at is None:
            return
        lag = max(0.0, now - at)
        self.events += 1
        self.sum_s += lag
        if lag > self.max_s:
            self.max_s = lag

    def close(self, timings: Optional[dict], now: float) -> None:
        """Stamp the terminal event's hand-over into the request's timings
        (the dict the /traces record shares): ``deliver_s`` runs from the end
        of the engine's detokenisation, so the spans stay back to back."""
        if timings is None:
            return
        finished = timings["recv_mono_s"] + sum(
            timings[k] for k in ("encode_s", "queue_s", "prefill_s", "decode_s", "detok_s")
        )
        timings.update(
            deliver_s=max(0.0, now - finished),
            stream_lag_max_s=self.max_s,
            stream_lag_sum_s=self.sum_s,
            stream_events=self.events,
        )


async def _stream_dialog(
    request: web.Request,
    eng,
    model: str,
    messages,
    rid: str,
    *,
    clock: Callable[[], float],
    **gen_kwargs,
) -> web.StreamResponse:
    """``"stream": true`` -> ``text/event-stream`` (wire format in
    docs/STREAMING.md): one ``data:`` event per emitted text delta, a terminal
    event carrying finish reason + usage + the full result text, then a
    literal ``[DONE]``.

    The FIRST chunk is awaited before the response is prepared so synchronous
    failures (load shed, infeasible deadline, bad request) still map to their
    proper HTTP statuses; later failures surface as an ``error`` event on the
    open stream.  A client disconnect mid-stream abandons the generator, whose
    cleanup cancels the engine request — the per-iteration reap then frees the
    decode slot within one tick (the deadline epoch mechanism)."""
    agen = eng.generate_stream(messages, trace_id=rid, **gen_kwargs)
    try:
        first = await agen.__anext__()
    except StopAsyncIteration:
        first = None
    except SchedulerRejected as e:
        return _shed_response(e, rid)
    except EngineUnavailable as e:
        return _unavailable_response(e, rid)
    except DeadlineExceeded as e:
        return _error_response(str(e), 504, rid)
    except Exception as e:
        logger.exception("stream dialog failed before first token")
        return _error_response(str(e), 500, rid)

    resp = web.StreamResponse(
        status=200,
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "X-Accel-Buffering": "no",
            "X-Request-Id": rid,
        },
    )
    await resp.prepare(request)
    lag = _StreamLag()
    try:
        chunk = first
        while chunk is not None:
            if chunk.done:
                if chunk.text:  # flushed hold-back tail rides its own event
                    await resp.write(
                        _sse({"delta": chunk.text, "index": chunk.index})
                    )
                r = chunk.result
                lag.close(r.timings, clock())
                await resp.write(
                    _sse(
                        {
                            "done": True,
                            "finish_reason": chunk.finish_reason,
                            "result": r.text,
                            "usage": _usage(model, r),
                            "length_limited": r.length_limited,
                            "request_id": rid,
                        }
                    )
                )
                break
            if chunk.text:
                lag.note(chunk.at, clock())
                await resp.write(_sse({"delta": chunk.text, "index": chunk.index}))
            try:
                chunk = await agen.__anext__()
            except StopAsyncIteration:
                break
        await resp.write(_sse("[DONE]"))
        await resp.write_eof()
    except (
        asyncio.CancelledError,
        ConnectionResetError,
        ConnectionError,
    ):
        # client went away mid-stream; the finally's aclose() cancels the
        # engine request so its slot frees within one decode tick
        logger.info("stream client disconnected mid-generation")
        raise
    except Exception as e:
        # already committed to 200: surface the failure as an error event
        logger.exception("stream dialog failed mid-stream")
        try:
            await resp.write(
                _sse(
                    {
                        "done": True,
                        "finish_reason": "error",
                        "error": str(e),
                        "request_id": rid,
                    }
                )
            )
            await resp.write(_sse("[DONE]"))
            await resp.write_eof()
        except (ConnectionResetError, ConnectionError):
            pass
    finally:
        await agen.aclose()
    return resp


def create_app(
    registry: ModelRegistry,
    *,
    drain_deadline_s: float = 30.0,
    clock: Callable[[], float] = time.monotonic,
) -> web.Application:
    """``clock`` stamps a request's receipt and its events' hand-over to the
    socket; it has to be the engines' clock (``time.monotonic`` in
    production) for ``usage.timings`` to tile."""
    app = web.Application()
    app[REGISTRY_KEY] = registry
    # graceful-drain state (the SIGTERM path, docs/RESILIENCE.md): once the
    # flag flips, admission endpoints 503 and on_shutdown waits — bounded by
    # drain_deadline_s — for every engine to finish what it already accepted
    # before on_cleanup stops the engines (which fails anything left).
    drain = {"draining": False, "deadline_s": float(drain_deadline_s)}
    app[DRAIN_KEY] = drain
    # the engines already hold the backend; read once, report on every probe
    device = device_info()

    async def embeddings(request: web.Request) -> web.Response:
        if drain["draining"]:
            return _draining_response()
        try:
            body = await request.json()
            model, texts = body["model"], body["texts"]
            if not isinstance(model, str):
                raise ValueError("model must be a string")
            if not isinstance(texts, list) or not all(isinstance(t, str) for t in texts):
                raise ValueError("texts must be a list of strings")
        except Exception:
            return web.json_response({"detail": "invalid request"}, status=422)
        eng = registry.get_embedder(model)
        if eng is None:
            return web.json_response({"detail": "Model is not supported"}, status=400)
        try:
            embs = await eng.embed(texts)
            return web.json_response({"embeddings": embs})
        except SchedulerRejected as e:
            return _shed_response(e)
        except Exception as e:
            logger.exception("embeddings failed")
            return web.json_response({"detail": str(e)}, status=500)

    async def dialog(request: web.Request) -> web.Response:
        received_at = clock()  # usage.timings: the start of encode_s
        rid = _request_id(request)
        if drain["draining"]:
            return _draining_response(rid)
        try:
            body = await request.json()
            model = body["model"]
            if not isinstance(model, str):
                raise ValueError("model must be a string")
            messages = body["messages"]
            json_format = bool(body.get("json_format", False))
            stream = body.get("stream", False)
            if not isinstance(stream, bool):
                raise _BadRequest("stream must be a boolean")
            if stream and json_format:
                # documented choice (docs/STREAMING.md): constrained-JSON
                # output is only validated as a whole document, and partial
                # JSON is not independently consumable — reject rather than
                # pretend chunks are usable
                raise _BadRequest(
                    "stream is not supported with json_format; "
                    "request one or the other"
                )
            temperature, top_p, max_tokens = _validate_sampling(body)
            priority, tenant, deadline_s = _scheduling_fields(request, body)
        except _BadRequest as e:
            return _error_response(str(e), 422, rid)
        except Exception:
            return _error_response("invalid request", 422, rid)
        eng = registry.get_generator(model)
        if eng is None:
            return _error_response("Model is not supported", 400, rid)
        if stream:
            return await _stream_dialog(
                request,
                eng,
                model,
                messages,
                rid,
                clock=clock,
                max_tokens=max_tokens,
                temperature=temperature,
                top_p=top_p,
                priority=priority,
                tenant=tenant,
                deadline_s=deadline_s,
                received_at=received_at,
            )
        try:
            # json_format enables grammar-constrained decoding: a JSON token-FSM
            # masks sampling inside the decode tick (ops/json_fsm.py), so the
            # output is valid JSON in one shot even at high temperature — the
            # reference instead retries with an LLM repair loop
            # (assistant/ai/providers/ollama.py:49-107)
            result = await eng.generate(
                messages,
                max_tokens=max_tokens,
                temperature=temperature,
                top_p=top_p,
                json_format=json_format,
                priority=priority,
                tenant=tenant,
                deadline_s=deadline_s,
                trace_id=rid,
                received_at=received_at,
            )
            return web.json_response(
                {
                    "response": {
                        "result": result.text,
                        "usage": _usage(model, result),
                        "length_limited": result.length_limited,
                    },
                    "request_id": rid,
                },
                headers={"X-Request-Id": rid},
            )
        except SchedulerRejected as e:
            return _shed_response(e, rid)
        except EngineUnavailable as e:
            return _unavailable_response(e, rid)
        except DeadlineExceeded as e:
            return _error_response(str(e), 504, rid)
        except Exception as e:
            logger.exception("dialog failed")
            return _error_response(str(e), 500, rid)

    async def healthz(request: web.Request) -> web.Response:
        # status degrades when ANY generator is unhealthy: restart circuit
        # open, engine thread dead, or a loop heartbeat older than the
        # threshold (a wedged XLA call used to keep reporting green here)
        status = "draining" if drain["draining"] else "ok"
        generators = {}
        for name, eng in registry.generators.items():
            g = {
                "active_slots": eng.num_active,
                "steps": eng.steps,
                "reclaimed_slots": getattr(eng, "reclaimed_slots", 0),
            }
            latency = getattr(eng, "latency_stats", None)
            if callable(latency):
                # TTFT / inter-token-latency percentiles + disconnect count —
                # the streaming plane's perceived-latency dashboard
                g["stream"] = latency()
            kv = getattr(eng, "kv_stats", None)
            if callable(kv):
                # KV memory plane gauges: pool occupancy, shared-page
                # fraction, allocator eviction/COW counters (docs/KV_PAGING.md)
                g["kv"] = kv()
            sl = getattr(eng, "slice_stats", None)
            if callable(sl):
                # mesh-sliced fleet (docs/MULTICHIP.md): slice identity +
                # per-slice HBM ledger per replica; routers add the planner's
                # total/free slice capacity (scale-up headroom)
                g["slices"] = sl()
            dec = getattr(eng, "decode_path_stats", None)
            if callable(dec):
                # decode fast-path gauges (docs/QUANT.md): fused-tick depth
                # configured vs effective (json downgrade), weight bits, and
                # the double-buffered upload fraction — which fast path is
                # ACTUALLY active
                g["decode"] = dec()
            spec = getattr(eng, "spec_stats", None)
            if callable(spec):
                # speculative-decoding gauges: accept rate/EMA (per tree
                # arm), the rung in use, and load- vs acceptance-disable —
                # None (omitted) on non-speculative engines
                sv = spec()
                if sv is not None:
                    g["spec"] = sv
            sched = getattr(eng, "scheduler", None)
            if sched is not None:
                # queue depth, shed counters, per-class wait percentiles —
                # the operator's overload dashboard (KV-pressure sheds appear
                # under sched.shed.kv_pressure, distinct from queue_full)
                g["sched"] = sched.stats()
            router = getattr(eng, "router_stats", None)
            if callable(router):
                # multi-replica fleet gauges: per-replica depth/breaker,
                # affinity hit rate, re-routes, drains, scale events
                # (serving/router.py)
                g["router"] = router()
            asc = getattr(registry, "autoscalers", {}).get(name)
            if asc is not None:
                # SLO autoscaler: current band/decision, fleet bounds, scale
                # and degradation counters (serving/autoscaler.py)
                g["autoscaler"] = asc.stats()
            sup = getattr(eng, "supervision_stats", None)
            if callable(sup):
                # restart/quarantine/circuit counters + loop_heartbeat_age_s
                # (routers aggregate: one unhealthy replica of N degrades the
                # fleet status, with per-replica blocks under "replicas")
                g["supervision"] = sv = sup()
                if not sv.get("healthy", True) and status == "ok":
                    status = "degraded"
            generators[name] = g
        payload = {
            "status": status,
            # platform / device_kind / count as JAX reports them: a server
            # that came up on the CPU says so here (utils/device.py)
            "device": device,
            # set-up wall times per model (checkpoint read + placement, and
            # warm-up compiles) — not rates
            "boot_s": getattr(registry, "boot_s", {}),
            "models": sorted(registry.specs),
            "generators": generators,
            "embedders": {
                name: {
                    "queue_depth": eng._queue.qsize(),
                    "max_queue": getattr(eng, "max_queue", 0),
                    "shed": getattr(eng, "shed", 0),
                    "dropped_cancelled": getattr(eng, "dropped_cancelled", 0),
                }
                for name, eng in registry.embedders.items()
            },
        }
        # RAG plane (when this process has built vector indexes): per-index
        # engine kind + the ANN recall/drift gauges (docs/ANN.md)
        rag = rag_plane_snapshot()
        if rag.get("indexes"):
            # durability roll-up across WAL+snapshot-backed indexes
            # (docs/DURABILITY.md): one block an operator can alert on
            # without walking per-index stats.  A corrupt-snapshot fallback
            # or a lost WAL flock degrades health — both mean the durable
            # plane is serving, but not the way it was configured to.
            durables = [
                (name, st["durability"])
                for name, st in sorted(rag["indexes"].items())
                if isinstance(st, dict) and st.get("durability")
            ]
            if durables:
                ages = [d["snapshot_age_s"] for _, d in durables if d.get("snapshot_age_s") is not None]
                rag["durability"] = {
                    "indexes": len(durables),
                    "writable": sum(1 for _, d in durables if d.get("writable")),
                    "wal_records": sum(int(d.get("wal_records") or 0) for _, d in durables),
                    "wal_bytes": sum(int(d.get("wal_bytes") or 0) for _, d in durables),
                    "oldest_snapshot_age_s": max(ages) if ages else None,
                    "replayed_records": sum(int(d.get("replayed_records") or 0) for _, d in durables),
                    "snapshot_fallbacks": sum(int(d.get("snapshot_fallbacks") or 0) for _, d in durables),
                    "torn_tail_truncations": sum(int(d.get("torn_tail_truncations") or 0) for _, d in durables),
                }
                if rag["durability"]["snapshot_fallbacks"] and status == "ok":
                    payload["status"] = status = "degraded"
            payload["rag"] = rag
        return web.json_response(payload)

    async def models(request: web.Request) -> web.Response:
        return web.json_response(
            {
                name: {"kind": spec.kind, "path": spec.path, "tiny": spec.tiny}
                for name, spec in registry.specs.items()
            }
        )

    async def metrics(request: web.Request) -> web.Response:
        # Prometheus text exposition (docs/OBSERVABILITY.md).  Deliberately
        # NOT gated on the drain flag: a draining/degraded fleet is exactly
        # when the scrape matters.  render_prometheus is a pure read path —
        # every stats surface does its own fine-grained locking, and no
        # router lock is ever held across an engine call (the PR 7 ABBA
        # family; witness-covered by the CI obs smoke).
        try:
            text = render_prometheus(registry)
        except Exception:
            logger.exception("/metrics render failed")
            return web.Response(status=500, text="metrics render failed")
        return web.Response(
            body=text.encode("utf-8"),
            headers={"Content-Type": "text/plain; version=0.0.4; charset=utf-8"},
        )

    # ---------------------------------------------------------- fleet plane
    # The cross-process wire (serving/fleet.py, docs/FLEET.md).  A plane
    # attached by the CLI (pool role, peer list) is reused; otherwise a
    # default unified plane is created so every serve process speaks the
    # fleet protocol out of the box.
    plane = getattr(registry, "fleet_plane", None)
    if plane is None:
        from .fleet import FleetPlane

        plane = FleetPlane(registry)
        registry.fleet_plane = plane
    app[FLEET_KEY] = plane

    def _validate_prompt_ids(body: Mapping[str, Any]) -> list:
        ids = body.get("prompt_ids")
        if (
            not isinstance(ids, list)
            or not ids
            or len(ids) > MAX_MAX_TOKENS
            or not all(
                isinstance(t, int) and not isinstance(t, bool) and t >= 0
                for t in ids
            )
        ):
            raise _BadRequest(
                "prompt_ids must be a non-empty list of non-negative ints"
            )
        return ids

    async def fleet_generate(request: web.Request) -> web.Response:
        """Token-level dialog contract for FleetRouter peers: prompt_ids in,
        token_ids + usage out (detokenized text rides along).  Honors the
        same sampling/scheduling validation as /dialog/, plus the fleet
        extras: prefix_len (warm-prefix restore), prefill_only + push_to
        (the disaggregated handoff), and force (pool-role bypass)."""
        received_at = clock()
        rid = _request_id(request)
        if drain["draining"]:
            return _draining_response(rid)
        try:
            body = await request.json()
            model = body["model"]
            if not isinstance(model, str):
                raise _BadRequest("model must be a string")
            prompt_ids = _validate_prompt_ids(body)
            temperature, top_p, max_tokens = _validate_sampling(body)
            priority, tenant, deadline_s = _scheduling_fields(request, body)
            json_format = bool(body.get("json_format", False))
            prefill_only = bool(body.get("prefill_only", False))
            force = bool(body.get("force", False))
            push_to = body.get("push_to")
            if push_to is not None and not isinstance(push_to, str):
                raise _BadRequest("push_to must be a string URL")
            prefix_len = body.get("prefix_len", 0)
            if (
                isinstance(prefix_len, bool)
                or not isinstance(prefix_len, int)
                or prefix_len < 0
            ):
                raise _BadRequest("prefix_len must be a non-negative integer")
            trace_id = body.get("trace_id") or rid
            if not isinstance(trace_id, str) or not _REQ_ID_RE.match(trace_id):
                trace_id = rid
            idem_key = body.get("idem_key")
            if idem_key is not None and (
                not isinstance(idem_key, str)
                or not _IDEM_KEY_RE.match(idem_key)
            ):
                idem_key = None  # malformed keys never gate execution
        except _BadRequest as e:
            return _error_response(str(e), 422, rid)
        except Exception:
            return _error_response("invalid request", 422, rid)
        eng = registry.get_generator(model)
        if eng is None:
            return _error_response("Model is not supported", 400, rid)
        # idempotent dispatch: a timeout-retry carrying the same key gets the
        # ORIGINAL result back (or coalesces onto the in-flight execution)
        # instead of re-executing — double execution is the failure the chaos
        # bench counts to zero
        idem_fut = None
        if idem_key is not None:
            for _ in range(2):
                state, f = plane.idem_claim(idem_key)
                if state == "mine":
                    idem_fut = f
                    break
                prior = await asyncio.wrap_future(f)
                if prior is not None:
                    return web.json_response(
                        {**prior, "deduped": True, "request_id": rid},
                        headers={"X-Request-Id": rid},
                    )
                # the owning execution failed and released — claim afresh
        completed = False
        try:
            rej = plane.admission_guard(
                model,
                eng,
                prompt_ids,
                prefix_len,
                prefill_only=prefill_only,
                force=force,
            )
            if rej is not None:
                return _shed_response(rej, rid)
            if prefill_only:
                # the handoff contract: full-prefix chunked prefill, one token
                # emitted, background class — the scheduler tag that keeps
                # handoff traffic distinct from interactive decode
                max_tokens = 1
                temperature = 0.0
                priority = "background"
                prefix_len = max(prefix_len, len(prompt_ids) - 1)
            try:
                fut = eng.submit(
                    prompt_ids,
                    max_tokens=max_tokens,
                    temperature=temperature,
                    top_p=top_p,
                    json_format=json_format,
                    prefix_len=prefix_len,
                    priority=priority,
                    tenant=tenant,
                    deadline_s=deadline_s,
                    trace_id=trace_id,
                    received_at=received_at,
                )
                result = await asyncio.wrap_future(fut)
            except SchedulerRejected as e:
                return _shed_response(e, rid)
            except EngineUnavailable as e:
                return _unavailable_response(e, rid)
            except DeadlineExceeded as e:
                return _error_response(str(e), 504, rid)
            except ValueError as e:
                return _error_response(str(e), 422, rid)
            except Exception as e:
                logger.exception("fleet generate failed")
                return _error_response(str(e), 500, rid)
            resp = {
                "token_ids": [int(t) for t in result.token_ids],
                "result": result.text,
                "usage": _usage(model, result),
                "length_limited": result.length_limited,
                "request_id": rid,
                "trace_id": trace_id,
            }
            if prefill_only:
                # export + push the finished prefix pages off the event loop
                resp["handoff"] = await asyncio.get_running_loop().run_in_executor(
                    None, plane.handoff_export, model, prompt_ids, prefix_len, push_to
                )
            if idem_fut is not None:
                plane.idem_complete(idem_key, idem_fut, resp)
                completed = True
            return web.json_response(resp, headers={"X-Request-Id": rid})
        finally:
            # every non-success exit (shed, 5xx, deadline, cancellation)
            # releases the ledger entry so a retry re-executes cleanly
            if idem_fut is not None and not completed:
                plane.idem_release(idem_key, idem_fut)

    async def fleet_healthz(request: web.Request) -> web.Response:
        check = request.query.get("peers", "1") not in ("0", "false")
        body = await asyncio.get_running_loop().run_in_executor(
            None, lambda: plane.healthz(check_peers=check)
        )
        if drain["draining"]:
            body["status"] = "draining"
        return web.json_response(body)

    async def fleet_prefix(request: web.Request) -> web.Response:
        try:
            since = int(request.query.get("since", "0"))
        except ValueError:
            return web.json_response(
                {"detail": "since must be an integer"}, status=422
            )
        return web.json_response(plane.prefix_events(since))

    async def fleet_kv_get(request: web.Request) -> web.Response:
        # deliberately NOT drain-gated: page migration off a draining peer
        # is exactly when this endpoint matters
        try:
            body = await request.json()
            model = body["model"]
            if not isinstance(model, str):
                raise _BadRequest("model must be a string")
            prompt_ids = _validate_prompt_ids(body)
            prefix_len = body.get("prefix_len", 0)
            if (
                isinstance(prefix_len, bool)
                or not isinstance(prefix_len, int)
                or prefix_len < 0
            ):
                raise _BadRequest("prefix_len must be a non-negative integer")
        except _BadRequest as e:
            return web.json_response({"detail": str(e)}, status=422)
        except Exception:
            return web.json_response({"detail": "invalid request"}, status=422)
        try:
            data = await asyncio.get_running_loop().run_in_executor(
                None, plane.kv_get_wire, model, prompt_ids, prefix_len
            )
        except KeyError:
            return web.json_response(
                {"detail": "Model is not supported"}, status=400
            )
        except Exception as e:
            logger.exception("fleet kv get failed")
            return web.json_response({"detail": str(e)}, status=500)
        if data is None:
            return web.json_response({"detail": "no matching prefix"}, status=404)
        return web.Response(
            body=data, content_type="application/octet-stream"
        )

    async def fleet_kv_put(request: web.Request) -> web.Response:
        model = request.query.get("model", "")
        data = await request.read()
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, plane.kv_put_wire, model, data
            )
        except WireVersionError as e:
            # cross-build peer: fail loudly, never absorb pages we cannot
            # prove we understand (the versioned-wire contract)
            return web.json_response({"detail": str(e)}, status=409)
        except WireIntegrityError as e:
            # checksum-failed payload: machine-readable reason so the
            # puller's one-re-fetch-then-cold-prefill policy can key off it
            return web.json_response(
                {"detail": str(e), "reason": "wire_integrity"}, status=422
            )
        except KeyError:
            return web.json_response(
                {"detail": "Model is not supported"}, status=400
            )
        except ValueError as e:
            return web.json_response({"detail": str(e)}, status=422)
        except Exception as e:
            logger.exception("fleet kv put failed")
            return web.json_response({"detail": str(e)}, status=500)
        return web.json_response(out)

    async def traces(request: web.Request) -> web.Response:
        """Obs trace rings across every engine, flattened — the surface the
        trace-export CLI replays through workload/ (cli/trace_export.py)."""
        return web.json_response({"traces": plane.collect_traces()})

    app.router.add_post("/embeddings/", embeddings)
    app.router.add_post("/embeddings", embeddings)
    app.router.add_post("/dialog/", dialog)
    app.router.add_post("/dialog", dialog)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/models", models)
    app.router.add_post("/fleet/generate", fleet_generate)
    app.router.add_get("/fleet/healthz", fleet_healthz)
    app.router.add_get("/fleet/prefix", fleet_prefix)
    app.router.add_post("/fleet/kv/get", fleet_kv_get)
    app.router.add_post("/fleet/kv/put", fleet_kv_put)
    app.router.add_get("/traces", traces)

    async def on_shutdown(app):
        # SIGTERM graceful drain: web.run_app's signal handling triggers
        # app.shutdown() BEFORE on_cleanup, while in-flight handlers still
        # run.  Stop admission (the endpoints 503 via the flag), then wait —
        # deadline-bounded — for every engine to finish what it accepted, so
        # the on_cleanup stop() below finds nothing to kill.  A single
        # --replicas 1 engine drains exactly the same way; routers
        # additionally stop their own dispatch fleet-wide.
        drain["draining"] = True
        for eng in registry.generators.values():
            begin = getattr(eng, "begin_drain", None)
            if callable(begin):
                # routers stop their own dispatch too (non-blocking mark;
                # the poll below is the single wait loop)
                begin()
        deadline = asyncio.get_running_loop().time() + drain["deadline_s"]
        while asyncio.get_running_loop().time() < deadline:
            if registry.idle():
                logger.info("graceful drain complete; shutting down")
                return
            await asyncio.sleep(0.05)
        logger.warning(
            "graceful drain deadline (%.1fs) expired with work in flight; "
            "remaining requests fail on engine stop",
            drain["deadline_s"],
        )

    async def on_cleanup(app):
        registry.stop()

    app.on_shutdown.append(on_shutdown)
    app.on_cleanup.append(on_cleanup)
    return app


def load_config_file(path: str) -> Mapping[str, Any]:
    """TOML or JSON model config: ``[models.<name>] kind=... path=...``."""
    import json

    if path.endswith(".toml"):
        import tomllib

        with open(path, "rb") as f:
            data = tomllib.load(f)
    else:
        with open(path) as f:
            data = json.load(f)
    return data.get("models", data)


def run_server(
    config_path: str | None = None,
    *,
    host: str = "0.0.0.0",
    port: int = 11435,
    registry: ModelRegistry | None = None,
    drain_deadline_s: float = 30.0,
):
    """Blocking entry (CLI ``serve``).  Default port matches the reference
    (11435).  SIGTERM/SIGINT trigger a graceful drain: admission stops (503),
    in-flight work finishes within ``drain_deadline_s``, then the process
    exits 0 — a rolling restart sheds nothing instead of killing mid-stream
    generations."""
    if registry is None:
        config = load_config_file(config_path) if config_path else {}
        registry = ModelRegistry.from_config(config)
    web.run_app(
        create_app(registry, drain_deadline_s=drain_deadline_s),
        host=host,
        port=port,
    )
