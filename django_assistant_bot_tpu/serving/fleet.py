"""Cross-process fleet plane: the single-process router's contract, one level up.

PR 15 scaled the fleet with chips inside ONE process (mesh-sliced replicas);
this module distributes it across processes/hosts.  Three pieces
(docs/FLEET.md):

- **Wire protocol + peer client.**  Every ``serve`` process exposes a small
  fleet API next to its serving endpoints: ``/fleet/healthz`` (supervision/
  breaker/slice summary), ``/fleet/prefix`` (prefix-registry gossip deltas),
  ``/fleet/kv/put|get`` (prefix KV pages in the PR 12 device-agnostic numpy
  snapshot format, wrapped in the versioned dtype-tagged wire encoding below
  — fp8/int8 pools round-trip bit-exactly), and ``/fleet/generate`` (the
  token-level dialog contract the :class:`FleetRouter` dispatches on).

- **Cross-process prefix registry.**  Each process's :class:`FleetPlane`
  keeps a seq-numbered delta log of its local KV tier-transition events
  (fed by the same listener chain the in-process
  :class:`~.router.FleetPrefixRegistry` reads); followers poll
  ``/fleet/prefix`` and apply the deltas into their OWN FleetPrefixRegistry,
  so affinity routes a returning session to the PROCESS that holds its warm
  pages — and a missing local prefix can be *pulled* from the holder over
  ``/fleet/kv/get`` into the target's host tier ahead of suffix prefill
  (the restore path itself is unchanged).

- **Disaggregated prefill/decode pools.**  A ``--pool`` role knob: prefill
  processes run chunked prefill only (``prefill_only`` requests, background
  class — the scheduler tag that already distinguishes the traffic), write
  finished pages through the host tier, push them to the decode pool over
  the wire, and hand off; decode processes admit via restore and REJECT
  long prefill (``pool_role`` shed), so decode ITL is isolated from
  long-prompt arrivals.  When a whole pool is dead, availability beats role
  purity: the router retries with ``force`` and the bypass is counted.

The :class:`FleetRouter` mirrors :meth:`EngineRouter.submit`'s exact
contract (same kwargs, a ``concurrent.futures.Future`` result) and its
dispatch precedence — health first (peer healthz + per-peer
:class:`~..ai.providers.failover.CircuitBreaker`), prefix affinity second
(the gossip-fed registry), least-loaded last with a rotating tie-break —
with token-less re-route on peer death (non-streaming requests are
token-less by construction until the response lands) and trace_id
propagation end to end.

Thread contract: the router dispatches on a small worker pool (one wire
round-trip per request thread); counters live under one leaf lock; no
future is ever resolved under it (dabtlint DABT102) and every timestamp
flows through the injectable ``clock``/``sleep`` (DABT105).
"""

from __future__ import annotations

import http.client
import json
import logging
import os
import threading
import time
import urllib.parse
from collections import OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ai.providers.failover import CircuitBreaker
from .engine import EngineUnavailable
from .faults import FaultInjector, global_injector
from .kv_pool import (
    KV_WIRE_COMPAT_VERSIONS,
    KV_WIRE_VERSION,
    TIER_DISK,
    TIER_HBM,
    TIER_HOST,
    HostPrefixEntry,
    WireDecodeError,
    WireIntegrityError,
    WireVersionError,
)
from ..storage.integrity import crc32c, entry_crc32c
from .obs import FlightRecorder, new_trace_id
from .scheduler import DeadlineExceeded, SchedulerRejected

logger = logging.getLogger(__name__)

_TIER_RANK = {TIER_HBM: 0, TIER_HOST: 1, TIER_DISK: 2}

# ---------------------------------------------------------------- wire codec
# Layout: MAGIC | uint32-LE header length | JSON header | k bytes | v bytes.
# The header is dtype-tagged exactly like the PR 12 disk format (raw uint8
# views + a dtype STRING re-resolved on the receiver), so fp8/bf16/int8
# pools round-trip bit-exactly across processes and builds that agree on
# KV_WIRE_VERSION — and fail loudly across builds that don't.  Since wire v2
# the header also carries a CRC-32C of the k+v body, verified on decode; v1
# payloads (no checksum) still decode, per KV_WIRE_COMPAT_VERSIONS.
KV_WIRE_MAGIC = b"DABTKV"

# The versions THIS decoder accepts (module-level so a test can emulate an
# old decoder meeting a new payload by narrowing it).
WIRE_ACCEPT_VERSIONS = KV_WIRE_COMPAT_VERSIONS


def _resolve_dtype(name: str) -> np.dtype:
    """``np.dtype`` from its string name; ml_dtypes names (float8_e4m3fn,
    bfloat16, ...) resolve once ml_dtypes has registered them."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # noqa: F401  (registers the extended dtypes)

        return np.dtype(name)


def encode_kv_entry(entry: HostPrefixEntry) -> bytes:
    """One :class:`HostPrefixEntry` -> wire bytes (see module docstring)."""
    k = np.ascontiguousarray(entry.k)
    v = np.ascontiguousarray(entry.v)
    header = {
        "wire_version": KV_WIRE_VERSION,
        "key": [int(t) for t in entry.key],
        "length": int(entry.length),
        "dtype": str(k.dtype),
        "k_shape": list(k.shape),
        "v_shape": list(v.shape),
        "k_nbytes": int(k.nbytes),
        "v_nbytes": int(v.nbytes),
        "crc32c": entry_crc32c(k, v),
    }
    hb = json.dumps(header, separators=(",", ":")).encode("utf-8")
    return b"".join(
        [
            KV_WIRE_MAGIC,
            len(hb).to_bytes(4, "little"),
            hb,
            k.view(np.uint8).tobytes(),
            v.view(np.uint8).tobytes(),
        ]
    )


def decode_kv_entry(data: bytes) -> HostPrefixEntry:
    """Wire bytes -> :class:`HostPrefixEntry` (numpy arrays in the sender's
    exact dtype).  Raises :class:`WireVersionError` for a payload stamped by
    a build outside ``WIRE_ACCEPT_VERSIONS``, :class:`WireIntegrityError`
    when the payload's CRC-32C does not match its bytes, and
    :class:`WireDecodeError` for anything malformed (truncation at any
    envelope boundary, bad magic, unreadable header, body/metadata mismatch)
    — the receiver must never guess at bytes it cannot prove it understands.
    All three are ``ValueError`` subclasses, so pre-CRC callers still catch
    them."""
    m = len(KV_WIRE_MAGIC)
    if len(data) < m + 4 or data[:m] != KV_WIRE_MAGIC:
        raise WireDecodeError("not a DABT KV wire payload (bad magic)")
    hlen = int.from_bytes(data[m : m + 4], "little")
    if len(data) < m + 4 + hlen:
        raise WireDecodeError("truncated KV wire payload (header)")
    try:
        header = json.loads(data[m + 4 : m + 4 + hlen].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise WireDecodeError(f"unreadable KV wire header: {e}") from None
    if not isinstance(header, dict):
        raise WireDecodeError("unreadable KV wire header: not a JSON object")
    ver = header.get("wire_version")
    if ver not in WIRE_ACCEPT_VERSIONS:
        raise WireVersionError(
            f"KV wire payload has wire_version {ver!r} (this build accepts "
            f"{tuple(WIRE_ACCEPT_VERSIONS)}); refusing to decode cross-build "
            "pages"
        )
    try:
        dtype = _resolve_dtype(str(header["dtype"]))
        k_nbytes = int(header["k_nbytes"])
        v_nbytes = int(header["v_nbytes"])
        k_shape = [int(d) for d in header["k_shape"]]
        v_shape = [int(d) for d in header["v_shape"]]
        key = tuple(int(t) for t in header["key"])
        length = int(header["length"])
    except (KeyError, TypeError, ValueError) as e:
        raise WireDecodeError(f"malformed KV wire header: {e}") from None
    body = data[m + 4 + hlen :]
    if len(body) != k_nbytes + v_nbytes:
        raise WireDecodeError(
            f"KV wire payload body is {len(body)} bytes; header promised "
            f"{k_nbytes + v_nbytes}"
        )
    # v2+: the body must prove itself against the header checksum BEFORE any
    # bytes are reinterpreted as pages.  v1 carried none — accepted as-is.
    crc = header.get("crc32c")
    if ver >= 2:
        if not isinstance(crc, int):
            raise WireDecodeError("KV wire v2 payload is missing its crc32c")
        actual = crc32c(body)
        if actual != crc:
            raise WireIntegrityError(
                f"KV wire payload failed its CRC-32C (stored {crc:#010x}, "
                f"computed {actual:#010x}) — corrupt in flight; rejecting"
            )
    try:
        k = (
            np.frombuffer(body, np.uint8, count=k_nbytes)
            .view(dtype)
            .reshape(k_shape)
        )
        v = (
            np.frombuffer(body, np.uint8, count=v_nbytes, offset=k_nbytes)
            .view(dtype)
            .reshape(v_shape)
        )
    except ValueError as e:
        raise WireDecodeError(f"KV wire payload shape mismatch: {e}") from None
    if length != len(key) or length <= 0:
        raise WireDecodeError("KV wire payload key/length mismatch")
    return HostPrefixEntry(
        key=key,
        length=length,
        k=k,
        v=v,
        nbytes=k_nbytes + v_nbytes,
        pages=0,  # receiver recomputes against its OWN page size on put
        wire_version=int(ver),
        crc32c=crc if isinstance(crc, int) else None,
    )


# --------------------------------------------------------------- peer client
class PeerUnreachable(RuntimeError):
    """Connection-level failure: the peer process is dead, unreachable, or
    timed out before producing a status line — replica-death-shaped, so the
    router may re-route a token-less request.

    ``phase`` distinguishes WHERE the wire died, because the safe recovery
    differs: ``"connect"`` means the request never left this process (free to
    retry or re-route), ``"read"`` means it was already on the wire when the
    connection died — the peer may well have executed it, so the router
    retries the SAME peer under the request's idempotency key instead of
    re-routing into a double execution."""

    def __init__(self, detail: str, *, phase: str = "connect"):
        super().__init__(detail)
        self.phase = phase


class PeerHTTPError(RuntimeError):
    """The peer answered with a non-2xx status.  ``retry_after_s`` carries
    the peer's own ``Retry-After`` hint (429/503 — the PR 5 policy);
    ``reason`` the shed reason when the body had one."""

    def __init__(
        self,
        status: int,
        detail: str,
        *,
        retry_after_s: Optional[float] = None,
        reason: str = "",
    ):
        super().__init__(f"peer HTTP {status}: {detail}")
        self.status = int(status)
        self.detail = detail
        self.retry_after_s = retry_after_s
        self.reason = reason


def _chain_digest(digest: int, ev: dict) -> int:
    """Fold one gossip event into a rolling CRC32C chain.  Both sides (the
    plane's append path and the router's delta-apply path) fold the SAME
    canonical JSON encoding, so equal logs yield equal digests and a
    diverged ``/fleet/prefix`` log is detectable in one integer compare."""
    blob = json.dumps(ev, sort_keys=True, separators=(",", ":")).encode("utf-8")
    return crc32c(blob, digest)


def _flip_one_byte(data: bytes) -> bytes:
    """The ``net_corrupt`` payload mutation: one bit of the middle byte —
    exactly the failure a checksum exists to catch, deterministic so the
    chaos bench's injected-vs-rejected accounting is exact."""
    if not data:
        return data
    out = bytearray(data)
    out[len(out) // 2] ^= 0x01
    return bytes(out)


class PeerClient:
    """Tiny synchronous HTTP client for the fleet wire (stdlib only — the
    serving container ships no HTTP client library).  One request per call,
    no connection reuse: peers are long-lived but requests must never share
    failure state across threads.

    The single legacy ``timeout_s`` is split: ``connect_timeout_s`` bounds
    the TCP connect (a black-holed SYN fails in seconds, not the full
    request budget) while ``timeout_s`` — overridable per call — bounds the
    read, so a long KV transfer still completes.  Failures carry the phase
    (:class:`PeerUnreachable`); ``retries`` re-attempts CONNECT-phase
    failures only (nothing reached the peer) with exponential backoff
    through the injectable ``sleep``.

    Network chaos: when a :class:`~.faults.FaultInjector` is attached (or
    the env-gated global one exists), the ``net_*`` sites are consulted per
    request under ``fault_key`` — the caller's ``"self->peer"`` edge string
    — so each edge replays its own seeded schedule (see serving/faults.py)."""

    def __init__(
        self,
        base_url: str,
        *,
        timeout_s: float = 30.0,
        connect_timeout_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        injector: Optional[FaultInjector] = None,
        fault_key: str = "",
    ):
        self.base_url = base_url.rstrip("/")
        self.timeout_s = float(timeout_s)
        self.connect_timeout_s = (
            float(connect_timeout_s)
            if connect_timeout_s is not None
            else min(5.0, self.timeout_s)
        )
        self._clock = clock
        self._sleep = sleep
        self._injector = injector
        self.fault_key = fault_key

    def _inj(self) -> Optional[FaultInjector]:
        return self._injector if self._injector is not None else global_injector()

    def _request(
        self,
        method: str,
        path: str,
        *,
        body: Optional[bytes] = None,
        content_type: str = "application/json",
        timeout_s: Optional[float] = None,
        headers: Optional[Dict[str, str]] = None,
        retries: int = 0,
    ) -> Tuple[int, bytes]:
        attempt = 0
        while True:
            try:
                return self._request_once(
                    method,
                    path,
                    body=body,
                    content_type=content_type,
                    timeout_s=timeout_s,
                    headers=headers,
                )
            except PeerUnreachable as e:
                # only connect-phase failures are provably un-executed and
                # safe to blindly re-send; read-phase recovery belongs to the
                # router, which holds the idempotency key
                if attempt >= int(retries) or e.phase != "connect":
                    raise
                attempt += 1
                self._sleep(min(1.0, 0.05 * (2 ** (attempt - 1))))

    def _request_once(
        self,
        method: str,
        path: str,
        *,
        body: Optional[bytes],
        content_type: str,
        timeout_s: Optional[float],
        headers: Optional[Dict[str, str]],
    ) -> Tuple[int, bytes]:
        inj = self._inj()
        edge = self.fault_key
        if inj is not None:
            if inj.should_fire("net_partition", edge):
                raise PeerUnreachable(
                    f"{self.base_url}: injected net_partition (connection refused)",
                    phase="connect",
                )
            if inj.should_fire("net_blackhole", edge):
                raise PeerUnreachable(
                    f"{self.base_url}: injected net_blackhole (connect timed "
                    f"out after {self.connect_timeout_s}s)",
                    phase="connect",
                )
            d = inj.sleep_s("net_delay", edge)
            if d > 0:
                self._sleep(d)
            if (
                body is not None
                and content_type == "application/octet-stream"
                and inj.should_fire("net_corrupt", edge)
            ):
                body = _flip_one_byte(body)
        sp = urllib.parse.urlsplit(self.base_url + path)
        conn_cls = (
            http.client.HTTPSConnection
            if sp.scheme == "https"
            else http.client.HTTPConnection
        )
        conn = conn_cls(sp.netloc, timeout=self.connect_timeout_s)
        try:
            try:
                conn.connect()
            except (OSError, TimeoutError) as e:
                raise PeerUnreachable(
                    f"{self.base_url}: {e}", phase="connect"
                ) from None
            sock = getattr(conn, "sock", None)
            if sock is not None:
                read_timeout = (
                    float(timeout_s) if timeout_s is not None else self.timeout_s
                )
                sock.settimeout(max(0.001, read_timeout))
            target = (sp.path or "/") + (f"?{sp.query}" if sp.query else "")
            try:
                conn.request(
                    method,
                    target,
                    body=body,
                    headers={"Content-Type": content_type, **(headers or {})},
                )
                if inj is not None and inj.should_fire("net_drop", edge):
                    # the request is already on the wire: the peer may be
                    # executing it right now — read-phase failure semantics
                    raise PeerUnreachable(
                        f"{self.base_url}: injected net_drop (connection lost "
                        "awaiting response)",
                        phase="read",
                    )
                resp = conn.getresponse()
                data = resp.read()
                status = int(resp.status)
                resp_ct = resp.headers.get("Content-Type", "") or ""
                retry_hdr = resp.headers.get("Retry-After")
            except PeerUnreachable:
                raise
            except (http.client.HTTPException, OSError, TimeoutError) as e:
                # post-connect death: the request MAY have been received and
                # executed — the phase tells the router to dedup, not re-run
                raise PeerUnreachable(
                    f"{self.base_url}: {e!r}", phase="read"
                ) from None
        finally:
            conn.close()
        if (
            inj is not None
            and status < 400
            and resp_ct.startswith("application/octet-stream")
            and inj.should_fire("net_corrupt", edge)
        ):
            data = _flip_one_byte(data)
        if status >= 400:
            detail, reason, retry = f"HTTP {status}", "", None
            try:
                payload = json.loads(data.decode("utf-8"))
                detail = str(payload.get("detail", detail))
                reason = str(payload.get("reason", ""))
                if "retry_after_s" in payload:
                    retry = float(payload["retry_after_s"])
            except Exception:
                pass
            if retry is None and retry_hdr is not None:
                try:
                    retry = float(retry_hdr)
                except ValueError:
                    retry = None
            raise PeerHTTPError(status, detail, retry_after_s=retry, reason=reason)
        return status, data

    def get_json(
        self, path: str, *, timeout_s: Optional[float] = None, retries: int = 0
    ) -> dict:
        _, data = self._request("GET", path, timeout_s=timeout_s, retries=retries)
        return json.loads(data.decode("utf-8"))

    def post_json(
        self, path: str, body: dict, *, timeout_s: Optional[float] = None
    ) -> dict:
        _, data = self._request(
            "POST",
            path,
            body=json.dumps(body).encode("utf-8"),
            timeout_s=timeout_s,
        )
        return json.loads(data.decode("utf-8"))

    def post_for_bytes(
        self, path: str, body: dict, *, timeout_s: Optional[float] = None
    ) -> Optional[bytes]:
        """POST JSON, expect raw bytes back; None on 404 (an honest miss,
        not an error — the /fleet/kv/get contract)."""
        try:
            _, data = self._request(
                "POST",
                path,
                body=json.dumps(body).encode("utf-8"),
                timeout_s=timeout_s,
            )
        except PeerHTTPError as e:
            if e.status == 404:
                return None
            raise
        return data

    def post_bytes(
        self, path: str, data: bytes, *, timeout_s: Optional[float] = None
    ) -> dict:
        _, out = self._request(
            "POST",
            path,
            body=data,
            content_type="application/octet-stream",
            timeout_s=timeout_s,
        )
        return json.loads(out.decode("utf-8"))


# ---------------------------------------------------------------- fleet peer
class FleetPeer:
    """One remote ``serve`` process as the router sees it: address, circuit
    breaker, pool role, and the last health/load/gossip snapshot."""

    def __init__(
        self,
        name: str,
        base_url: str,
        *,
        pool: str = "unified",
        breaker: Optional[CircuitBreaker] = None,
        client: Optional[PeerClient] = None,
        timeout_s: float = 30.0,
    ):
        self.name = name
        self.base_url = base_url.rstrip("/")
        self.client = client or PeerClient(base_url, timeout_s=timeout_s)
        self.breaker = breaker or CircuitBreaker()
        self.pool = pool
        self.draining = False
        self.healthy = True  # optimistic until a refresh says otherwise
        self.queued = 0
        self.active = 0
        self.prefix_seq = 0  # gossip cursor into the peer's delta log
        self.prefix_digest = 0  # running CRC chain over the peer's gossip log
        self.dispatched = 0
        self.last_refresh_ok = False
        # partition-tolerance state (FleetRouter.refresh owns all of it):
        # when the peer was last CONFIRMED reachable, when the current
        # unreachable streak began, whether its gossip-learned holdings were
        # TTL-dropped, why the last refresh failed, and — on heal — when the
        # forced anti-entropy resync started (convergence gauge)
        self.last_confirmed: Optional[float] = None
        self.unreachable_since: Optional[float] = None
        self.ttl_dropped = False
        self.last_failure_reason = ""
        self.resync_started_at: Optional[float] = None

    def load(self) -> int:
        return self.queued + self.active


class _FleetRequest:
    """Mutable per-request dispatch state (one worker thread owns it)."""

    __slots__ = (
        "prompt_ids",
        "body",
        "prefix_len",
        "deadline_at",
        "trace_id",
        "hops",
        "affinity_hit",
        "forced",
        "timeout_retries_used",
    )

    def __init__(self, prompt_ids, body, prefix_len, deadline_at, trace_id):
        self.prompt_ids = prompt_ids
        self.body = body
        self.prefix_len = prefix_len
        self.deadline_at = deadline_at
        self.trace_id = trace_id
        self.hops = 0
        self.affinity_hit = False
        self.forced = False
        self.timeout_retries_used = 0


class FleetResult:
    """What a fleet dispatch resolves to — the token-level subset of
    :class:`~.engine.GenerationResult` plus fleet routing metadata.  Token
    ids are the bit-identity surface (text is the peer's detokenization)."""

    def __init__(
        self,
        *,
        token_ids: List[int],
        text: str,
        prompt_tokens: int,
        completion_tokens: int,
        length_limited: bool,
        peer: str,
        reroutes: int,
        trace_id: str,
        handoff: Optional[dict] = None,
        timings: Optional[dict] = None,
    ):
        self.token_ids = token_ids
        self.text = text
        self.prompt_tokens = prompt_tokens
        self.completion_tokens = completion_tokens
        self.length_limited = length_limited
        self.peer = peer
        self.reroutes = reroutes
        self.trace_id = trace_id
        self.handoff = handoff
        # the serving peer's usage.timings, unchanged: its spans are on ITS
        # clock (recv_mono_s is that process's), wire time is not in them
        self.timings = timings

    def usage_dict(self, model: str) -> dict:
        out = {
            "model": model,
            "prompt_tokens": self.prompt_tokens,
            "completion_tokens": self.completion_tokens,
            "total_tokens": self.prompt_tokens + self.completion_tokens,
            "peer": self.peer,
        }
        if self.timings is not None:
            out["timings"] = dict(self.timings)
        return out


# -------------------------------------------------------------- fleet router
class FleetRouter:
    """Dispatch dialog requests across ``serve`` PROCESSES with the
    in-process router's exact submit contract and precedence (health >
    affinity > least-loaded), per-peer circuit breakers, token-less re-route
    on peer death, and — when the fleet is disaggregated — the two-stage
    prefill-pool -> decode-pool handoff.

    ``peers`` is a sequence of ``(name, base_url)`` pairs or
    :class:`FleetPeer` objects.  ``refresh()`` polls every peer's
    ``/fleet/healthz`` and ``/fleet/prefix`` (gossip) — called lazily from
    dispatch when the last poll is older than ``refresh_interval_s``, or
    continuously via :meth:`start`.
    """

    def __init__(
        self,
        peers: Sequence[Any],
        *,
        model: str,
        name: str = "router",
        breaker_threshold: int = 3,
        breaker_reset_s: float = 10.0,
        max_reroutes: int = 2,
        request_timeout_s: float = 300.0,
        connect_timeout_s: float = 5.0,
        health_timeout_s: float = 5.0,
        refresh_interval_s: float = 2.0,
        registry_ttl_s: float = 30.0,
        timeout_retries: int = 1,
        handoff_suffix_tokens: int = 64,
        pull_min_tokens: int = 1,
        max_workers: int = 8,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        injector: Optional[FaultInjector] = None,
    ):
        from .router import FleetPrefixRegistry

        self.model = model
        self.name = str(name)
        self.max_reroutes = max(0, int(max_reroutes))
        self.request_timeout_s = float(request_timeout_s)
        self.connect_timeout_s = float(connect_timeout_s)
        self.health_timeout_s = float(health_timeout_s)
        self.refresh_interval_s = float(refresh_interval_s)
        # how long a peer may stay unreachable before the affinity claims we
        # learned from its gossip age out of the registry (partition
        # tolerance: a dead link must stop attracting traffic)
        self.registry_ttl_s = float(registry_ttl_s)
        # read-phase failures re-try the SAME peer this many times before the
        # peer counts as dead — paired with the idempotency key, the retry
        # returns the original result instead of double-executing
        self.timeout_retries = max(0, int(timeout_retries))
        self.handoff_suffix_tokens = int(handoff_suffix_tokens)
        self.pull_min_tokens = max(1, int(pull_min_tokens))
        self._clock = clock
        self._sleep = sleep
        self.peers: List[FleetPeer] = []
        for p in peers:
            if isinstance(p, FleetPeer):
                self.peers.append(p)
            else:
                peer_name, url = p
                self.peers.append(
                    FleetPeer(
                        peer_name,
                        url,
                        breaker=CircuitBreaker(
                            breaker_threshold, breaker_reset_s, clock=clock
                        ),
                        client=PeerClient(
                            url,
                            timeout_s=request_timeout_s,
                            connect_timeout_s=connect_timeout_s,
                            clock=clock,
                            sleep=sleep,
                            injector=injector,
                            fault_key=f"{self.name}->{peer_name}",
                        ),
                    )
                )
        if not self.peers:
            raise ValueError("FleetRouter needs at least one peer")
        self.prefix_registry = FleetPrefixRegistry()
        self.flight = FlightRecorder(name=f"fleet-{model}", clock=clock)
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, int(max_workers)),
            thread_name_prefix=f"fleet-{model}",
        )
        self._lock = threading.Lock()
        self._rr = 0
        self._last_refresh = float("-inf")
        self._peer_reps: Dict[str, set] = {}  # peer -> namespaced sub-replicas
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # counters (stats() / the dabt_fleet_* metric surface)
        self.reroutes = 0
        self.rerouted_failed = 0
        self.no_peer_available = 0
        self.sheds = 0
        self.affinity_hits = 0
        self.affinity_misses = 0
        self.prefix_pulls = 0
        self.pull_misses = 0
        self.pull_failures = 0
        self.pages_shipped = 0
        self.handoffs = 0
        self.handoff_fallbacks = 0
        self.pool_role_bypasses = 0
        self.refresh_failures = 0
        self.refresh_failure_reasons: Dict[str, int] = {}
        self.ttl_drops = 0
        self.gossip_digest_mismatches = 0
        self.reconciles = 0
        self.reconcile_last_s = 0.0  # heal -> snapshot-applied convergence
        self.timeout_retries_total = 0
        self.pull_integrity_rejects = 0
        self.pull_refetches = 0

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "FleetRouter":
        """Run :meth:`refresh` on a daemon thread every
        ``refresh_interval_s`` (tests and the bench drive refresh()
        directly instead)."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._refresh_loop, name=f"fleet-{self.model}-refresh",
            daemon=True,
        )
        self._thread.start()
        return self

    def _refresh_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.refresh()
            except Exception:
                logger.exception("fleet refresh failed")
            self._stop.wait(self.refresh_interval_s)

    def close(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=max(5.0, 2 * self.refresh_interval_s))
        self._thread = None
        self._pool.shutdown(wait=False, cancel_futures=True)

    # --------------------------------------------------------------- refresh
    def refresh(self) -> None:
        """One poll of every peer: health/load off ``/fleet/healthz``,
        prefix gossip off ``/fleet/prefix?since=<cursor>``.  An unreachable
        peer is marked unhealthy AND fed to its breaker, so dispatch skips
        it without paying a connect timeout per request; WHY it failed
        (timeout vs conn-refused vs 5xx) is classified into
        ``refresh_failure_reasons`` and the flight recorder.  A peer
        unreachable past ``registry_ttl_s`` has its gossip-learned holdings
        dropped (its affinity claims stop attracting traffic); on heal its
        log is reconciled via a forced reset-snapshot exchange and the
        convergence time lands in ``reconcile_last_s``."""
        for peer in list(self.peers):
            try:
                hz = peer.client.get_json(
                    "/fleet/healthz?peers=0", timeout_s=self.health_timeout_s
                )
            except (PeerUnreachable, PeerHTTPError, ValueError) as e:
                self._note_refresh_failure(peer, e)
                continue
            self._note_refresh_success(peer)
            status = hz.get("status", "ok")
            peer.healthy = status in ("ok", "degraded")
            peer.draining = status == "draining"
            peer.last_refresh_ok = True
            fleet = hz.get("fleet", {})
            if fleet.get("pool"):
                peer.pool = fleet["pool"]
            load = hz.get("load", {})
            peer.queued = int(load.get("queued", 0))
            peer.active = int(load.get("active", 0))
            try:
                self._poll_prefix(peer)
            except (PeerUnreachable, PeerHTTPError, ValueError, KeyError):
                logger.warning(
                    "fleet prefix poll failed for %s", peer.name, exc_info=True
                )
        with self._lock:
            self._last_refresh = self._clock()

    @staticmethod
    def _failure_reason(exc: BaseException) -> str:
        """Classify a refresh failure for the reason-labelled gauge: the
        operator triaging a partition needs 'timeout' vs 'conn_refused' vs
        'http_5xx' at a glance, not a generic failure count."""
        if isinstance(exc, PeerHTTPError):
            return f"http_{exc.status // 100}xx"
        if isinstance(exc, PeerUnreachable):
            if getattr(exc, "phase", "connect") == "read":
                return "timeout"
            text = str(exc).lower()
            if "refused" in text:
                return "conn_refused"
            if "timed out" in text or "timeout" in text:
                return "timeout"
            return "unreachable"
        return "bad_payload"

    def _note_refresh_failure(self, peer: FleetPeer, exc: BaseException) -> None:
        reason = self._failure_reason(exc)
        was_healthy = peer.healthy
        if peer.healthy or not peer.last_refresh_ok:
            peer.breaker.record_failure()
        peer.healthy = False
        peer.last_refresh_ok = False
        peer.last_failure_reason = reason
        now = self._clock()
        if peer.unreachable_since is None:
            peer.unreachable_since = now
        with self._lock:
            self.refresh_failures += 1
            self.refresh_failure_reasons[reason] = (
                self.refresh_failure_reasons.get(reason, 0) + 1
            )
        if was_healthy:
            self.flight.record(
                "peer_unhealthy", peer=peer.name, reason=reason,
                detail=str(exc)[:200],
            )
        if (
            not peer.ttl_dropped
            and now - peer.unreachable_since >= self.registry_ttl_s
        ):
            dropped = self._drop_peer_holdings(peer)
            peer.ttl_dropped = True
            with self._lock:
                self.ttl_drops += 1
            self.flight.record(
                "registry_ttl_drop",
                peer=peer.name,
                reason=reason,
                entries=dropped,
                unreachable_s=round(now - peer.unreachable_since, 3),
            )

    def _note_refresh_success(self, peer: FleetPeer) -> None:
        now = self._clock()
        if peer.unreachable_since is not None and peer.ttl_dropped:
            # heal after a TTL drop: our view of the peer's log is stale by
            # construction — force the anti-entropy reset-snapshot exchange
            # and time the convergence (resync_started_at -> snapshot applied)
            peer.resync_started_at = now
            peer.prefix_seq = -1  # always predates the log window -> reset
        peer.unreachable_since = None
        peer.ttl_dropped = False
        peer.last_failure_reason = ""
        peer.last_confirmed = now

    def _drop_peer_holdings(self, peer: FleetPeer) -> int:
        """Drop every registry holding learned from this peer's gossip
        (namespaced sub-replicas aggregate to the process)."""
        with self._lock:
            names = set(self._peer_reps.get(peer.name, ()))
        dropped = 0
        for nm in names:
            dropped += int(self.prefix_registry.drop_replica(nm) or 0)
        return dropped

    def _note_rep(self, peer_name: str, namespaced: str) -> None:
        with self._lock:
            self._peer_reps.setdefault(peer_name, set()).add(namespaced)

    def _poll_prefix(self, peer: FleetPeer, *, depth: int = 0) -> None:
        pj = peer.client.get_json(
            f"/fleet/prefix?since={peer.prefix_seq}",
            timeout_s=self.health_timeout_s,
        )
        server_digest = pj.get("digest")
        if pj.get("reset"):
            # the peer's delta log was trimmed (or restarted) past our
            # cursor: drop its holdings and re-apply the snapshot
            self._drop_peer_holdings(peer)
            for h in pj.get("holdings", []):
                if h.get("model") != self.model:
                    continue
                nm = f"{peer.name}/{h['replica']}"
                self._note_rep(peer.name, nm)
                self.prefix_registry.apply_holding(
                    nm, tuple(h["key"]), int(h["length"]), h.get("tier", TIER_HOST)
                )
            # a snapshot is authoritative: adopt the server's digest as the
            # new chain base for subsequent deltas
            if server_digest is not None:
                peer.prefix_digest = int(server_digest)
            if peer.resync_started_at is not None:
                elapsed = self._clock() - peer.resync_started_at
                peer.resync_started_at = None
                with self._lock:
                    self.reconciles += 1
                    self.reconcile_last_s = float(elapsed)
                self.flight.record(
                    "gossip_reconciled",
                    peer=peer.name,
                    reconcile_s=round(elapsed, 4),
                )
        else:
            # chain the digest over EVERY event in the delta (the server
            # digest covers its whole log, not one model's slice)
            d = peer.prefix_digest
            for ev in pj.get("events", []):
                d = _chain_digest(d, ev)
                if ev.get("model") != self.model:
                    continue
                nm = f"{peer.name}/{ev['replica']}"
                self._note_rep(peer.name, nm)
                self.prefix_registry.on_event(
                    nm, ev["event"], tuple(ev["key"]), int(ev["length"])
                )
            peer.prefix_digest = d
            if (
                server_digest is not None
                and int(server_digest) != d
                and depth == 0
            ):
                # diverged logs (missed delta, disagreeing builds): never
                # skew affinity silently — force the reset-snapshot path now
                with self._lock:
                    self.gossip_digest_mismatches += 1
                self.flight.record(
                    "gossip_digest_mismatch",
                    peer=peer.name,
                    ours=d,
                    theirs=int(server_digest),
                )
                if peer.resync_started_at is None:
                    peer.resync_started_at = self._clock()
                peer.prefix_seq = -1
                return self._poll_prefix(peer, depth=depth + 1)
        peer.prefix_seq = int(pj.get("seq", peer.prefix_seq))

    def _maybe_refresh(self) -> None:
        with self._lock:
            stale = self._clock() - self._last_refresh >= self.refresh_interval_s
        if stale:
            self.refresh()

    # -------------------------------------------------------------- dispatch
    def submit(
        self,
        prompt_ids: Sequence[int],
        *,
        max_tokens: int = 1024,
        temperature: float = 0.8,
        top_p: float = 0.95,
        json_format: bool = False,
        prefix_len: int = 0,
        priority: str = "interactive",
        tenant: str = "default",
        deadline_s: Optional[float] = None,
        stream: Any = None,
        trace_id: Optional[str] = None,
        attempt: int = 0,
        received_at: Optional[float] = None,
    ) -> Future:
        """The :meth:`EngineRouter.submit` contract over the wire.  Returns
        a ``Future[FleetResult]``; raises synchronously only for contract
        violations (streams do not cross the wire — attach them at a peer).

        ``attempt`` is the CALLER's retry ordinal: it feeds the idempotency
        key (``trace_id:attempt``), so a caller-level retry that WANTS a
        fresh execution bumps it, while the router's own internal
        timeout-retries reuse the same key and dedup server-side.

        ``received_at`` is accepted for the contract's sake and not sent: it
        is a stamp on this process's clock, and the serving peer stamps its
        own receipt (``usage.timings`` comes back as the peer made it)."""
        if stream is not None:
            raise ValueError(
                "FleetRouter does not stream across processes; send streaming "
                "requests to a serving peer's /dialog/ directly"
            )
        trace_id = trace_id or new_trace_id()
        prompt_ids = [int(t) for t in prompt_ids]
        prefix_len = max(0, min(int(prefix_len), max(0, len(prompt_ids) - 1)))
        body = {
            "model": self.model,
            "prompt_ids": prompt_ids,
            "max_tokens": int(max_tokens),
            "temperature": float(temperature),
            "top_p": float(top_p),
            "json_format": bool(json_format),
            "prefix_len": prefix_len,
            "priority": priority,
            "tenant": tenant,
            "trace_id": trace_id,
            "idem_key": f"{trace_id}:{int(attempt)}",
        }
        deadline_at = (
            self._clock() + float(deadline_s) if deadline_s is not None else None
        )
        if deadline_s is not None:
            body["deadline_s"] = float(deadline_s)
        st = _FleetRequest(prompt_ids, body, prefix_len, deadline_at, trace_id)
        fut: Future = Future()
        self._pool.submit(self._run, st, fut)
        return fut

    def _run(self, st: _FleetRequest, fut: Future) -> None:
        try:
            self._maybe_refresh()
            if self._disaggregated(st):
                result = self._run_disagg(st)
            else:
                peer, resp = self._dispatch_loop(st, st.body, roles=None)
                result = self._result_from(resp, peer, st)
        except BaseException as e:  # noqa: BLE001 — the future carries it
            if not fut.set_running_or_notify_cancel():
                return
            fut.set_exception(e)
        else:
            if not fut.set_running_or_notify_cancel():
                return
            fut.set_result(result)

    def _disaggregated(self, st: _FleetRequest) -> bool:
        """Handoff when both pools exist AND the un-cached suffix is long
        enough that a decode peer would (rightly) refuse to prefill it."""
        have_prefill = any(
            p.pool == "prefill" and not p.draining for p in self.peers
        )
        have_decode = any(
            p.pool == "decode" and not p.draining for p in self.peers
        )
        if not (have_prefill and have_decode):
            return False
        return len(st.prompt_ids) - st.prefix_len >= self.handoff_suffix_tokens

    def _remaining(self, st: _FleetRequest) -> Optional[float]:
        if st.deadline_at is None:
            return None
        return st.deadline_at - self._clock()

    def _peer_holders(self, prompt_ids, prefix_len) -> Dict[str, int]:
        """peer name -> best tier rank over the gossip-fed registry (the
        namespaced sub-replica holdings aggregate up to their process)."""
        out: Dict[str, int] = {}
        for rep, tier in self.prefix_registry.holders(
            prompt_ids, prefix_len
        ).items():
            peer = rep.split("/", 1)[0]
            r = _TIER_RANK.get(tier, 9)
            if r < out.get(peer, 9):
                out[peer] = r
        return out

    def _candidate_order(
        self,
        st: _FleetRequest,
        excluded: set,
        roles: Optional[Tuple[str, ...]],
        prefer: Optional[str] = None,
    ) -> Tuple[List[FleetPeer], Dict[str, int]]:
        with self._lock:
            self._rr += 1
            rr = self._rr
            peers = list(self.peers)
        n = max(1, len(peers))
        pos = {p.name: i for i, p in enumerate(peers)}
        cands = [
            p
            for p in peers
            if p.name not in excluded
            and not p.draining
            and (roles is None or p.pool in roles)
        ]
        holders = self._peer_holders(st.prompt_ids, st.prefix_len)
        cands.sort(
            key=lambda p: (
                p.name != prefer,
                not p.healthy,
                p.name not in holders,
                holders.get(p.name, 9),
                p.load(),
                (pos[p.name] - rr) % n,
            )
        )
        return cands, holders

    def _dispatch_loop(
        self,
        st: _FleetRequest,
        body: dict,
        roles: Optional[Tuple[str, ...]],
        prefer: Optional[str] = None,
    ) -> Tuple[FleetPeer, dict]:
        """The re-route loop: walk candidates in precedence order, POST
        ``/fleet/generate``, re-route token-less failures up to
        ``max_reroutes`` extra hops.  Sheds (429) exclude the peer and move
        on; when EVERY reject was ``pool_role`` the loop retries once with
        ``force`` — availability beats role purity when a pool is gone."""
        excluded: set = set()
        sheds: List[float] = []
        shed_reasons: List[str] = []
        breaker_waits: List[float] = []
        while True:
            rem = self._remaining(st)
            if rem is not None and rem <= 0:
                raise DeadlineExceeded(
                    f"fleet deadline expired after {st.hops} hops"
                )
            cands, holders = self._candidate_order(st, excluded, roles, prefer)
            peer = None
            for cand in cands:
                if not cand.breaker.allow():
                    breaker_waits.append(cand.breaker.retry_in_s())
                    continue
                peer = cand
                break
            if peer is None:
                if (
                    sheds
                    and shed_reasons
                    and all(r == "pool_role" for r in shed_reasons)
                    and not body.get("force")
                ):
                    # the only objection was pool role — bypass it rather
                    # than fail a servable request (counted, flight-recorded)
                    body = {**body, "force": True}
                    st.forced = True
                    with self._lock:
                        self.pool_role_bypasses += 1
                    self.flight.record(
                        "pool_role_bypass", trace_id=st.trace_id, roles=roles
                    )
                    excluded.clear()
                    sheds.clear()
                    shed_reasons.clear()
                    continue
                with self._lock:
                    self.no_peer_available += 1
                if sheds:
                    with self._lock:
                        self.sheds += 1
                    raise SchedulerRejected("fleet_shed", min(sheds))
                retry = min(breaker_waits) if breaker_waits else 1.0
                raise EngineUnavailable(
                    "no fleet peer available", retry_after_s=max(0.1, retry)
                )
            if peer.name in holders:
                st.affinity_hit = True
                with self._lock:
                    self.affinity_hits += 1
            else:
                with self._lock:
                    self.affinity_misses += 1
                if (
                    holders
                    and st.prefix_len >= self.pull_min_tokens
                    and not body.get("prefill_only")
                ):
                    self._maybe_pull(peer, holders, st)
            timeout = self.request_timeout_s if rem is None else min(
                self.request_timeout_s, rem + 5.0
            )
            if rem is not None:
                body = {**body, "deadline_s": max(0.001, rem)}
            try:
                resp = peer.client.post_json(
                    "/fleet/generate", body, timeout_s=timeout
                )
            except PeerHTTPError as e:
                if e.status == 429:
                    # a shed is back-pressure, not death: never a breaker
                    # failure (half-open probes release instead)
                    peer.breaker.release_probe()
                    excluded.add(peer.name)
                    sheds.append(e.retry_after_s or 1.0)
                    shed_reasons.append(e.reason or "shed")
                    continue
                if e.status == 504:
                    raise DeadlineExceeded(e.detail) from None
                if e.status in (400, 404, 422):
                    raise ValueError(e.detail) from None
                # 5xx: replica-shaped failure — token-less by construction
                # (no token crossed the wire), so re-route
                self._note_peer_failure(peer, excluded, st, str(e))
                continue
            except PeerUnreachable as e:
                if (
                    getattr(e, "phase", "connect") == "read"
                    and st.timeout_retries_used < self.timeout_retries
                ):
                    # the request was already on the wire — the peer may have
                    # executed it.  Retry the SAME peer under the request's
                    # idempotency key (a dup returns the original result);
                    # re-routing here is what double-executes.
                    st.timeout_retries_used += 1
                    with self._lock:
                        self.timeout_retries_total += 1
                    self.flight.record(
                        "timeout_retry",
                        trace_id=st.trace_id,
                        peer=peer.name,
                        attempt=st.timeout_retries_used,
                        detail=str(e)[:200],
                    )
                    prefer = peer.name
                    continue
                self._note_peer_failure(peer, excluded, st, str(e))
                continue
            peer.breaker.record_success()
            peer.healthy = True
            with self._lock:
                peer.dispatched += 1
            return peer, resp

    def _note_peer_failure(
        self, peer: FleetPeer, excluded: set, st: _FleetRequest, detail: str
    ) -> None:
        """Breaker + re-route bookkeeping for a replica-shaped peer failure;
        raises when the hop budget is spent."""
        peer.breaker.record_failure()
        peer.healthy = False
        excluded.add(peer.name)
        if st.hops < self.max_reroutes:
            st.hops += 1
            with self._lock:
                self.reroutes += 1
            self.flight.record(
                "reroute",
                trace_id=st.trace_id,
                from_peer=peer.name,
                hops=st.hops,
                detail=detail[:200],
            )
            return
        with self._lock:
            self.rerouted_failed += 1
        raise EngineUnavailable(
            f"fleet request failed after {st.hops} re-routes: {detail}",
            retry_after_s=1.0,
        )

    def _maybe_pull(
        self, peer: FleetPeer, holders: Dict[str, int], st: _FleetRequest
    ) -> None:
        """Cross-process prefix pull: fetch the holder's longest matching
        entry over ``/fleet/kv/get`` and plant it in the target peer's host
        tier ahead of the dispatch — the restore path on the target is
        unchanged.  Best-effort: any failure costs one re-prefill, never
        the request."""
        src = None
        for name in sorted(holders, key=holders.get):
            if name == peer.name:
                continue
            cand = next((p for p in self.peers if p.name == name), None)
            if cand is not None and cand.healthy:
                src = cand
                break
        if src is None:
            return
        out = None
        for fetch in range(2):  # original pull + ONE integrity re-fetch
            try:
                data = src.client.post_for_bytes(
                    "/fleet/kv/get",
                    {
                        "model": self.model,
                        "prompt_ids": st.prompt_ids,
                        "prefix_len": st.prefix_len,
                    },
                    timeout_s=self.health_timeout_s * 4,
                )
                if data is None:
                    with self._lock:
                        self.pull_misses += 1
                    return
                out = peer.client.post_bytes(
                    f"/fleet/kv/put?model={urllib.parse.quote(self.model)}",
                    data,
                    timeout_s=self.health_timeout_s * 4,
                )
                break
            except PeerHTTPError as e:
                if e.reason == "wire_integrity":
                    # the payload rotted on THIS transfer — the holder still
                    # has the intact entry, so one clean re-fetch is cheap;
                    # a second corruption means cold prefill (never garbage)
                    with self._lock:
                        self.pull_integrity_rejects += 1
                    if fetch == 0:
                        with self._lock:
                            self.pull_refetches += 1
                        self.flight.record(
                            "pull_integrity_refetch",
                            trace_id=st.trace_id,
                            from_peer=src.name,
                            to_peer=peer.name,
                        )
                        continue
                with self._lock:
                    self.pull_failures += 1
                logger.warning("fleet prefix pull failed: %s", e)
                return
            except (PeerUnreachable, ValueError) as e:
                with self._lock:
                    self.pull_failures += 1
                logger.warning("fleet prefix pull failed: %s", e)
                return
        if out is None:
            return
        if out.get("stored"):
            with self._lock:
                self.prefix_pulls += 1
                self.pages_shipped += int(out.get("pages", 0))
            self.flight.record(
                "prefix_pull",
                trace_id=st.trace_id,
                from_peer=src.name,
                to_peer=peer.name,
                pages=int(out.get("pages", 0)),
            )
        else:
            with self._lock:
                self.pull_failures += 1

    # ------------------------------------------------- disaggregated handoff
    def _run_disagg(self, st: _FleetRequest) -> FleetResult:
        """Two-stage dispatch: (1) chunked prefill on the prefill pool as a
        background-class ``prefill_only`` request that pushes the finished
        prefix pages to the chosen decode peer; (2) the real request on the
        decode pool with ``prefix_len`` covering the pushed prefix, admitted
        via restore.  Greedy outputs are identical to the unified arm —
        restore bit-identity is the tested invariant underneath."""
        plen = max(st.prefix_len, len(st.prompt_ids) - 1)
        decode_cands, _ = self._candidate_order(
            st, set(), roles=("decode",)
        )
        target = next(
            (p for p in decode_cands if p.breaker.allow()), None
        )
        handoff = None
        if target is not None:
            pre_body = {
                **st.body,
                "max_tokens": 1,
                "temperature": 0.0,
                "json_format": False,
                "priority": "background",
                "prefill_only": True,
                "prefix_len": plen,
                "push_to": target.base_url,
            }
            try:
                _peer, pre = self._dispatch_loop(
                    st, pre_body, roles=("prefill",)
                )
                handoff = pre.get("handoff")
            except (EngineUnavailable, SchedulerRejected) as e:
                # the prefill pool is gone or saturated: fall back to a
                # unified dispatch (force past pool-role guards) — counted,
                # so the bench can see availability winning over purity
                with self._lock:
                    self.handoff_fallbacks += 1
                self.flight.record(
                    "handoff_fallback", trace_id=st.trace_id, detail=str(e)[:200]
                )
        if handoff is not None and handoff.get("pushed"):
            with self._lock:
                self.handoffs += 1
                self.pages_shipped += int(handoff.get("pages", 0))
            dec_body = {**st.body, "prefix_len": plen}
            peer, resp = self._dispatch_loop(
                st, dec_body, roles=("decode",), prefer=target.name
            )
            result = self._result_from(resp, peer, st)
            result.handoff = handoff
            return result
        # no usable handoff: serve anywhere (decode peers may pool_role-shed;
        # the loop's force retry keeps the request servable)
        peer, resp = self._dispatch_loop(st, st.body, roles=None)
        return self._result_from(resp, peer, st)

    def _result_from(
        self, resp: dict, peer: FleetPeer, st: _FleetRequest
    ) -> FleetResult:
        usage = resp.get("usage", {})
        return FleetResult(
            token_ids=[int(t) for t in resp.get("token_ids", [])],
            text=resp.get("result", ""),
            prompt_tokens=int(usage.get("prompt_tokens", 0)),
            completion_tokens=int(usage.get("completion_tokens", 0)),
            length_limited=bool(resp.get("length_limited", False)),
            peer=peer.name,
            reroutes=st.hops,
            trace_id=st.trace_id,
            handoff=resp.get("handoff"),
            timings=usage.get("timings"),
        )

    # ----------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._lock:
            peers = [
                {
                    "name": p.name,
                    "pool": p.pool,
                    "healthy": p.healthy,
                    "draining": p.draining,
                    "breaker": p.breaker.state,
                    "queued": p.queued,
                    "active": p.active,
                    "dispatched": p.dispatched,
                    "last_failure_reason": p.last_failure_reason,
                    "ttl_dropped": p.ttl_dropped,
                }
                for p in self.peers
            ]
            out = {
                "model": self.model,
                "peers_total": len(self.peers),
                "peers_healthy": sum(1 for p in self.peers if p.healthy),
                "peers": peers,
                "reroutes": self.reroutes,
                "rerouted_failed": self.rerouted_failed,
                "no_peer_available": self.no_peer_available,
                "sheds": self.sheds,
                "affinity_hits": self.affinity_hits,
                "affinity_misses": self.affinity_misses,
                "prefix_pulls": self.prefix_pulls,
                "pull_misses": self.pull_misses,
                "pull_failures": self.pull_failures,
                "pages_shipped": self.pages_shipped,
                "handoffs": self.handoffs,
                "handoff_fallbacks": self.handoff_fallbacks,
                "pool_role_bypasses": self.pool_role_bypasses,
                "refresh_failures": self.refresh_failures,
                "refresh_failure_reasons": dict(self.refresh_failure_reasons),
                "ttl_drops": self.ttl_drops,
                "gossip_digest_mismatches": self.gossip_digest_mismatches,
                "reconciles": self.reconciles,
                "reconcile_last_s": self.reconcile_last_s,
                "timeout_retries": self.timeout_retries_total,
                "pull_integrity_rejects": self.pull_integrity_rejects,
                "pull_refetches": self.pull_refetches,
            }
        out["prefix_registry"] = self.prefix_registry.stats()
        return out


# --------------------------------------------------------------- fleet plane
class FleetPlane:
    """The SERVER side of the fleet wire, one per ``serve`` process: the
    gossip delta log of local KV tier events, the KV import/export surface
    (``/fleet/kv/put|get``), the pool-role admission guard, and the
    ``/fleet/healthz`` summary.  Wired onto the registry's generators at
    construction (router event taps / engine prefix listeners); attach as
    ``registry.fleet_plane`` before ``create_app`` — the server creates a
    default unified plane when none is attached."""

    def __init__(
        self,
        registry: Any,
        *,
        name: Optional[str] = None,
        pool: Optional[str] = None,
        peers: Sequence[Tuple[str, str]] = (),
        decode_max_prefill_tokens: int = 64,
        log_size: int = 4096,
        idem_ledger_size: int = 512,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.registry = registry
        self.name = name or f"proc-{os.getpid()}"
        self.pool = pool or self._pool_from_specs(registry)
        self.peers = [(str(n), str(u)) for n, u in peers]
        self.decode_max_prefill_tokens = int(decode_max_prefill_tokens)
        self._clock = clock
        self._lock = threading.Lock()
        self._log: deque = deque(maxlen=max(16, int(log_size)))
        self._seq = 0  # seq of the NEWEST event in the log
        self._digest = 0  # rolling CRC32C chain over the WHOLE event log
        self.events_total = 0
        self.kv_puts = 0
        self.kv_gets = 0
        self.kv_put_rejects = 0
        self.kv_integrity_rejects = 0
        self.pages_in = 0
        self.pages_out = 0
        self.pushes = 0
        self.push_failures = 0
        self.pool_rejects = 0
        self.pool_bypasses = 0
        # idempotency ledger: idem_key -> (Future, done_flag).  Bounded and
        # insertion-ordered; completed entries evict first so an in-flight
        # execution is never forgotten while a dup could still arrive.
        self._idem: "OrderedDict[str, list]" = OrderedDict()
        self._idem_cap = max(8, int(idem_ledger_size))
        self.idem_executions = 0
        self.idem_hits = 0
        self.idem_coalesced = 0
        self.idem_evictions = 0
        self._wire(registry)

    @staticmethod
    def _pool_from_specs(registry: Any) -> str:
        for spec in getattr(registry, "specs", {}).values():
            pool = getattr(spec, "pool", "unified")
            if getattr(spec, "kind", "") == "decoder" and pool != "unified":
                return pool
        return "unified"

    def _wire(self, registry: Any) -> None:
        """Chain onto every generator's tier-event plumbing: routers get an
        event tap (their replicas' listeners stay registry-owned), bare
        engines get the prefix listener directly.  Defensive throughout —
        an odd test registry must never break plane construction."""
        for model, eng in getattr(registry, "generators", {}).items():
            try:
                tap = getattr(eng, "set_event_tap", None)
                if callable(tap):
                    tap(
                        lambda replica, event, key, length, _m=model: (
                            self.on_tier_event(_m, replica, event, key, length)
                        )
                    )
                    continue
                setter = getattr(eng, "set_prefix_listener", None)
                if callable(setter):
                    rep_name = getattr(eng, "name", model)
                    setter(
                        lambda event, key, length, pages, _m=model, _n=rep_name: (
                            self.on_tier_event(_m, _n, event, key, length)
                        )
                    )
            except Exception:
                logger.exception("fleet plane wiring failed for %s", model)

    # ---------------------------------------------------------------- gossip
    def on_tier_event(
        self, model: str, replica: str, event: str, key: tuple, length: int
    ) -> None:
        ev = {
            "model": model,
            "replica": replica,
            "event": event,
            "key": [int(t) for t in key],
            "length": int(length),
        }
        with self._lock:
            self._seq += 1
            self.events_total += 1
            self._log.append((self._seq, ev))
            self._digest = _chain_digest(self._digest, ev)

    def prefix_events(self, since: int) -> dict:
        """Delta log entries past ``since``; when the cursor predates the
        log window (trim or process restart), a ``reset`` with the full
        warm-holdings snapshot instead — followers drop-and-reapply.  Both
        shapes carry the log's rolling ``digest`` so a follower whose own
        chain diverges (missed delta, disagreeing builds) can detect it and
        force this reset path instead of silently skewing affinity."""
        with self._lock:
            seq = self._seq
            digest = self._digest
            oldest = self._log[0][0] if self._log else self._seq + 1
            if since >= oldest - 1:
                events = [ev for s, ev in self._log if s > since]
                return {"seq": seq, "digest": digest, "events": events}
        return {
            "seq": seq,
            "digest": digest,
            "reset": True,
            "holdings": self._holdings(),
        }

    # ----------------------------------------------------- idempotent dispatch
    def idem_claim(self, key: str) -> Tuple[str, Future]:
        """Claim an idempotency key.  ``("mine", fut)`` means the caller owns
        the execution and must later :meth:`idem_complete` (success) or
        :meth:`idem_release` (failure) the SAME future; ``("wait", fut)``
        means another execution owns it — await the future, a non-``None``
        result is the original response to return verbatim."""
        with self._lock:
            rec = self._idem.get(key)
            if rec is not None:
                if rec[1]:
                    self.idem_hits += 1
                else:
                    self.idem_coalesced += 1
                return ("wait", rec[0])
            fut: Future = Future()
            self._idem[key] = [fut, False]
            self.idem_executions += 1
            while len(self._idem) > self._idem_cap:
                victim = next(
                    (k for k, r in self._idem.items() if r[1]), None
                ) or next(iter(self._idem))
                del self._idem[victim]
                self.idem_evictions += 1
            return ("mine", fut)

    def idem_complete(self, key: str, fut: Future, payload: dict) -> None:
        """Record a successful execution: dups arriving later (or already
        awaiting) get ``payload`` back instead of a re-execution."""
        with self._lock:
            rec = self._idem.get(key)
            if rec is not None and rec[0] is fut:
                rec[1] = True
        # resolve OUTSIDE the lock — waiter callbacks run inline (DABT102)
        if not fut.done():
            fut.set_result(payload)

    def idem_release(self, key: str, fut: Future) -> None:
        """Failed execution: drop the ledger entry so a retry re-executes,
        and resolve waiters with ``None`` (their cue to claim afresh)."""
        with self._lock:
            rec = self._idem.get(key)
            if rec is not None and rec[0] is fut:
                del self._idem[key]
        if not fut.done():
            fut.set_result(None)

    def _holdings(self) -> List[dict]:
        """Warm holdings across every generator's HOST tier (host DRAM +
        disk — the durable tiers; write-through keeps registered HBM
        prefixes mirrored there, so for routing purposes this IS the warm
        set).  ``length == len(key)`` by construction of prefix keys."""
        out: List[dict] = []
        for model, eng in getattr(self.registry, "generators", {}).items():
            reps = getattr(eng, "replicas", None)
            pairs = (
                [(rep.name, rep.engine) for rep in reps]
                if reps is not None
                else [(getattr(eng, "name", model), eng)]
            )
            for rep_name, e in pairs:
                tier = getattr(e, "kv_host_tier", None)
                if tier is None:
                    continue
                try:
                    for key, _pages in tier.warm_keys():
                        out.append(
                            {
                                "model": model,
                                "replica": rep_name,
                                "key": [int(t) for t in key],
                                "length": len(key),
                                "tier": TIER_HOST,
                            }
                        )
                except Exception:
                    logger.exception("fleet holdings snapshot failed")
        return out

    # ------------------------------------------------------------ KV surface
    def _model_engines(self, model: str) -> List[Any]:
        eng = self.registry.get_generator(model)
        if eng is None:
            raise KeyError(model)
        reps = getattr(eng, "replicas", None)
        if reps is not None:
            return [rep.engine for rep in reps]
        return [eng]

    def kv_get_wire(
        self, model: str, prompt_ids: Sequence[int], prefix_len: int
    ) -> Optional[bytes]:
        """Longest matching warm prefix across this process's replicas,
        wire-encoded; None on a miss.  Read-only on every tier."""
        best: Optional[HostPrefixEntry] = None
        for eng in self._model_engines(model):
            tier = getattr(eng, "kv_host_tier", None)
            if tier is None:
                continue
            ent = tier.export_match(prompt_ids, prefix_len)
            if ent is not None and (best is None or ent.length > best.length):
                best = ent
        if best is None:
            return None
        with self._lock:
            self.kv_gets += 1
            self.pages_out += int(best.pages)
        return encode_kv_entry(best)

    def kv_put_wire(self, model: str, data: bytes) -> dict:
        """Decode + absorb one wire entry into the least-loaded replica's
        host tier (geometry/dtype validated by the engine).  Raises
        :class:`WireVersionError` for cross-build payloads,
        :class:`WireIntegrityError` for checksum-failed ones (counted —
        the chaos bench's rejected-corruption criterion reads it here),
        ``ValueError`` for malformed ones, ``KeyError`` for an unknown
        model."""
        try:
            entry = decode_kv_entry(data)
        except WireIntegrityError:
            with self._lock:
                self.kv_integrity_rejects += 1
            raise
        engines = self._model_engines(model)
        engines.sort(key=lambda e: e.queued_depth() + e.num_active)
        stored = False
        pages = 0
        for eng in engines:
            absorb = getattr(eng, "absorb_remote_entry", None)
            if not callable(absorb):
                continue
            if absorb(entry.key, entry.length, entry.k, entry.v):
                stored = True
                tier = eng.kv_host_tier
                page = getattr(tier, "page_size", 1)
                pages = -(-entry.length // max(1, page))
                break
        with self._lock:
            if stored:
                self.kv_puts += 1
                self.pages_in += pages
            else:
                self.kv_put_rejects += 1
        return {"stored": stored, "pages": pages, "key_tokens": len(entry.key)}

    def handoff_export(
        self,
        model: str,
        prompt_ids: Sequence[int],
        prefix_len: int,
        push_to: Optional[str],
    ) -> dict:
        """The prefill-pool epilogue: export the just-registered prefix
        entry (write-through already mirrored it to the host tier; a cheap
        spill sweep covers the writethrough-off case) and push it to the
        decode peer named by ``push_to``.  Best-effort — a failed push
        degrades to the decode peer pulling or re-prefilling."""
        plen = max(0, min(int(prefix_len), len(prompt_ids) - 1))
        key = tuple(int(t) for t in prompt_ids[:plen])
        entry = None
        engines = self._model_engines(model)
        for attempt in range(2):
            for eng in engines:
                tier = getattr(eng, "kv_host_tier", None)
                if tier is None:
                    continue
                entry = tier.export_entry(key)
                if entry is not None:
                    break
            if entry is not None or attempt == 1:
                break
            for eng in engines:
                spill = getattr(eng, "spill_registered_to_host", None)
                if callable(spill):
                    try:
                        spill()
                    except Exception:
                        logger.exception("handoff spill sweep failed")
        if entry is None:
            return {
                "key_tokens": plen,
                "length": plen,
                "pages": 0,
                "pushed": False,
                "reason": "no_entry",
            }
        out = {
            "key_tokens": len(entry.key),
            "length": int(entry.length),
            "pages": int(entry.pages),
            "pushed": False,
        }
        if push_to:
            scheme = urllib.parse.urlsplit(push_to).scheme
            if scheme not in ("http", "https"):
                out["reason"] = "bad_push_to"
                return out
            try:
                resp = PeerClient(push_to, timeout_s=20.0).post_bytes(
                    f"/fleet/kv/put?model={urllib.parse.quote(model)}",
                    encode_kv_entry(entry),
                )
            except (PeerUnreachable, PeerHTTPError, ValueError) as e:
                with self._lock:
                    self.push_failures += 1
                out["reason"] = f"push_failed: {e}"[:200]
                return out
            out["pushed"] = bool(resp.get("stored"))
            with self._lock:
                if out["pushed"]:
                    self.pushes += 1
                    self.pages_out += int(entry.pages)
                else:
                    self.push_failures += 1
        return out

    # -------------------------------------------------------- admission guard
    def admission_guard(
        self,
        model: str,
        eng: Any,
        prompt_ids: Sequence[int],
        prefix_len: int,
        *,
        prefill_only: bool,
        force: bool,
    ) -> Optional[SchedulerRejected]:
        """The pool-role contract at /fleet/generate admission: a prefill
        process serves only ``prefill_only`` work; a decode process never
        runs long prefill — a request whose un-restorable suffix exceeds
        ``decode_max_prefill_tokens`` sheds with reason ``pool_role`` so the
        FleetRouter hands it off instead.  ``force`` bypasses (counted):
        when a whole pool is dead, availability beats purity."""
        pool = self.pool
        if pool == "unified":
            return None
        if force:
            with self._lock:
                self.pool_bypasses += 1
            return None
        if pool == "prefill" and not prefill_only:
            with self._lock:
                self.pool_rejects += 1
            return SchedulerRejected("pool_role", 1.0)
        if pool == "decode":
            if prefill_only:
                with self._lock:
                    self.pool_rejects += 1
                return SchedulerRejected("pool_role", 1.0)
            warm = self._holds(eng, prompt_ids, prefix_len)
            suffix = len(prompt_ids) - (prefix_len if warm else 0)
            if suffix > self.decode_max_prefill_tokens:
                with self._lock:
                    self.pool_rejects += 1
                return SchedulerRejected("pool_role", 1.0)
        return None

    @staticmethod
    def _holds(eng: Any, prompt_ids: Sequence[int], prefix_len: int) -> bool:
        reps = getattr(eng, "replicas", None)
        engines = [rep.engine for rep in reps] if reps is not None else [eng]
        for e in engines:
            fn = getattr(e, "holds_prefix", None)
            if callable(fn):
                try:
                    if fn(prompt_ids, prefix_len):
                        return True
                except Exception:
                    continue
        return False

    # ----------------------------------------------------------- healthz etc
    def healthz(self, *, check_peers: bool = False) -> dict:
        """The /fleet/healthz body: per-model supervision/load/latency/
        breaker summary plus the fleet block (pool role, gossip seq, peer
        reachability).  ``check_peers`` probes each configured peer's
        /healthz with a short timeout — the fleet status degrades when a
        peer is gone, which is exactly what the chaos smoke asserts."""
        reg = self.registry
        status = "ok"
        models: Dict[str, Any] = {}
        queued_total = 0
        active_total = 0
        for name, eng in getattr(reg, "generators", {}).items():
            m: Dict[str, Any] = {}
            try:
                m["queued"] = int(eng.queued_depth())
                m["active"] = int(eng.num_active)
            except Exception:
                m["queued"] = m["active"] = 0
            queued_total += m["queued"]
            active_total += m["active"]
            healthy_fn = getattr(eng, "healthy", None)
            if callable(healthy_fn):
                try:
                    m["healthy"] = bool(healthy_fn())
                except Exception:
                    m["healthy"] = False
                if not m["healthy"]:
                    status = "degraded"
            lat = getattr(eng, "latency_stats", None)
            if callable(lat):
                try:
                    m["latency"] = lat()
                except Exception:
                    pass
            rs = getattr(eng, "router_stats", None)
            if callable(rs):
                try:
                    r = rs()
                    m["replicas"] = [
                        {
                            "name": rep["name"],
                            "breaker": rep["breaker"],
                            "draining": rep["draining"],
                        }
                        for rep in r.get("replicas", [])
                    ]
                    for k in ("slices_total", "slices_free"):
                        if k in r:
                            m[k] = r[k]
                except Exception:
                    pass
            models[name] = m
        with self._lock:
            seq = self._seq
            digest = self._digest
            integrity_rejects = self.kv_integrity_rejects
            idem_hits = self.idem_hits
        out = {
            "status": status,
            "name": self.name,
            "load": {"queued": queued_total, "active": active_total},
            "models": models,
            "fleet": {
                "pool": self.pool,
                "seq": seq,
                "digest": digest,
                "peers_total": len(self.peers),
                "kv_integrity_rejects": integrity_rejects,
                "idem_hits": idem_hits,
            },
        }
        if check_peers and self.peers:
            reachable = 0
            peer_rows = []
            for pname, url in self.peers:
                ok = True
                try:
                    PeerClient(url, timeout_s=2.0).get_json("/healthz")
                except (PeerUnreachable, PeerHTTPError, ValueError):
                    ok = False
                reachable += 1 if ok else 0
                peer_rows.append({"name": pname, "url": url, "reachable": ok})
            out["fleet"]["peers_reachable"] = reachable
            out["fleet"]["peers"] = peer_rows
            out["fleet"]["status"] = (
                "ok" if reachable == len(self.peers) else "degraded"
            )
        return out

    def collect_traces(self) -> List[dict]:
        """Every generator's obs trace ring, flattened — the GET /traces
        body the trace-export CLI consumes (cli/trace_export.py)."""
        out: List[dict] = []
        for _model, eng in getattr(self.registry, "generators", {}).items():
            reps = getattr(eng, "replicas", None)
            engines = [rep.engine for rep in reps] if reps is not None else [eng]
            for e in engines:
                obs = getattr(e, "obs", None)
                if obs is not None:
                    try:
                        out.extend(obs.traces())
                    except Exception:
                        logger.exception("trace collection failed")
        return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "name": self.name,
                "pool": self.pool,
                "peers_total": len(self.peers),
                "gossip_seq": self._seq,
                "gossip_digest": self._digest,
                "gossip_events_total": self.events_total,
                "kv_puts": self.kv_puts,
                "kv_gets": self.kv_gets,
                "kv_put_rejects": self.kv_put_rejects,
                "kv_integrity_rejects": self.kv_integrity_rejects,
                "idem_executions": self.idem_executions,
                "idem_hits": self.idem_hits,
                "idem_coalesced": self.idem_coalesced,
                "idem_evictions": self.idem_evictions,
                "idem_ledger": len(self._idem),
                "pages_in": self.pages_in,
                "pages_out": self.pages_out,
                "pushes": self.pushes,
                "push_failures": self.push_failures,
                "pool_rejects": self.pool_rejects,
                "pool_bypasses": self.pool_bypasses,
            }
