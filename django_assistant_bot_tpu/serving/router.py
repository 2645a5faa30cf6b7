"""Fault-tolerant multi-replica serving: the front-door engine router.

The reference's only hardware-facing component is a single FastAPI gpu_service
process — one crash takes down every bot (PAPER.md §7) — and until now this
repo's serving plane was likewise ONE :class:`~.engine.GenerationEngine`:
supervised (crash-only restarts, a restart circuit — docs/RESILIENCE.md) but
with no redundancy.  :class:`EngineRouter` owns N engine replicas — each
independently supervised, with its own scheduler, KV page pool, and fault
injector — and fronts them with the engine's own ``submit()`` /
``generate()`` / ``generate_stream()`` surface, so the HTTP layer and the
providers cannot tell a fleet from a single engine.

Dispatch policy (docs/RESILIENCE.md "Fleet topology"):

- **Health first.**  A replica is a candidate only when it is not draining,
  its engine loop is alive (running thread, fresh heartbeat, restart circuit
  closed), and its per-replica :class:`~...ai.providers.failover.CircuitBreaker`
  admits it.  The breaker — reused verbatim from the provider failover plane —
  is fed by :class:`~.engine.EngineUnavailable`, heartbeat staleness, dead
  threads, and replica-shaped request failures; a half-open breaker admits
  exactly one probe request, so a recovering replica earns traffic back one
  request at a time instead of eating a thundering herd.
- **Prefix affinity, then least-loaded.**  A request carrying a shareable
  prefix (system prompt + packed RAG context) is routed to the replica whose
  KV page pool *already holds* that prefix — a read-only, LRU-neutral registry
  peek (:meth:`~.kv_pool.PageAllocator.holds_prefix`), so multi-turn dialogs
  keep hitting the prefix cache they warmed instead of re-prefilling on a
  random replica.  Everything else (and affinity misses) goes least-loaded:
  ``queued_depth + num_active``, rotation tie-break.  Health and breaker state
  take precedence over affinity — a cached prefix is never a reason to route
  into a sick replica.
- **Token-less re-route.**  When a replica fails a request that has emitted
  NO tokens (replica died with the request queued or mid-prefill, engine
  degraded, crash-only restart budget exhausted), the router re-submits it to
  another healthy replica — bounded by the same ``max_request_restarts``
  budget the engine's own crash-restart salvage uses, so a request that
  deterministically kills engines cannot hop forever.  Requests past their
  first token fail cleanly (a replay would double-bill latency or repeat
  streamed output) — exactly the single-engine restart contract, lifted to
  the fleet.
- **Graceful drain.**  :meth:`drain` stops admitting to one replica, lets its
  in-flight work finish (deadline-bounded, injectable clock so tests are
  deterministic), then restarts it while the rest of the fleet absorbs
  traffic; :meth:`rolling_restart` chains that over every replica for
  zero-downtime restarts.  ``drain_all`` (no restart) is the SIGTERM path:
  the server stops admission, the fleet finishes what it accepted, the
  process exits 0.

- **Dynamic fleet size.**  :meth:`add_replica` spawns a fresh replica from
  the registry-provided factory (same shared weights, its own scheduler/KV
  pool/faults) and :meth:`remove_replica` drains one and detaches it — the
  SLO autoscaler's actuators (serving/autoscaler.py, docs/AUTOSCALING.md).
  Dispatch state is held by replica OBJECT, never by index, so a request's
  re-route callback stays correct while the fleet grows or shrinks under it.

Chaos sites ``replica_dead`` / ``replica_slow`` (serving/faults.py) exercise
all of the above deterministically: ``replica_dead`` kills the replica the
dispatcher is about to pick — in-flight work fails, the breaker trips, and
token-less requests re-route — and the ``router_*`` bench section measures
goodput and recovery the same way ``chaos_*`` does for one engine.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence, Set

from ..ai.providers.failover import CircuitBreaker
from .engine import EngineUnavailable, GenerationEngine, _safe_resolve
from .kv_pool import TIER_DISK, TIER_HBM, TIER_HOST
from .obs import new_trace_id
from .scheduler import SchedulerRejected

logger = logging.getLogger(__name__)


class FleetPrefixRegistry:
    """Router-owned map of which replica holds which warm prefix, at which
    tier — the fleet-level promotion of the per-replica ``holds_prefix`` peek
    (docs/KV_PAGING.md "Tiered KV").

    Fed by the engines' tier-transition events (register/spill/restore/
    evict — :meth:`GenerationEngine.set_prefix_listener`), so it SURVIVES
    what the per-replica peek cannot: a crash-only restart downgrades a
    replica's entries from ``hbm`` to ``host`` (write-through kept the
    bytes) instead of forgetting them, and a scale-down migration re-points
    entries at the absorbing replica.  Affinity dispatch reads
    :meth:`holders` instead of peeking N allocators per request.

    Lock discipline: one leaf lock.  Event callbacks arrive from engine
    threads (and the router thread during migration absorb) OUTSIDE every
    engine/allocator/tier lock; readers are dispatch and stats threads.
    Nothing is called out of this class while the lock is held."""

    # event -> (tier, present-after-event)
    _EVENTS = {
        "register": (TIER_HBM, True),
        "restore": (TIER_HBM, True),  # re-registered by the restore admit
        "evict_spilled": (TIER_HBM, False),
        "evict_dropped": (TIER_HBM, False),
        "host_put": (TIER_HOST, True),
        "disk_promote": (TIER_HOST, True),
        "host_evict_disk": (TIER_HOST, False),
        "host_evict_dropped": (TIER_HOST, False),
        "host_put_too_large": (TIER_HOST, False),
        "disk_drop": (TIER_DISK, False),
    }
    # host_evict_disk also ADDS the disk tier; disk_promote removes it
    _RANK = {TIER_HBM: 0, TIER_HOST: 1, TIER_DISK: 2}

    def __init__(self):
        self._lock = threading.Lock()
        # key -> {replica_name -> set(tiers)}
        self._entries: dict = {}
        # first token -> set(keys): holders() only scans keys that can
        # possibly prefix the prompt, so per-dispatch cost tracks the
        # MATCHING warm set, not total fleet warm state
        self._by_first: dict = {}

    def _index_add_locked(self, key: tuple) -> None:
        self._by_first.setdefault(key[0], set()).add(key)

    def _index_drop_locked(self, key: tuple) -> None:
        bucket = self._by_first.get(key[0])
        if bucket is not None:
            bucket.discard(key)
            if not bucket:
                del self._by_first[key[0]]

    def on_event(self, replica: str, event: str, key: tuple, length: int) -> None:
        tier_change = self._EVENTS.get(event)
        if tier_change is None:
            return
        tier, present = tier_change
        with self._lock:
            holders = self._entries.setdefault(key, {})
            self._index_add_locked(key)
            tiers = holders.setdefault(replica, set())
            if present:
                tiers.add(tier)
            else:
                tiers.discard(tier)
            if event == "host_evict_disk":
                tiers.add(TIER_DISK)
            elif event == "disk_promote":
                tiers.discard(TIER_DISK)
            if not tiers:
                holders.pop(replica, None)
            if not holders:
                self._entries.pop(key, None)
                self._index_drop_locked(key)

    def apply_holding(
        self, replica: str, key: tuple, length: int, tier: str
    ) -> None:
        """Directly assert one (replica, key, tier) holding — the fleet
        plane's SNAPSHOT application path (serving/fleet.py): when a peer's
        gossip delta log has been trimmed past the follower's cursor, the
        follower drops that peer's holdings and re-applies the full holdings
        snapshot through here instead of replaying events it never saw."""
        if tier not in self._RANK or length <= 0:
            return
        with self._lock:
            holders = self._entries.setdefault(key, {})
            self._index_add_locked(key)
            holders.setdefault(replica, set()).add(tier)

    def drop_replica(self, replica: str) -> int:
        """Forget every entry held only by ``replica`` (detach epilogue —
        migrated entries were already re-pointed by the target's absorb
        events).  Returns how many (key, replica) holdings dropped."""
        n = 0
        with self._lock:
            for key in list(self._entries):
                holders = self._entries[key]
                if replica in holders:
                    del holders[replica]
                    n += 1
                    if not holders:
                        del self._entries[key]
                        self._index_drop_locked(key)
        return n

    def holders(
        self, prompt_ids: Sequence[int], prefix_len: int
    ) -> Dict[str, str]:
        """replica name -> best tier (``hbm`` < ``host`` < ``disk``) over
        EVERY registered prefix of this prompt that replica holds — not just
        the fleet-wide longest match.  Per-replica aggregation preserves the
        old peek-every-allocator semantics: when the longest-prefix holder
        is draining or unhealthy, a replica warm with a SHORTER prefix (an
        earlier turn of the same session) still beats a cold one."""
        if prefix_len <= 0:
            return {}
        n = len(prompt_ids)
        if n == 0:
            return {}
        first = prompt_ids[0]
        out: Dict[str, str] = {}
        with self._lock:
            # first-token bucket + O(1) last-token rejection before the
            # O(ln) slice: this runs under the dispatch lock on EVERY
            # routed request, so cost tracks the matching warm set, not
            # total fleet warm state
            for key in self._by_first.get(first, ()):
                holders = self._entries.get(key)
                if holders is None:
                    continue
                ln = len(key)
                if (
                    ln >= n
                    or key[-1] != prompt_ids[ln - 1]
                    or tuple(prompt_ids[:ln]) != key
                ):
                    continue
                for rep, tiers in holders.items():
                    if not tiers:
                        continue
                    tier = min(tiers, key=self._RANK.__getitem__)
                    cur = out.get(rep)
                    if cur is None or self._RANK[tier] < self._RANK[cur]:
                        out[rep] = tier
        return out

    def stats(self) -> dict:
        with self._lock:
            per_tier = {TIER_HBM: 0, TIER_HOST: 0, TIER_DISK: 0}
            holdings = 0
            for holders in self._entries.values():
                for tiers in holders.values():
                    holdings += 1
                    for t in tiers:
                        per_tier[t] += 1
            return {
                "prefixes": len(self._entries),
                "holdings": holdings,
                "hbm": per_tier[TIER_HBM],
                "host": per_tier[TIER_HOST],
                "disk": per_tier[TIER_DISK],
            }


class _StreamShim:
    """Router-side token tap between an engine and the client's TokenStream.

    Counts every client-visible token (the re-route eligibility test: ONLY
    token-less requests may move replica) and forwards to the real stream
    when one is attached.  The terminal event is NOT forwarded from the inner
    engine future — the router resolves its OUTER future (which carries the
    client stream's ``finish`` callback) only once re-routing is settled, so
    a replica death mid-queue never closes the client stream early."""

    __slots__ = ("inner", "tokens")

    def __init__(self, inner: Any = None):
        self.inner = inner
        self.tokens = 0

    def push_token(
        self, tok: int, *, notify: bool = True, at: Optional[float] = None
    ) -> bool:
        self.tokens += 1
        if self.inner is not None:
            return self.inner.push_token(tok, notify=notify, at=at)
        return False

    def notify_now(self) -> None:
        if self.inner is not None:
            self.inner.notify_now()

    def finish(self, fut: Future) -> None:  # inner future done-callback
        pass  # terminal rides the router's outer future instead


class _Replica:
    """One engine behind the router: breaker, drain flag, counters."""

    __slots__ = (
        "engine",
        "name",
        "breaker",
        "draining",
        "dispatched",
        "completed_ok",
        "last_success_at",
    )

    def __init__(self, engine: GenerationEngine, name: str, breaker: CircuitBreaker):
        self.engine = engine
        self.name = name
        self.breaker = breaker
        self.draining = False
        self.dispatched = 0
        self.completed_ok = 0
        self.last_success_at: Optional[float] = None


class _Routed:
    """Mutable per-request routing state carried across re-dispatches."""

    __slots__ = (
        "prompt_ids",
        "kwargs",
        "outer",
        "shim",
        "reroutes",
        "replica",
        "inner",
        "holders",
        "deadline_at",
    )

    def __init__(
        self,
        prompt_ids: List[int],
        kwargs: dict,
        outer: Future,
        shim: _StreamShim,
        *,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.prompt_ids = prompt_ids
        self.kwargs = kwargs
        self.outer = outer
        self.shim = shim
        self.reroutes = 0
        # the _Replica OBJECT currently carrying the request — never an index:
        # add_replica/remove_replica shift list positions under live requests
        self.replica: Optional[_Replica] = None
        self.inner: Optional[Future] = None
        # the client's ABSOLUTE deadline, fixed at first submission: each
        # engine.submit computes its own deadline_at from deadline_s, so a
        # re-route must pass the REMAINING budget, not restart the clock —
        # otherwise every hop silently grants the client a fresh deadline.
        # The router's injectable clock rides in so fake-time drain tests
        # see deadline math too (dabtlint DABT105).
        self.deadline_at: Optional[float] = None
        if kwargs.get("deadline_s") is not None:
            self.deadline_at = clock() + float(kwargs["deadline_s"])
        # replicas whose prefix registry held this prompt's prefix at the
        # last candidate ordering — a hit is counted only when the replica
        # ACTUALLY dispatched to is one of them (a skipped holder is a miss)
        self.holders: Set["_Replica"] = set()


class EngineRouter:
    """N supervised :class:`~.engine.GenerationEngine` replicas behind one
    engine-shaped face (``submit``/``generate``/``generate_stream``/stats).

    ``clock``/``sleep`` are injectable so the drain deadline logic is
    deterministic under test; the engines themselves keep real time."""

    def __init__(
        self,
        engines: Sequence[GenerationEngine],
        *,
        names: Optional[Sequence[str]] = None,
        breaker_threshold: int = 3,
        breaker_reset_s: float = 10.0,
        max_reroutes: Optional[int] = None,
        faults=None,
        replica_factory: Optional[Callable[[int], GenerationEngine]] = None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ):
        if not engines:
            raise ValueError("EngineRouter needs at least one engine replica")
        self._clock = clock
        self._sleep = sleep
        self._faults = faults
        # spawns replica N from the shared ModelSpec weights (registry
        # closure) — the autoscaler's scale-up actuator; None = fixed fleet
        self._replica_factory = replica_factory
        self._breaker_threshold = int(breaker_threshold)
        self._breaker_reset_s = float(breaker_reset_s)
        names = list(names) if names else [f"replica{i}" for i in range(len(engines))]
        if len(names) != len(engines):
            raise ValueError("names must match engines 1:1")
        self.replicas: List[_Replica] = [
            _Replica(
                eng,
                name,
                CircuitBreaker(breaker_threshold, breaker_reset_s, clock=clock),
            )
            for eng, name in zip(engines, names)
        ]
        # monotonic spawn counter: replica names are never reused, so flight
        # artifacts and /metrics labels stay unambiguous across scale cycles
        self._spawned = len(engines)
        # mesh-sliced fleet (parallel/slicing.py): the registry attaches its
        # MeshPlanner here so /healthz + /metrics can report slice capacity
        # next to the fleet gauges; None on an unsliced fleet
        self.mesh_planner = None
        # one request survives at most this many replica hops — the same
        # budget the engines' own crash-restart salvage enforces per replica
        self.max_reroutes = (
            int(max_reroutes)
            if max_reroutes is not None
            else max(e.max_request_restarts for e in engines)
        )
        self.tokenizer = engines[0].tokenizer
        # the fleet's context contract is the tightest replica's (the
        # in-process TPUProvider reads this off whatever the registry hands
        # it for prompt budgeting — replicas are homogeneous today, but min
        # stays honest if that ever changes)
        self.max_seq_len = min(e.max_seq_len for e in engines)
        self.scheduler = None  # per-replica schedulers; see router_stats()
        self._lock = threading.Lock()
        self._rr = 0  # rotation counter: load-tie break spreads, not pins
        self.affinity_hits = 0
        self.affinity_misses = 0
        self.reroutes = 0
        self.rerouted_failed = 0  # token-less re-routable failures past budget
        # replica-shaped failures a request could NOT be re-routed away from
        # (it was past its first client-visible token — the honest cost of a
        # replica death, distinguished from token-less goodput in the bench)
        self.failed_past_first_token = 0
        self.drains = 0
        self.drain_shed = 0  # requests failed by a deadline-forced drain
        self.no_replica_available = 0
        # dynamic-fleet counters (scale events are scrapeable via /metrics)
        self.replicas_added = 0
        self.replicas_removed = 0
        self.replica_restarts = 0
        # --- durable warm state (docs/KV_PAGING.md "Tiered KV") -----------
        # fleet-wide prefix registry: which replica holds which warm prefix,
        # at which tier — affinity survives drains, restarts, scale-downs
        self.prefix_registry = FleetPrefixRegistry()
        # scale-down warm-state accounting: pages the fleet LOST at a
        # detach (the satellite counter — visible even before migration
        # lands a target) vs pages/entries migration preserved
        self.pages_lost_at_detach = 0
        self.pages_migrated = 0
        self.entries_migrated = 0
        self.detach_migrations = 0
        # cross-process fleet plane tap (set_event_tap): forwarded a copy of
        # every tier event so the gossip delta log sees what the registry saw
        self._event_tap: Optional[Callable[..., None]] = None
        for rep in self.replicas:
            self._wire_replica(rep)

    def _wire_replica(self, rep: "_Replica") -> None:
        """Subscribe the fleet prefix registry to this replica's KV
        tier-transition events (no-op for engines without the hook — stub
        engines in tests).  When an event tap is attached
        (:meth:`set_event_tap` — the cross-process fleet plane's gossip
        log), every event ALSO forwards there after the registry update."""
        setter = getattr(rep.engine, "set_prefix_listener", None)
        if callable(setter):
            name = rep.name

            def _listener(event, key, length, pages, _n=name):
                self.prefix_registry.on_event(_n, event, key, length)
                tap = self._event_tap
                if tap is not None:
                    try:
                        tap(_n, event, key, length)
                    except Exception:
                        logger.exception("router event tap failed (%s)", event)

            setter(_listener)

    def set_event_tap(self, fn: Optional[Callable[..., None]]) -> None:
        """Attach ``fn(replica, event, key, length)`` to ride every KV
        tier-transition event AFTER the local prefix-registry update — how
        the cross-process fleet plane (serving/fleet.py) builds its gossip
        delta log without stealing the engines' single prefix listener."""
        self._event_tap = fn

    # engine.generate / generate_stream only touch self.tokenizer and
    # self.submit — both present here, so the router reuses them verbatim
    # (tokenization, prefix split, stream plumbing identical to one engine)
    generate = GenerationEngine.generate
    generate_stream = GenerationEngine.generate_stream

    # ------------------------------------------------------------- dispatch
    def _healthy(self, rep: _Replica) -> bool:
        """Dispatch-time liveness — the ENGINE's own predicate (the same one
        /healthz reports), so routing and health reporting can never
        disagree.  (The breaker is consulted separately — this is the direct
        evidence that also FEEDS it when stale.)"""
        return rep.engine.healthy()

    def _load(self, rep: _Replica) -> int:
        return rep.engine.queued_depth() + rep.engine.num_active

    def _candidate_order(
        self, state: _Routed, exclude: Optional[Set["_Replica"]]
    ) -> List["_Replica"]:
        """Dispatch preference: non-draining replicas, prefix-registry holders
        first (least-loaded among holders), then everything else least-loaded
        with a rotating tie-break.  Returns replica OBJECTS over a snapshot of
        the (possibly growing/shrinking) fleet — positions are only used for
        the rotation tie-break."""
        with self._lock:
            self._rr += 1
            rr = self._rr
            reps = list(self.replicas)
        n = max(1, len(reps))
        pos = {id(rep): i for i, rep in enumerate(reps)}
        cands = [
            rep
            for rep in reps
            if not rep.draining and (not exclude or rep not in exclude)
        ]
        cands.sort(key=lambda rep: (self._load(rep), (pos[id(rep)] - rr) % n))
        prefix_len = state.kwargs.get("prefix_len", 0)
        state.holders = set()
        if prefix_len and len(cands) > 1:
            # the fleet registry answers in one lookup (and knows the TIER:
            # an HBM holder beats a host/disk holder — zero-copy sharing vs
            # a restore upload); the per-replica peek remains as a fallback
            # for engines that emit no tier events (stubs, a replica whose
            # listener is unset)
            tiers = self.prefix_registry.holders(state.prompt_ids, prefix_len)
            hbm = [rep for rep in cands if tiers.get(rep.name) == TIER_HBM]
            warm = [
                rep
                for rep in cands
                if tiers.get(rep.name) in (TIER_HOST, TIER_DISK)
            ]
            # peek every candidate the registry has NO answer for — not
            # just the all-empty case: a non-event-emitting replica's warm
            # state must stay visible even while event-emitting replicas
            # hold (worse-tier) matches of the same session
            for rep in cands:
                if rep.name not in tiers and rep.engine.holds_prefix(
                    state.prompt_ids, prefix_len
                ):
                    hbm.append(rep)
            if hbm or warm:
                state.holders = set(hbm) | set(warm)
                rest = [rep for rep in cands if rep not in state.holders]
                cands = hbm + warm + rest
        return cands

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
        received_at: Optional[float] = None,
    ) -> Future:
        """Thread-safe fleet submission; returns Future[GenerationResult].

        Raises :class:`SchedulerRejected` when every candidate replica sheds
        (fleet-wide overload) and :class:`EngineUnavailable` when no healthy
        replica exists — the same synchronous contract one engine has, so the
        HTTP layer's 429/503 mapping applies unchanged."""
        if self._faults is not None:
            # deterministic fleet chaos: a stalled dispatch hop, or the
            # picked replica dying under the dispatcher's feet (the injected
            # sleep, so fake-time harnesses stay deterministic)
            delay = self._faults.sleep_s("replica_slow")
            if delay:
                self._sleep(delay)
        outer: Future = Future()
        if stream is not None:
            outer.add_done_callback(stream.finish)
        state = _Routed(
            list(prompt_ids),
            dict(
                max_tokens=max_tokens,
                temperature=temperature,
                top_p=top_p,
                json_format=json_format,
                prefix_len=prefix_len,
                priority=priority,
                tenant=tenant,
                deadline_s=deadline_s,
                # assigned HERE (not per-engine) so every re-route hop and
                # the flight-recorder events of each replica carry ONE id —
                # a failed leg and its retry correlate by trace_id alone
                trace_id=trace_id or new_trace_id(),
                # receipt at the socket rides every hop: usage.timings'
                # encode_s then includes the routing (and any re-route)
                **({} if received_at is None else {"received_at": received_at}),
            ),
            outer,
            _StreamShim(stream),
            clock=self._clock,
        )
        if self._faults is not None and self._faults.should_fire("replica_dead"):
            order = self._candidate_order(state, None)
            if order:
                self._kill(order[0])
        self._dispatch(state, exclude=None, sync=True)
        # outer cancel (client disconnect) must reach whichever inner future
        # currently carries the request so the engine's reap frees the slot
        outer.add_done_callback(lambda f: self._propagate_cancel(state, f))
        return outer

    def _propagate_cancel(self, state: _Routed, outer: Future) -> None:
        if outer.cancelled():
            inner = state.inner
            if inner is not None and not inner.done():
                inner.cancel()

    def _dispatch(
        self, state: _Routed, exclude: Optional[Set["_Replica"]], *, sync: bool
    ) -> None:
        """Try candidates in preference order; on ``sync`` (the caller's
        thread) synchronous rejections raise, on re-route they resolve the
        outer future instead."""
        last_unavail: Optional[EngineUnavailable] = None
        last_shed: Optional[SchedulerRejected] = None
        for rep in self._candidate_order(state, exclude):
            br = rep.breaker
            if not br.allow():
                continue
            if not self._healthy(rep):
                # heartbeat-stale / dead-thread / degraded evidence feeds the
                # breaker directly (and clears any probe slot allow() claimed)
                br.record_failure()
                continue
            try:
                inner = rep.engine.submit(state.prompt_ids, **state.kwargs, stream=state.shim)
            except EngineUnavailable as e:
                br.record_failure()
                last_unavail = e
                continue
            except SchedulerRejected as e:
                # load shed is pressure, not a fault: the probe slot frees
                # and the breaker's failure streak is untouched
                br.release_probe()
                last_shed = e
                continue
            with self._lock:
                rep.dispatched += 1
                if state.kwargs.get("prefix_len", 0) and len(self.replicas) > 1:
                    # a hit only if THIS replica holds the prefix — a holder
                    # skipped for health/breaker reasons is a miss (the
                    # request re-prefills), and the gauge must say so
                    if rep in state.holders:
                        self.affinity_hits += 1
                    else:
                        self.affinity_misses += 1
            state.replica = rep
            state.inner = inner
            if state.outer.cancelled():
                inner.cancel()
            inner.add_done_callback(
                lambda f, s=state, r=rep: self._on_inner_done(s, r, f)
            )
            return
        # no replica took it
        with self._lock:
            self.no_replica_available += 1
            reps = list(self.replicas)
        exc: BaseException
        if last_shed is not None and last_unavail is None:
            exc = last_shed
        elif last_unavail is not None and last_shed is None:
            exc = last_unavail
        elif last_shed is not None and last_unavail is not None:
            # mixed: prefer the shed (429 + honest Retry-After) — part of
            # the fleet is alive, the client should back off and retry
            exc = last_shed
        else:
            # honest Retry-After: the soonest any breaker would re-admit —
            # the predictive-admission discipline (no fixed constants) applied
            # to the 503 path too (docs/AUTOSCALING.md)
            hints = [rep.breaker.retry_in_s() for rep in reps]
            retry = min((h for h in hints if h > 0), default=1.0)
            exc = EngineUnavailable(
                "no healthy replica available",
                retry_after_s=min(30.0, max(0.5, retry)),
            )
        if sync:
            raise exc
        _safe_resolve(state.outer, exc=exc)

    @staticmethod
    def _reroutable(exc: BaseException) -> bool:
        """Replica-shaped failures (the replica died / degraded / kept
        crashing) re-route; request-shaped outcomes (deadline, shed,
        poisoned prompt, bad arguments) stick with the request."""
        from .engine import RequestPoisoned
        from .scheduler import DeadlineExceeded

        if isinstance(
            exc, (DeadlineExceeded, SchedulerRejected, RequestPoisoned, ValueError)
        ):
            return False
        return isinstance(exc, Exception)

    def _on_inner_done(self, state: _Routed, rep: "_Replica", inner: Future) -> None:
        br = rep.breaker
        if state.outer.cancelled():
            # the client went away; the engine's reap already owns cleanup —
            # just free any half-open probe slot this request held
            br.release_probe()
            return
        if inner.cancelled():
            br.release_probe()
            state.outer.cancel()
            return
        exc = inner.exception()
        if exc is None:
            now = self._clock()
            with self._lock:
                rep.completed_ok += 1
                rep.last_success_at = now
            br.record_success()
            _safe_resolve(state.outer, result=inner.result())
            return
        if self._reroutable(exc):
            br.record_failure()
            if state.shim.tokens == 0 and state.reroutes < self.max_reroutes:
                if state.deadline_at is not None:
                    # the single-engine salvage keeps the original
                    # _Request.deadline_at; the fleet contract must match —
                    # pass the REMAINING budget, and a hop with none left is
                    # a deadline failure, not a fresh attempt
                    remaining = state.deadline_at - self._clock()
                    if remaining <= 0:
                        from .scheduler import DeadlineExceeded

                        _safe_resolve(
                            state.outer,
                            exc=DeadlineExceeded(
                                "deadline expired while re-routing off a "
                                f"failed replica ({rep.name})"
                            ),
                        )
                        return
                    state.kwargs["deadline_s"] = remaining
                state.reroutes += 1
                with self._lock:
                    self.reroutes += 1
                obs = getattr(rep.engine, "obs", None)
                if obs is not None:
                    # the failed replica's flight ring keeps the hop evidence
                    # (a later dump of EITHER replica shows the re-route)
                    obs.flight.record(
                        "reroute",
                        trace_id=state.kwargs.get("trace_id"),
                        from_replica=rep.name,
                        hop=state.reroutes,
                        error=f"{type(exc).__name__}: {exc}",
                    )
                logger.warning(
                    "router: re-routing token-less request off %s (%s: %s); "
                    "hop %d/%d",
                    rep.name,
                    type(exc).__name__,
                    exc,
                    state.reroutes,
                    self.max_reroutes,
                )
                try:
                    self._dispatch(state, exclude={rep}, sync=False)
                except Exception as redispatch_exc:  # pragma: no cover - belt
                    # an unexpected submit error here would otherwise be
                    # swallowed by Future._invoke_callbacks and leave the
                    # outer future pending FOREVER — resolve it instead
                    logger.exception("router: re-dispatch failed")
                    _safe_resolve(state.outer, exc=redispatch_exc)
                return
            with self._lock:
                if state.shim.tokens == 0:
                    self.rerouted_failed += 1
                else:
                    self.failed_past_first_token += 1
        else:
            # the replica answered (with a request-level outcome): that
            # resolves a half-open probe as success and ends any streak
            br.record_success()
        _safe_resolve(state.outer, exc=exc)

    # ------------------------------------------------------ chaos / recovery
    def _kill(self, rep: "_Replica") -> None:
        logger.warning("router: chaos killed %s", rep.name)
        obs = getattr(rep.engine, "obs", None)
        if obs is not None:
            obs.flight.record("replica_kill", replica=rep.name)
        rep.engine._running = False

    def kill_replica(self, idx: int) -> None:
        """Abrupt replica death (the ``replica_dead`` chaos site): drop the
        engine's run flag so its loop exits at the top of the next iteration
        and its ``_shutdown`` fails everything in flight — exactly what the
        router must survive.  No drain, no goodbye."""
        self._kill(self.replicas[idx])

    def _restart_rep(self, rep: "_Replica", *, stop_timeout_s: float = 30.0) -> None:
        rep.engine.stop(drain_timeout_s=stop_timeout_s)
        rep.engine.start()
        rep.breaker.record_success()
        with self._lock:
            self.replica_restarts += 1

    def restart_replica(self, idx: int, *, stop_timeout_s: float = 30.0) -> None:
        """Operator restart of a (dead or drained) replica: bounded stop —
        failing whatever the dead loop left behind — then a fresh loop
        thread.  The breaker closes on the explicit restart; the device
        state (weights, caches, prefix registry) carries over."""
        self._restart_rep(self.replicas[idx], stop_timeout_s=stop_timeout_s)

    # ------------------------------------------------------- dynamic fleet
    def add_replica(self, engine: Optional[GenerationEngine] = None) -> str:
        """Grow the fleet by one replica and return its name — the
        autoscaler's scale-up actuator.  ``engine`` defaults to one spawned
        from the registry's ``replica_factory`` (shared ModelSpec weights;
        the factory returns a STARTED engine).  The new replica opens for
        dispatch atomically with its list append; its spawn index is
        monotonic, so names are never reused across scale cycles."""
        with self._lock:
            spawn_idx = self._spawned
            self._spawned += 1
        if engine is None:
            if self._replica_factory is None:
                with self._lock:
                    self._spawned -= 1
                raise RuntimeError(
                    "add_replica needs an engine or a replica_factory"
                )
            engine = self._replica_factory(spawn_idx)
        name = getattr(engine, "name", None) or f"replica{spawn_idx}"
        rep = _Replica(
            engine,
            name,
            CircuitBreaker(
                self._breaker_threshold, self._breaker_reset_s, clock=self._clock
            ),
        )
        if not getattr(engine, "_running", False):
            engine.start()
        self._wire_replica(rep)
        obs = getattr(engine, "obs", None)
        if obs is not None:
            obs.flight.record("replica_added", replica=name)
        with self._lock:
            self.replicas.append(rep)
            self.replicas_added += 1
        logger.info("router: added replica %s (fleet=%d)", name, len(self.replicas))
        return name

    def remove_replica(
        self,
        idx: int,
        *,
        deadline_s: float = 30.0,
        poll_s: float = 0.005,
        migrate: bool = True,
    ) -> dict:
        """Shrink the fleet by one replica: stop admitting to it, wait —
        deadline-bounded — for its in-flight work, then MIGRATE its warm KV
        state to a surviving replica's host tier, then stop and DETACH it
        (the autoscaler's scale-down actuator; drain-then-detach, no
        restart).  Safe against the replica dying mid-drain: a dead engine
        fails its in-flight work and reads idle, so the drain completes
        instead of wedging — and because the migration export is a pure
        host-memory snapshot (numpy copies, not device state), it still
        lands even when the replica died under the drain.  Without
        ``migrate`` (or without a host tier / a surviving target) the warm
        state is DROPPED and charged to ``pages_lost_at_detach`` — the
        scale-down-as-cache-wipe cost, now visible instead of silent."""
        with self._lock:
            if len(self.replicas) <= 1:
                raise RuntimeError("cannot remove the last replica")
            rep = self.replicas[idx]
            if rep.draining:
                raise RuntimeError(f"{rep.name} is already draining")
            rep.draining = True
            self.drains += 1
        obs = getattr(rep.engine, "obs", None)
        if obs is not None:
            obs.flight.record("scale_down", replica=rep.name)
        wait = self._wait_replica_idle(
            rep, deadline_s=deadline_s, poll_s=poll_s, tail="they fail on detach"
        )
        died = not rep.engine._running
        # warm-state migration BEFORE stop(): the export snapshots host
        # numpy (valid even if the engine died mid-drain — the race the
        # lock witness covers); the device registry's not-yet-spilled
        # entries are force-spilled while the engine object still exists
        migration = self._migrate_warm_state(rep, migrate=migrate)
        # stop fails anything the deadline forced (token-less victims
        # re-route through their done-callbacks, same as a replica death)
        rep.engine.stop(drain_timeout_s=1.0)
        # sliced fleet: return the replica's device slice to the planner so
        # a later scale-up can reuse those chips (idempotent release; the
        # hook exists only on slice-pinned engines).  AFTER stop(): the
        # engine must never tick on a slice another replica could acquire.
        release = getattr(rep.engine, "release_slice", None)
        if callable(release):
            try:
                release()
            except Exception:  # pragma: no cover - planner release is leaf
                logger.exception(
                    "router: slice release failed for %s", rep.name
                )
        self.prefix_registry.drop_replica(rep.name)
        with self._lock:
            if rep in self.replicas:
                self.replicas.remove(rep)
            self.replicas_removed += 1
            rep.draining = False
        report = {
            "replica": rep.name,
            "died_mid_drain": died,
            "slice_id": getattr(rep.engine, "slice_id", None),
            **wait,
            **migration,
        }
        if obs is not None:
            obs.flight.record("replica_removed", **report)
            if died or wait["forced_failures"]:
                # the race the lock witness + flight recorder exist to catch:
                # the replica died (or shed) under a scale-down — dump the
                # ring so the artifact shows the kill AND the scale decision
                obs.flight.dump("scale_down_interrupted", **report)
        logger.info(
            "router: removed replica %s (fleet=%d, drained=%s)",
            rep.name,
            len(self.replicas),
            wait["drained"],
        )
        return report

    def _migrate_warm_state(self, rep: "_Replica", *, migrate: bool) -> dict:
        """Move the detaching replica's warm prefixes into a surviving
        replica's host tier.  Returns the accounting block for the detach
        report: entries/pages migrated vs lost.  Never raises — a scale-down
        must complete even when the warm state cannot be saved."""
        eng = rep.engine
        pool = getattr(eng, "_kv_pool", None)
        src_tier = getattr(eng, "kv_host_tier", None)
        device_entries = pool.shared_keys() if pool is not None else []
        out = {
            "migrated_entries": 0,
            "migrated_pages": 0,
            "lost_entries": 0,
            "lost_pages": 0,
        }
        lost_reason = None
        if not migrate:
            lost_reason = "migration disabled"
        elif src_tier is None:
            lost_reason = "no host tier on the detaching replica"
        if lost_reason is None:
            # entries the device registry holds that write-through never
            # mirrored (writethrough=False): one last spill while the engine
            # object is whole.  A dead device makes the fetch raise — the
            # engine swallows it and those entries are charged as lost.
            try:
                eng.spill_registered_to_host()
            except Exception:
                logger.exception(
                    "migration: device-registry spill failed on %s", rep.name
                )
            # the FULL export — host DRAM plus disk rows loaded back into
            # memory (a prefix demoted to disk is still warm state; leaving
            # it behind would wipe it silently, since the victim's disk
            # namespace is swept on reuse).  Unreadable disk rows are
            # charged lost below.
            snapshot, unreadable = src_tier.export_all()
            with self._lock:
                others = [
                    r
                    for r in self.replicas
                    if r is not rep
                    and not r.draining
                    and getattr(r.engine, "kv_host_tier", None) is not None
                ]
            others = [r for r in others if self._healthy(r)]
            if not others:
                lost_reason = "no surviving replica with a host tier"
            else:
                target = min(others, key=self._load)
                # absorb() reports the snapshot keys the target RETAINS
                # (host or its disk tier) — per-key accounting, because a
                # put can be refused anywhere in the order (oversized
                # entry) or evict an earlier import
                retained = (
                    set(target.engine.kv_host_tier.absorb(snapshot))
                    if snapshot
                    else set()
                )
                pages_by_key = {e.key: e.pages for e in snapshot}
                out["migrated_entries"] = len(retained)
                out["migrated_pages"] = sum(
                    pg for key, pg in pages_by_key.items() if key in retained
                )
                # lost = export keys the target refused + disk rows whose
                # file could not be read back + device-registry entries that
                # never reached the export (spill failed / device died) —
                # keyed per unique prefix so a key present in two tiers is
                # charged once.  Accounted even when the export came back
                # EMPTY (the dead-device + writethrough-off shape: the
                # silent-wipe case pages_lost_at_detach exists to expose)
                lost: Dict[tuple, int] = {
                    key: pg
                    for key, pg in pages_by_key.items()
                    if key not in retained
                }
                for key, _ln, pg in unreadable:
                    lost.setdefault(key, pg)
                for key, _ln, pg in device_entries:
                    if key not in pages_by_key:
                        lost.setdefault(key, pg)
                out["lost_entries"] = len(lost)
                out["lost_pages"] = sum(lost.values())
                if snapshot:
                    with self._lock:
                        self.detach_migrations += 1
                        self.entries_migrated += out["migrated_entries"]
                        self.pages_migrated += out["migrated_pages"]
                    obs = getattr(eng, "obs", None)
                    if obs is not None:
                        obs.flight.record(
                            "kv_migrate",
                            from_replica=rep.name,
                            to_replica=target.name,
                            **out,
                        )
                    logger.info(
                        "router: migrated %d warm prefix entries (%d pages) "
                        "from %s to %s (%d lost)",
                        out["migrated_entries"],
                        out["migrated_pages"],
                        rep.name,
                        target.name,
                        out["lost_entries"],
                    )
        if lost_reason is not None:
            # the pre-migration bugfix half of the contract: a detach that
            # discards warm state SAYS so — counter + flight event — instead
            # of silently wiping the fleet's cache.  Count each UNIQUE
            # prefix once: with write-through most device-registry entries
            # also have a host copy, and summing both tiers would double
            # the reported loss.
            union: Dict[tuple, int] = {
                key: pg for key, _, pg in device_entries
            }
            if src_tier is not None:
                # warm_keys() spans host DRAM AND disk (no file reads) —
                # a prefix demoted to disk is warm state being discarded
                # just the same
                for key, pg in src_tier.warm_keys():
                    union.setdefault(key, pg)
            out["lost_entries"] = len(union)
            out["lost_pages"] = sum(union.values())
            out["lost_reason"] = lost_reason
        if out["lost_pages"]:
            with self._lock:
                self.pages_lost_at_detach += out["lost_pages"]
            obs = getattr(eng, "obs", None)
            if obs is not None:
                obs.flight.record(
                    "pages_lost_at_detach",
                    replica=rep.name,
                    pages=out["lost_pages"],
                    entries=out["lost_entries"],
                    reason=out.get("lost_reason", "budget/unsaved"),
                )
        return out

    # ---------------------------------------------------------------- drain
    def _replica_idle(self, rep: _Replica) -> bool:
        return rep.engine.idle()

    def _wait_replica_idle(
        self, rep: _Replica, *, deadline_s: float, poll_s: float, tail: str
    ) -> dict:
        """The drain-wait core shared by graceful drain (restart epilogue)
        and scale-down (detach epilogue): poll until the replica holds no
        accepted work or the deadline lands, charging ``drain_shed`` for
        whatever the deadline forces.  ``tail`` names the caller's fate for
        the forced work in the log line."""
        t0 = self._clock()
        while not self._replica_idle(rep) and self._clock() - t0 < deadline_s:
            self._sleep(poll_s)
        drained = self._replica_idle(rep)
        forced = 0
        if not drained:
            forced = rep.engine.num_active + rep.engine.queued_depth()
            with self._lock:
                self.drain_shed += forced
            logger.warning(
                "router: drain of %s hit its %.1fs deadline with %d "
                "request(s) still in flight; %s",
                rep.name,
                deadline_s,
                forced,
                tail,
            )
        return {
            "drained": drained,
            "forced_failures": forced,
            "waited_s": round(self._clock() - t0, 3),
        }

    def drain(
        self,
        idx: int,
        *,
        deadline_s: float = 30.0,
        restart: bool = True,
        poll_s: float = 0.005,
    ) -> dict:
        """Gracefully drain one replica: stop admitting to it (the rest of
        the fleet absorbs traffic), wait — deadline-bounded — for its
        in-flight and queued work to finish, then restart it.  Returns a
        summary dict; ``forced_failures`` counts requests the deadline
        forced to fail (0 on a clean drain — the zero-shed rolling-restart
        contract)."""
        return self._drain_rep(
            self.replicas[idx], deadline_s=deadline_s, restart=restart, poll_s=poll_s
        )

    def _drain_rep(
        self,
        rep: "_Replica",
        *,
        deadline_s: float = 30.0,
        restart: bool = True,
        poll_s: float = 0.005,
    ) -> dict:
        with self._lock:
            if rep not in self.replicas:
                # a concurrent remove_replica (autoscaler scale-down) won the
                # race: the replica is already detached and stopped — there
                # is nothing to drain and NOTHING to restart (restarting a
                # detached engine would orphan a running loop no dispatch
                # can reach and no stop() will ever visit)
                return {
                    "replica": rep.name,
                    "drained": True,
                    "forced_failures": 0,
                    "waited_s": 0.0,
                    "skipped": "detached",
                }
            if rep.draining:
                raise RuntimeError(f"{rep.name} is already draining")
            rep.draining = True
            self.drains += 1
        obs = getattr(rep.engine, "obs", None)
        if obs is not None:
            obs.flight.record("drain_begin", replica=rep.name)
        try:
            wait = self._wait_replica_idle(
                rep,
                deadline_s=deadline_s,
                poll_s=poll_s,
                tail="they fail on restart",
            )
            drained, forced = wait["drained"], wait["forced_failures"]
            if restart:
                with self._lock:
                    still_attached = rep in self.replicas
                if still_attached:
                    self._restart_rep(rep)
            if obs is not None:
                obs.flight.record(
                    "drain_end",
                    replica=rep.name,
                    drained=drained,
                    forced_failures=forced,
                )
                # a forced drain killed work the replica promised to finish:
                # that is a post-mortem artifact, same as a crash restart
                if forced:
                    obs.flight.dump("drain_forced", replica=rep.name, forced=forced)
            return {"replica": rep.name, **wait}
        finally:
            with self._lock:
                rep.draining = False

    def rolling_restart(self, *, deadline_s: float = 30.0) -> List[dict]:
        """Drain-and-restart every replica, one at a time, under live
        traffic — the zero-downtime restart path.  With >= 2 replicas the
        fleet keeps serving throughout.  Snapshots the fleet first: replicas
        an autoscaler adds mid-restart are already fresh, and ones it drains
        or detaches concurrently are SKIPPED (reported, not fatal) — an
        aborted rolling restart would leave the tail of the fleet on the old
        state."""
        with self._lock:
            reps = list(self.replicas)
        reports = []
        for rep in reps:
            try:
                reports.append(
                    self._drain_rep(rep, deadline_s=deadline_s, restart=True)
                )
            except RuntimeError as e:
                # concurrently draining (autoscaler scale-down mid-flight):
                # that drain already does the work this pass wanted
                reports.append({"replica": rep.name, "skipped": str(e)})
        return reports

    def begin_drain(self) -> None:
        """Non-blocking fleet-wide admission stop (the SIGTERM path): every
        replica is marked draining so dispatch fails fast while in-flight
        work keeps running.  The caller owns the wait (the server's shutdown
        handler polls ``idle()``); :meth:`drain_all` wraps both."""
        with self._lock:
            for rep in self.replicas:
                rep.draining = True

    def drain_all(self, *, deadline_s: float = 30.0, poll_s: float = 0.01) -> bool:
        """Whole-router drain (SIGTERM): stop admitting everywhere, wait for
        the fleet to finish what it accepted.  Returns True when everything
        drained inside the deadline.  No restart — the process is exiting."""
        self.begin_drain()
        t0 = self._clock()
        while self._clock() - t0 < deadline_s:
            if all(self._replica_idle(rep) for rep in list(self.replicas)):
                return True
            self._sleep(poll_s)
        return all(self._replica_idle(rep) for rep in list(self.replicas))

    # ------------------------------------------------------- engine surface
    # (aggregates snapshot the fleet list: add_replica/remove_replica mutate
    # it under the router lock while these read from scrape/HTTP threads)
    @property
    def num_active(self) -> int:
        return sum(rep.engine.num_active for rep in list(self.replicas))

    @property
    def steps(self) -> int:
        return sum(rep.engine.steps for rep in list(self.replicas))

    @property
    def reclaimed_slots(self) -> int:
        return sum(rep.engine.reclaimed_slots for rep in list(self.replicas))

    @property
    def cancelled_slots(self) -> int:
        return sum(rep.engine.cancelled_slots for rep in list(self.replicas))

    def queued_depth(self) -> int:
        return sum(rep.engine.queued_depth() for rep in list(self.replicas))

    def idle(self) -> bool:
        return all(rep.engine.idle() for rep in list(self.replicas))

    def holds_prefix(self, prompt_ids: Sequence[int], prefix_len: int) -> bool:
        return any(
            rep.engine.holds_prefix(prompt_ids, prefix_len)
            for rep in list(self.replicas)
        )

    def start(self) -> "EngineRouter":
        for rep in list(self.replicas):
            rep.engine.start()
        return self

    def stop(self, drain_timeout_s: float = 120.0) -> None:
        for rep in list(self.replicas):
            rep.engine.stop(drain_timeout_s=drain_timeout_s)

    # --------------------------------------------------------------- stats
    def router_stats(self) -> dict:
        """Fleet gauges for tick_stats / healthz: per-replica depth and
        breaker state, affinity hit rate, re-routes, drains.

        The router lock covers ONLY the router-owned counters.  Per-replica
        depth goes through ``queued_depth()`` → the replica's scheduler lock,
        and a dying replica's engine thread resolves futures UNDER that
        scheduler lock whose done-callbacks take the router lock — holding
        the router lock across the engine call would be the classic ABBA
        deadlock, wedging /healthz and every submit the moment a probe races
        a replica death."""
        with self._lock:
            hits, misses = self.affinity_hits, self.affinity_misses
            reps = list(self.replicas)
            out = {
                "n_replicas": len(reps),
                "affinity_hits": hits,
                "affinity_misses": misses,
                "affinity_hit_rate": round(hits / max(1, hits + misses), 4),
                "reroutes": self.reroutes,
                "rerouted_failed": self.rerouted_failed,
                "failed_past_first_token": self.failed_past_first_token,
                "drains": self.drains,
                "drain_shed": self.drain_shed,
                "no_replica_available": self.no_replica_available,
                "replicas_added": self.replicas_added,
                "replicas_removed": self.replicas_removed,
                "replica_restarts": self.replica_restarts,
                "pages_lost_at_detach": self.pages_lost_at_detach,
                "pages_migrated": self.pages_migrated,
                "entries_migrated": self.entries_migrated,
                "detach_migrations": self.detach_migrations,
            }
        # fleet prefix registry block (its own leaf lock — never nested
        # under the router lock)
        out["prefix_registry"] = self.prefix_registry.stats()
        out["replicas"] = [
            {
                "name": rep.name,
                "depth": rep.engine.queued_depth(),
                "active": rep.engine.num_active,
                "breaker": rep.breaker.state,
                "draining": rep.draining,
                "healthy": self._healthy(rep),
                "dispatched": rep.dispatched,
                "completed_ok": rep.completed_ok,
                "slice_id": getattr(rep.engine, "slice_id", None),
            }
            for rep in reps
        ]
        # slice capacity (sliced fleets): total/free slices next to the
        # fleet size, so "at hardware limit" is readable off one surface
        if self.mesh_planner is not None:
            ps = self.mesh_planner.stats()
            out["slices_total"] = ps["slices_total"]
            out["slices_free"] = ps["slices_free"]
            out["replica_devices"] = ps["replica_devices"]
        return out

    def slice_stats(self) -> dict:
        """Fleet slice topology for /healthz (docs/MULTICHIP.md): the
        planner's capacity snapshot plus each replica's slice identity and
        per-slice HBM ledger (engines without the surface — stubs — are
        skipped)."""
        out: dict = {
            "planner": (
                self.mesh_planner.stats()
                if self.mesh_planner is not None
                else None
            ),
        }
        per = []
        for rep in list(self.replicas):
            fn = getattr(rep.engine, "slice_stats", None)
            if callable(fn):
                s = fn()
                s["name"] = rep.name
                per.append(s)
        out["replicas"] = per
        return out

    def latency_stats(self) -> dict:
        """Fleet-wide perceived-latency percentiles: the replicas' raw TTFT /
        ITL sample windows concatenated (percentiles cannot be merged from
        per-replica percentiles)."""
        ttft: List[float] = []
        itl: List[float] = []
        for rep in list(self.replicas):
            ttft.extend(rep.engine._ttft_s)
            itl.extend(rep.engine._itl_s)
        p = GenerationEngine._pctl_ms
        return {
            "ttft_p50_ms": p(ttft, 0.50),
            "ttft_p95_ms": p(ttft, 0.95),
            "ttft_n": len(ttft),
            "itl_p50_ms": p(itl, 0.50),
            "itl_p95_ms": p(itl, 0.95),
            "itl_n": len(itl),
            "cancelled_slots": self.cancelled_slots,
        }

    def kv_stats(self) -> dict:
        """Aggregated KV gauges + the per-replica blocks."""
        per = [rep.engine.kv_stats() for rep in list(self.replicas)]
        out: dict = {
            "prefix_hits": sum(p.get("prefix_hits", 0) for p in per),
            "prefix_misses": sum(p.get("prefix_misses", 0) for p in per),
            "replicas": per,
        }
        if all("kv_pages_total" in p for p in per):
            for key in ("kv_pages_total", "kv_pages_used", "kv_pages_free"):
                out[key] = sum(p[key] for p in per)
            if all("kv_pages_obtainable" in p for p in per):
                out["kv_pages_obtainable"] = sum(
                    p["kv_pages_obtainable"] for p in per
                )
        return out

    def decode_path_stats(self) -> dict:
        """Fleet decode fast-path gauges (docs/QUANT.md): fused depth /
        weight bits from the replicas (uniform by construction — every
        replica is built from the same spec), effective depth as the MIN
        across replicas (one json-downgraded replica is what an operator
        must see), counters summed, per-replica blocks attached."""
        per = [rep.engine.decode_path_stats() for rep in list(self.replicas)]
        if not per:
            return {}
        return {
            "decode_steps": per[0]["decode_steps"],
            "decode_steps_effective": min(
                p["decode_steps_effective"] for p in per
            ),
            "json_downgraded_ticks": sum(
                p["json_downgraded_ticks"] for p in per
            ),
            "upload_overlap_frac": round(
                sum(p["upload_overlap_frac"] for p in per) / len(per), 4
            ),
            "weight_bits": per[0]["weight_bits"],
            # continuous batching: chunks piggybacked is a plain counter
            # sum; the feature flags are uniform by construction
            "prefill_piggyback": per[0].get("prefill_piggyback", False),
            "prefill_chunks_piggybacked": sum(
                p.get("prefill_chunks_piggybacked", 0) for p in per
            ),
            "attn_fp8": per[0].get("attn_fp8", False),
            "decode_kv_path": per[0].get("decode_kv_path", "xla"),
            "moe_experts_path": per[0].get("moe_experts_path"),
            "replicas": per,
        }

    def supervision_stats(self) -> dict:
        """Aggregate supervision: healthy only when EVERY replica is (one
        dead replica of N is exactly what an operator must see as degraded),
        with the per-replica blocks attached for /healthz."""
        per = []
        for rep in list(self.replicas):
            s = rep.engine.supervision_stats()
            s["name"] = rep.name
            s["breaker"] = rep.breaker.state
            s["draining"] = rep.draining
            per.append(s)
        return {
            "running": any(p["running"] for p in per),
            "healthy": all(p["healthy"] for p in per),
            "degraded": any(p["degraded"] for p in per),
            "replicas": per,
            "engine_restarts": sum(p["engine_restarts"] for p in per),
            "poisoned_requests": sum(p["poisoned_requests"] for p in per),
            "circuit_trips": sum(p["circuit_trips"] for p in per),
            "restarted_requests_resubmitted": sum(
                p["restarted_requests_resubmitted"] for p in per
            ),
            "restarted_requests_failed": sum(
                p["restarted_requests_failed"] for p in per
            ),
            "reroutes": self.reroutes,
        }

    def tick_stats(self) -> dict:
        """Fleet tick_stats: router gauges + aggregated latency/KV/supervision
        plus each replica's full engine tick_stats block."""
        out = {
            "router": self.router_stats(),
            "kv": self.kv_stats(),
            "supervision": self.supervision_stats(),
            "replicas": [rep.engine.tick_stats() for rep in list(self.replicas)],
        }
        out.update(self.latency_stats())
        return out
