"""Durable warm KV state: host-tier spill/restore + the fleet prefix
registry (docs/KV_PAGING.md "Tiered KV").

Evidence layers, all CPU so tier-1 gates the tentpole without hardware:

- host-tier unit tests (LRU byte ledger, disk demotion/promotion with raw
  byte views so fp8 round-trips, absorb/migration budgets);
- allocator integration: spill-on-evict + registration write-through via a
  fake fetch, tier-transition events firing OUTSIDE the locks;
- a pinned-seed THREE-tier fuzz extending the allocator fuzz to the
  hbm/host/disk state machine (refcount + byte-ledger invariants across
  tiers, restore racing eviction, register racing the host budget);
- engine-level: restore-then-suffix-prefill is BIT-identical to a cold full
  prefill, COW against a restored page, crash-only restart re-seeding warm
  sessions from the host tier (chaos: tick_raise mid-trace), restore racing
  a replica kill (token-less re-route, goodput 1.0);
- fleet-level: scale-down migration moves warm state to a survivor
  (pages_lost_at_detach ~ 0 with migration on, > 0 and flight-recorded
  without it), the registry re-points affinity, and migration survives the
  replica dying mid-drain (the export is host numpy, not device state).
"""

import os
import random
import time

import numpy as np

import jax

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine
from django_assistant_bot_tpu.serving.faults import FaultInjector
from django_assistant_bot_tpu.serving.kv_pool import (
    HostKVTier,
    PageAllocator,
)
from django_assistant_bot_tpu.serving.router import EngineRouter


# ----------------------------------------------------------------- helpers
def _fake_kv(n_pages: int, fill: float = 0.0, *, layers=2, kh=1, page=16, d=4):
    shape = (layers, n_pages, kh, page, d)
    return (
        np.full(shape, fill, np.float32),
        np.full(shape, -fill, np.float32),
    )


def _fake_fetch(pages):
    """Stand-in for the engine's device->host page gather: content encodes
    the page ids so a restore's bytes are checkable."""
    k, v = _fake_kv(len(pages))
    for i, p in enumerate(pages):
        k[:, i] = float(p)
        v[:, i] = -float(p)
    return k, v


_shared_params = {}


def _tiny_engine(**kw):
    cfg = DecoderConfig.tiny()
    if "params" not in _shared_params:
        _shared_params["cfg"] = cfg
        _shared_params["params"] = llama.init(cfg, jax.random.key(7))
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("kv_page_size", 64)
    kw.setdefault("prefix_cache_size", 4)
    kw.setdefault("prefix_min_tokens", 16)
    return GenerationEngine(
        _shared_params["cfg"], _shared_params["params"], ByteTokenizer(), **kw
    )


# ---------------------------------------------------------- host tier units
def test_host_tier_put_get_lru_budget():
    host = HostKVTier(1000, page_size=16)
    k, v = _fake_kv(1)  # 2*1*1*16*4*4 = 512 bytes each array
    assert k.nbytes == 512
    # one entry (1024 B) exceeds the 1000 B budget -> refused, counted
    assert not host.put((1,) * 10, 10, k, v)
    assert host.stats()["kv_tier_dropped"] == 1
    host = HostKVTier(4096, page_size=16)
    assert host.put((1,) * 10, 10, k, v)
    assert host.put((2,) * 10, 10, k, v)
    assert not host.put((1,) * 10, 10, k, v)  # duplicate: touch, not store
    s = host.stats()
    assert s["kv_host_entries"] == 2 and s["kv_host_bytes"] == 2048
    assert host.put((3,) * 10, 10, k, v)
    assert host.put((4,) * 10, 10, k, v)
    # budget 4096 holds 4 x 1024; a 5th evicts the LRU — that is (2,): the
    # duplicate put of (1,) LRU-touched it
    assert host.put((5,) * 10, 10, k, v)
    s = host.stats()
    assert s["kv_host_entries"] == 4
    assert s["kv_host_evictions"] == 1
    assert host.lookup([2] * 20, 10) is None  # (2,) was the eviction victim
    assert host.lookup([1] * 20, 10) is not None


def test_host_tier_longest_match_and_restore_count():
    host = HostKVTier(1 << 20, page_size=16)
    k, v = _fake_kv(1)
    host.put((1, 2, 3), 3, k, v)
    k2, v2 = _fake_kv(1)
    host.put((1, 2, 3, 4, 5), 5, k2, v2)
    hit = host.lookup([1, 2, 3, 4, 5, 6], 5)
    assert hit is not None and hit.length == 5
    hit = host.lookup([1, 2, 3, 9], 3)
    assert hit is not None and hit.length == 3
    # lookup is repeatable and side-effect-free (a queued head re-runs it
    # every admission attempt): only note_restored counts a SERVED restore
    assert host.stats()["kv_host_restores"] == 0
    host.note_restored((1, 2, 3))
    assert host.stats()["kv_host_restores"] == 1
    # a peek is LRU-neutral and counts nothing
    assert host.holds([1, 2, 3, 9], 3)
    assert host.stats()["kv_host_restores"] == 1


def test_host_tier_disk_demotion_promotes_bit_exact(tmp_path):
    """Host budget of one entry + a spill dir: the second entry demotes the
    first to disk; a later lookup promotes it back BIT-exact (raw byte
    views, so any pool dtype — incl. fp8 — survives the round trip)."""
    import jax.numpy as jnp

    # each fp8 entry is 2*(2*1*1*8*4) = 128 bytes; a 150-byte budget holds
    # exactly one, so the second put demotes the first to disk
    host = HostKVTier(150, page_size=8, spill_dir=str(tmp_path))

    def mk(val):
        return np.asarray(jnp.full((2, 1, 1, 8, 4), val, jnp.float8_e4m3fn))
    a_k, a_v = mk(1.5), mk(-1.5)
    host.put((1,) * 6, 6, a_k, a_v)
    host.put((2,) * 6, 6, mk(2.5), mk(-2.5))
    s = host.stats()
    assert s["kv_host_entries"] == 1 and s["kv_disk_entries"] == 1
    assert s["kv_disk_spills"] == 1
    assert any(f.startswith("kvspill-") for f in os.listdir(tmp_path))
    hit = host.lookup([1] * 10, 6)
    assert hit is not None and hit.length == 6
    np.testing.assert_array_equal(
        hit.k.view(np.uint8), a_k.view(np.uint8)
    )
    np.testing.assert_array_equal(
        hit.v.view(np.uint8), a_v.view(np.uint8)
    )
    assert host.stats()["kv_disk_promotes"] == 1


def test_host_tier_absorb_respects_budget_and_counts():
    src = HostKVTier(1 << 20, page_size=16)
    k, v = _fake_kv(1)
    for i in range(4):
        src.put((i,) * 8, 8, k, v)
    dst = HostKVTier(2 * 1024, page_size=16)  # room for 2 of the 4
    retained = dst.absorb(src.snapshot())
    assert sorted(retained) == [(2,) * 8, (3,) * 8]
    s = dst.stats()
    assert s["kv_host_entries"] == 2 and s["kv_migrated_in"] == 2
    # LRU-order import: the source's MRU entries (2,), (3,) survive the
    # target's budget; the oldest fall out
    assert dst.lookup([3] * 10, 8) is not None
    assert dst.lookup([2] * 10, 8) is not None
    assert dst.lookup([0] * 10, 8) is None


# ------------------------------------------------- allocator spill/events
def test_allocator_spills_on_evict_and_restores_content():
    host = HostKVTier(1 << 20, page_size=16)
    al = PageAllocator(
        8, 16, max_shared_entries=1, min_prefix_tokens=1,
        host_tier=host, writethrough=False,
    )
    al.bind_spill_fetch(_fake_fetch)
    p = al.alloc(2)
    assert al.register([7] * 20, 20, p)
    assert host.stats()["kv_host_entries"] == 0  # writethrough off
    al.decref(p)
    q = al.alloc(1)
    assert al.register([8] * 10, 10, q)  # entry bound 1 -> evicts [7]*20
    assert al.evictions == 1
    ent = host.lookup([7] * 30, 20)
    assert ent is not None and ent.length == 20
    # spilled content is the page-id-encoded bytes the fake fetch produced
    assert ent.k[0, 0, 0, 0, 0] == float(p[0])
    assert ent.k[0, 1, 0, 0, 0] == float(p[1])
    al.decref(q)


def test_allocator_writethrough_copies_at_registration():
    host = HostKVTier(1 << 20, page_size=16)
    al = PageAllocator(
        8, 16, max_shared_entries=4, min_prefix_tokens=1, host_tier=host
    )
    al.bind_spill_fetch(_fake_fetch)
    p = al.alloc(2)
    assert al.register([3] * 20, 20, p)
    assert host.stats()["kv_host_entries"] == 1  # copied down immediately
    # reset() (crash-only restart) keeps the host copy and says so
    events = []
    al.on_event = lambda ev, key, length, pages: events.append(ev)
    al.reset()
    assert "evict_spilled" in events
    assert host.lookup([3] * 30, 20) is not None


def test_allocator_tier_events_fire_outside_locks():
    """Listener re-enters the allocator/tier stats paths — deadlock-free
    only because events fire after the locks release."""
    host = HostKVTier(1 << 20, page_size=16)
    al = PageAllocator(
        8, 16, max_shared_entries=1, min_prefix_tokens=1, host_tier=host
    )
    al.bind_spill_fetch(_fake_fetch)
    seen = []

    def listener(ev, key, length, pages):
        # taking the same component's lock again would deadlock if the
        # event fired under it
        al.stats()
        host.stats()
        seen.append((ev, length, pages))

    al.on_event = listener
    host.on_event = listener
    p = al.alloc(1)
    al.register([1] * 10, 10, p)
    al.decref(p)
    q = al.alloc(1)
    al.register([2] * 10, 10, q)
    al.decref(q)
    evs = [e for e, _, _ in seen]
    assert "register" in evs and "host_put" in evs and "evict_spilled" in evs


# --------------------------------------------------------- three-tier fuzz
def test_allocator_three_tier_fuzz_invariants(tmp_path):
    """Pinned-seed fuzz over the THREE-tier state machine: random
    alloc/decref/register/evict/host-lookup/disk traffic must keep (a) the
    device invariants the two-tier fuzz checks, (b) the host byte ledger
    exact and within budget, and (c) restores serving entries whose bytes
    match what was spilled.  Covers restore racing eviction (a lookup's
    winner can be evicted by the very next register) by construction.
    Seed pinned in CI via DABT_KV_FUZZ_SEED."""
    seed = int(os.environ.get("DABT_KV_FUZZ_SEED", "0"))
    rng = random.Random(f"tier:{seed}")
    host = HostKVTier(
        6 * 1024, page_size=16, spill_dir=str(tmp_path), max_disk_bytes=16 * 1024
    )
    al = PageAllocator(
        32, 16, page_bytes=7, max_shared_bytes=70, max_shared_entries=4,
        min_prefix_tokens=1, host_tier=host, writethrough=True,
    )
    al.bind_spill_fetch(_fake_fetch)
    held = []
    for _step in range(1500):
        op = rng.random()
        if op < 0.35:
            n = rng.randint(1, 6)
            got = al.alloc(n)
            if got is None:
                assert al.pages_free < n
            else:
                held.append(got)
        elif op < 0.6 and held:
            al.decref(held.pop(rng.randrange(len(held))))
        elif op < 0.8 and held:
            pages = held[rng.randrange(len(held))]
            toks = rng.randrange(64)
            length = len(pages) * al.page_size - rng.randint(0, al.page_size - 1)
            al.register([toks] * length, length, pages)
        else:
            # host-tier lookup: the restore side racing the eviction side
            toks = rng.randrange(64)
            ent = host.lookup([toks] * rng.randint(1, 80), rng.randint(1, 40))
            if ent is not None:
                # the spilled bytes encode their source page ids: every
                # page's K slab must be constant and equal to -V
                assert ent.k.shape[1] == ent.pages
                np.testing.assert_array_equal(ent.k, -ent.v)
        # ---- device invariants (the original fuzz's contract) ----------
        free = al.pages_free
        with al._lock:
            refd = set(al._refs)
            free_set = set(al._free)
        assert not (refd & free_set)
        assert len(free_set) == free
        assert len(refd) + free == al.n_pages
        for pages in held:
            for p in pages:
                assert p in refd
        # ---- host/disk ledger invariants -------------------------------
        with host._lock:
            assert host._bytes == sum(e.nbytes for e in host._entries.values())
            assert host._bytes <= host.max_bytes
            assert host._disk_bytes == sum(
                nb for (_, _, nb, _) in host._disk.values()
            )
            assert host._disk_bytes <= host.max_disk_bytes
            assert not (set(host._entries) & set(host._disk))
    for pages in held:
        al.decref(pages)


# ------------------------------------------------------ engine-level tests
def test_restore_then_suffix_prefill_bit_identical_to_cold():
    """Warm a prefix, evict it to the host tier (registry bound 1), then hit
    it again: the restore path's tokens must equal a host-tier-off engine's
    (which re-prefills cold) — restore-then-suffix-prefill is bit-identical
    to a cold full prefill."""
    rng = np.random.default_rng(21)
    pref1 = rng.integers(1, 255, 100).tolist()
    pref2 = rng.integers(1, 255, 100).tolist()
    turns = [
        (pref1 + rng.integers(1, 255, 30).tolist(), len(pref1)),
        (pref2 + rng.integers(1, 255, 30).tolist(), len(pref2)),
        (pref1 + rng.integers(1, 255, 40).tolist(), len(pref1)),
    ]

    def run(host_bytes):
        eng = _tiny_engine(
            prefix_cache_size=1, kv_host_bytes=host_bytes
        ).start()
        try:
            outs = [
                eng.submit(
                    t, max_tokens=8, temperature=0.0, prefix_len=pl
                ).result(timeout=300).token_ids
                for t, pl in turns
            ]
            return outs, eng.kv_stats()
        finally:
            eng.stop()

    ref, _ = run(0)
    got, st = run(1 << 26)
    assert got == ref
    assert st["kv_restores"] >= 1
    assert st["kv_host_hits"] >= 1
    assert st["kv_restores_inflight"] == 0
    assert st["kv_restore_p95_ms"] > 0


def test_cow_against_restored_page():
    """A restored prefix is re-registered: the NEXT sharer COW-clones its
    boundary page like any registry hit, and both outputs match the
    host-tier-off reference."""
    rng = np.random.default_rng(22)
    pref1 = rng.integers(1, 255, 90).tolist()  # 90 tokens: 1 full + 1 partial page
    pref2 = rng.integers(1, 255, 90).tolist()
    seq = [
        (pref1 + rng.integers(1, 255, 20).tolist(), len(pref1)),
        (pref2 + rng.integers(1, 255, 20).tolist(), len(pref2)),  # evicts pref1
        (pref1 + rng.integers(1, 255, 25).tolist(), len(pref1)),  # restore
        (pref1 + rng.integers(1, 255, 30).tolist(), len(pref1)),  # COW vs restored
    ]

    def run(host_bytes):
        eng = _tiny_engine(
            prefix_cache_size=1, kv_host_bytes=host_bytes
        ).start()
        try:
            outs = [
                eng.submit(
                    t, max_tokens=8, temperature=0.0, prefix_len=pl
                ).result(timeout=300).token_ids
                for t, pl in seq
            ]
            return outs, eng.kv_stats()
        finally:
            eng.stop()

    ref, _ = run(0)
    got, st = run(1 << 26)
    assert got == ref
    assert st["kv_restores"] >= 1
    # the 4th turn hit the RE-REGISTERED restored entry in HBM and cloned
    # its boundary page
    assert st["kv_cow_copies"] >= 1


def test_restore_when_pool_cannot_place_falls_back_cleanly():
    """Host hit whose page demand cannot be allocated: admission falls back
    (request completes as a full prefill or waits for pages) — no wedge, no
    wrong output.  Restore racing eviction, engine edition."""
    rng = np.random.default_rng(23)
    prefix = rng.integers(1, 255, 150).tolist()  # 3 pages of 64
    p_a = prefix + rng.integers(1, 255, 20).tolist()
    p_b = rng.integers(1, 255, 200).tolist()  # unrelated, hogs pages

    def run(host_bytes):
        eng = _tiny_engine(
            max_slots=2, prefix_cache_size=1, kv_pages=6,
            kv_host_bytes=host_bytes,
        ).start()
        try:
            outs = []
            outs.append(
                eng.submit(
                    p_a, max_tokens=8, temperature=0.0, prefix_len=len(prefix)
                ).result(timeout=300).token_ids
            )
            outs.append(
                eng.submit(p_b, max_tokens=8, temperature=0.0)
                .result(timeout=300).token_ids
            )
            outs.append(
                eng.submit(
                    p_a, max_tokens=8, temperature=0.0, prefix_len=len(prefix)
                ).result(timeout=300).token_ids
            )
            return outs
        finally:
            eng.stop()

    assert run(1 << 26) == run(0)


def test_crash_restart_preserves_warm_state_via_host_tier():
    """The durability acceptance shape: tick_raise mid-trace forces a
    crash-only restart (allocator reset, HBM registry gone) — but the host
    tier survives, the next prefix hit RESTORES instead of re-prefilling,
    and every future completes (goodput 1.0)."""
    inj = FaultInjector({})
    eng = _tiny_engine(
        faults=inj, prefix_cache_size=4, kv_host_bytes=1 << 26
    ).start()
    rng = np.random.default_rng(24)
    prefix = rng.integers(1, 255, 100).tolist()
    try:
        eng.submit(
            prefix + rng.integers(1, 255, 20).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        assert eng.kv_stats()["kv_host_entries"] == 1  # write-through
        inj.arm("tick_raise")
        futs = [
            eng.submit(
                prefix + rng.integers(1, 255, 20 + i).tolist(),
                max_tokens=4, temperature=0.0, prefix_len=len(prefix),
            )
            for i in range(3)
        ]
        results = [f.result(timeout=300) for f in futs]
        assert all(len(r.token_ids) == 4 for r in results)  # goodput 1.0
        assert eng.engine_restarts == 1
        st = eng.kv_stats()
        # the restart dropped HBM but not the host tier; post-restart
        # traffic restored (not re-prefilled) the warm prefix
        assert st["kv_host_entries"] >= 1
        assert st["kv_restores"] >= 1
        assert eng.supervision_stats()["healthy"] is True
        if eng.obs is not None:
            evs = [e["event"] for e in eng.obs.flight.events()]
            assert "kv_tier_survives_restart" in evs
            assert "kv_tier" in evs
    finally:
        eng.stop()


def test_scheduler_stats_carry_kv_tier_block():
    """bind_kv_tier (the bind_spec discipline): an engine with a host tier
    and a scheduler surfaces the tier's gauges inside scheduler.stats(), so
    pool pressure and warm-tier depth read side by side."""
    from django_assistant_bot_tpu.serving.scheduler import (
        RequestScheduler,
        SchedulerConfig,
    )

    sched = RequestScheduler(SchedulerConfig())
    eng = _tiny_engine(kv_host_bytes=1 << 26, scheduler=sched)
    st = sched.stats()
    assert "kv_tier" in st and st["kv_tier"]["kv_host_entries"] == 0
    plain = RequestScheduler(SchedulerConfig())
    eng2 = _tiny_engine(kv_host_bytes=0, scheduler=plain)
    assert "kv_tier" not in plain.stats()
    del eng, eng2


# ------------------------------------------------------------- fleet level
def _mk_fleet(n=2, host_bytes=1 << 26, **eng_kw):
    engines = [
        _tiny_engine(
            kv_host_bytes=host_bytes, name=f"r{i}", **eng_kw
        ).start()
        for i in range(n)
    ]
    return EngineRouter(engines, names=[f"r{i}" for i in range(n)])


def test_scale_down_migrates_warm_state_and_registry_repoints():
    router = _mk_fleet()
    rng = np.random.default_rng(31)
    prefix = rng.integers(1, 255, 100).tolist()
    try:
        router.submit(
            prefix + rng.integers(1, 255, 20).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        holders = router.prefix_registry.holders(prefix + [1], len(prefix))
        assert len(holders) == 1
        holder_name, tier = next(iter(holders.items()))
        assert tier == "hbm"
        idx = [rep.name for rep in router.replicas].index(holder_name)
        report = router.remove_replica(idx, deadline_s=10.0)
        assert report["migrated_entries"] == 1
        assert report["lost_pages"] == 0
        rs = router.router_stats()
        assert rs["pages_lost_at_detach"] == 0  # ~0 with migration on
        assert rs["entries_migrated"] == 1
        # the registry re-points at the survivor, at the host tier
        holders = router.prefix_registry.holders(prefix + [1], len(prefix))
        survivor = router.replicas[0].name
        assert holders == {survivor: "host"}
        # and the next hit restores on the survivor
        r = router.submit(
            prefix + rng.integers(1, 255, 30).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        assert len(r.token_ids) == 4
        surv = router.replicas[0].engine
        assert surv.kv_stats()["kv_restores"] >= 1
        assert surv.kv_stats()["kv_migrated_in"] == 1
    finally:
        router.stop()


def test_detach_without_host_tier_counts_lost_pages_and_flight_event():
    """The pre-migration satellite bugfix: a drain-then-detach that discards
    the replica's prefix registry must SAY so — pages_lost_at_detach counter
    + flight event — instead of silently wiping warm state."""
    router = _mk_fleet(host_bytes=0)  # tiering off: nothing to migrate into
    rng = np.random.default_rng(32)
    prefix = rng.integers(1, 255, 100).tolist()
    try:
        router.submit(
            prefix + rng.integers(1, 255, 20).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        holder = next(
            i
            for i, rep in enumerate(router.replicas)
            if rep.engine.kv_stats()["kv_shared_entries"] > 0
        )
        eng = router.replicas[holder].engine
        report = router.remove_replica(holder, deadline_s=10.0)
        assert report["lost_pages"] > 0
        assert report["lost_reason"]
        assert router.router_stats()["pages_lost_at_detach"] == report["lost_pages"]
        if eng.obs is not None:
            evs = [e["event"] for e in eng.obs.flight.events()]
            assert "pages_lost_at_detach" in evs
    finally:
        router.stop()


def test_detach_migrate_off_counts_each_prefix_once():
    """Union accounting: with write-through a warm prefix exists in BOTH the
    device registry and the host tier — a migrate=False detach must charge
    it once, not twice."""
    router = _mk_fleet()
    rng = np.random.default_rng(36)
    prefix = rng.integers(1, 255, 100).tolist()  # 2 pages of 64
    try:
        router.submit(
            prefix + rng.integers(1, 255, 20).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        holder = next(
            i
            for i, rep in enumerate(router.replicas)
            if rep.engine.kv_stats()["kv_shared_entries"] > 0
        )
        report = router.remove_replica(holder, deadline_s=10.0, migrate=False)
        assert report["lost_entries"] == 1
        assert report["lost_pages"] == 2  # NOT 4: hbm + host copies are one prefix
        assert report["lost_reason"] == "migration disabled"
    finally:
        router.stop()


def test_detach_with_dead_device_and_no_writethrough_counts_loss():
    """The silent-wipe shape pages_lost_at_detach exists to expose: with
    write-through OFF and the device unreadable at detach (spill fetch
    raises), the host snapshot comes back empty — the device-registry
    entries must STILL be charged as lost, with the flight event."""
    router = _mk_fleet(kv_host_writethrough=False)
    rng = np.random.default_rng(37)
    prefix = rng.integers(1, 255, 100).tolist()  # 2 pages
    try:
        router.submit(
            prefix + rng.integers(1, 255, 20).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        holder = next(
            i
            for i, rep in enumerate(router.replicas)
            if rep.engine.kv_stats()["kv_shared_entries"] > 0
        )
        eng = router.replicas[holder].engine
        assert eng.kv_stats()["kv_host_entries"] == 0  # writethrough off

        def dead_fetch(pages):
            raise RuntimeError("device unreadable (simulated death)")

        eng._fetch_pages_host = dead_fetch
        eng._kv_pool.bind_spill_fetch(dead_fetch)
        report = router.remove_replica(holder, deadline_s=10.0)
        assert report["migrated_entries"] == 0
        assert report["lost_entries"] == 1 and report["lost_pages"] == 2
        assert router.router_stats()["pages_lost_at_detach"] == 2
        if eng.obs is not None:
            evs = [e["event"] for e in eng.obs.flight.events()]
            assert "pages_lost_at_detach" in evs
    finally:
        router.stop()


def test_scale_down_migrates_disk_tier_entries(tmp_path):
    """A prefix demoted to the victim's DISK tier is warm state too: the
    migration export loads it back (HostKVTier.export_all) and moves it to
    the survivor — it is neither device-resident nor in host DRAM, so the
    host-only snapshot used to wipe it silently with pages_lost_at_detach
    staying 0."""
    router = _mk_fleet(kv_spill_dir=str(tmp_path))
    try:
        tier = router.replicas[1].engine.kv_host_tier
        k, v = _fake_kv(1)  # 1024 B per entry
        tier.put((7,) * 8, 8, k, v)
        # shrink the budget so the next put demotes the LRU entry to disk
        tier.max_bytes = 1024
        k2, v2 = _fake_kv(1, 2.0)
        tier.put((9,) * 8, 8, k2, v2)
        s = tier.stats()
        assert s["kv_disk_entries"] == 1 and s["kv_host_entries"] == 1
        report = router.remove_replica(1, deadline_s=10.0)
        assert report["migrated_entries"] == 2
        assert report["lost_entries"] == 0 and report["lost_pages"] == 0
        assert router.router_stats()["pages_lost_at_detach"] == 0
        # the demoted entry's BYTES made it to the survivor
        hit = router.replicas[0].engine.kv_host_tier.lookup([7] * 10, 8)
        assert hit is not None
        np.testing.assert_array_equal(hit.k, k)
    finally:
        router.stop()


def test_migration_charges_unreadable_disk_rows_lost(tmp_path):
    """A disk row whose file cannot be read back at export time is charged
    to pages_lost_at_detach instead of vanishing from the accounting."""
    router = _mk_fleet(kv_spill_dir=str(tmp_path))
    try:
        tier = router.replicas[1].engine.kv_host_tier
        k, v = _fake_kv(1)
        tier.put((7,) * 8, 8, k, v)
        tier.max_bytes = 1024
        tier.put((9,) * 8, 8, k, v)
        assert tier.stats()["kv_disk_entries"] == 1
        for f in os.listdir(tmp_path):  # corrupt the spill namespace
            os.unlink(os.path.join(tmp_path, f))
        report = router.remove_replica(1, deadline_s=10.0)
        assert report["migrated_entries"] == 1  # the host-DRAM entry
        assert report["lost_entries"] == 1 and report["lost_pages"] == 1
        assert router.router_stats()["pages_lost_at_detach"] == 1
    finally:
        router.stop()


def test_detach_migrate_off_counts_disk_entries(tmp_path):
    """migrate=False loss accounting spans host DRAM AND disk
    (HostKVTier.warm_keys) — a demoted prefix is warm state being
    discarded just the same."""
    router = _mk_fleet(kv_spill_dir=str(tmp_path))
    try:
        tier = router.replicas[1].engine.kv_host_tier
        k, v = _fake_kv(1)
        tier.put((7,) * 8, 8, k, v)
        tier.max_bytes = 1024
        tier.put((9,) * 8, 8, k, v)
        assert tier.stats()["kv_disk_entries"] == 1
        report = router.remove_replica(1, deadline_s=10.0, migrate=False)
        assert report["lost_entries"] == 2  # the host row AND the disk row
        assert report["lost_pages"] == 2
        assert report["lost_reason"] == "migration disabled"
    finally:
        router.stop()


def test_fallback_peek_covers_non_emitting_replica():
    """The per-replica holds_prefix peek must run for every candidate the
    fleet registry has NO answer for — not only when the registry is empty
    fleet-wide.  A non-event-emitting replica's HBM warm state beats an
    event-emitting replica's (worse-tier) registry holding of the same
    session."""
    router = _mk_fleet()
    rng = np.random.default_rng(41)
    prefix = rng.integers(1, 255, 100).tolist()
    try:
        # replica r1 stops emitting tier events (the shape the fallback
        # exists for), then warms the session HBM-directly
        b = router.replicas[1]
        b.engine.set_prefix_listener(None)
        b.engine.submit(
            prefix + rng.integers(1, 255, 20).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        assert b.engine.kv_stats()["kv_shared_entries"] == 1
        assert router.prefix_registry.holders(prefix + [1], len(prefix)) == {}
        # the registry knows only a (faked) host-tier holding on r0
        router.prefix_registry.on_event(
            "r0", "host_put", tuple(prefix), len(prefix)
        )
        r = router.submit(
            prefix + rng.integers(1, 255, 30).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        assert len(r.token_ids) == 4
        # the peeked HBM holder won over the registry's host-tier holder
        assert b.engine.kv_stats()["prefix_hits"] == 1
    finally:
        router.stop()


def test_fleet_registry_holders_aggregate_across_prefix_lengths():
    """A replica warm with a SHORTER prefix of the same session must keep
    its affinity preference even when another replica holds a longer one
    (the longest holder may be draining/unhealthy at dispatch time)."""
    from django_assistant_bot_tpu.serving.router import FleetPrefixRegistry

    reg = FleetPrefixRegistry()
    reg.on_event("r0", "register", (1, 2, 3), 3)
    reg.on_event("r1", "host_put", (1, 2, 3, 4, 5), 5)
    holders = reg.holders([1, 2, 3, 4, 5, 6, 7], 5)
    assert holders == {"r0": "hbm", "r1": "host"}


def test_host_tier_sweeps_its_stale_namespace_at_boot(tmp_path):
    """The disk index is in-memory: a previous process's files under THIS
    tier's namespace are unreachable and must be swept at construction —
    without touching other replicas' namespaces in a shared dir."""
    k, v = _fake_kv(1)
    a = HostKVTier(1100, page_size=16, spill_dir=str(tmp_path), name="repA")
    a.put((1,) * 8, 8, k, v)
    a.put((2,) * 8, 8, k, v)  # demotes (1,) to disk
    b = HostKVTier(1100, page_size=16, spill_dir=str(tmp_path), name="repB")
    b.put((3,) * 8, 8, k, v)
    b.put((4,) * 8, 8, k, v)
    files = sorted(os.listdir(tmp_path))
    assert any("repA" in f for f in files) and any("repB" in f for f in files)
    # a restarted repA process sweeps repA's orphan, leaves repB's file
    a2 = HostKVTier(1100, page_size=16, spill_dir=str(tmp_path), name="repA")
    files = sorted(os.listdir(tmp_path))
    assert not any("repA" in f for f in files)
    assert any("repB" in f for f in files)
    assert b.lookup([3] * 12, 8) is not None  # repB's disk entry still live
    del a2


def test_sweep_spares_live_sibling_process_files(tmp_path):
    """Spill filenames carry the writing pid: a boot sweep reclaims only
    files whose process is GONE (or recycled as ours), so two live serve
    processes sharing one DABT_KV_SPILL_DIR — even with the same replica
    name — cannot delete each other's warm state.  Pidless old-format
    files are always stale."""
    digest = "0" * 24
    live = f"kvspill-repA-p1-{digest}.npz"  # pid 1 is always alive
    dead_pid = next(
        p for p in range(400000, 500000) if not HostKVTier._pid_alive(p)
    )
    dead = f"kvspill-repA-p{dead_pid}-{digest}.npz"
    old = f"kvspill-repA-{digest}.npz"  # pre-pid format
    for f in (live, dead, old):
        with open(os.path.join(tmp_path, f), "wb") as fh:
            fh.write(b"x")
    HostKVTier(1100, page_size=16, spill_dir=str(tmp_path), name="repA")
    files = os.listdir(tmp_path)
    assert live in files
    assert dead not in files
    assert old not in files


def test_promote_racing_redemote_never_dangles_disk_index(tmp_path):
    """While a lookup holds a disk row reserved (file read outside the
    lock), a concurrent put-then-demote can re-write the SAME key's file at
    the same deterministic path and re-index it — the promote's cleanup
    must absorb that row instead of deleting a file the index points at."""
    host = HostKVTier(1100, page_size=16, spill_dir=str(tmp_path), name="r")
    k, v = _fake_kv(1)
    host.put((1,) * 8, 8, k, v)
    host.put((2,) * 8, 8, k, v)  # (1,) demoted to disk
    assert host.stats()["kv_disk_entries"] == 1
    orig = host._load_disk_file

    def racing_load(path, key, *a):
        ent = orig(path, key, *a)
        host._load_disk_file = orig  # the nested puts must not re-enter
        # the "concurrent" thread, deterministically: (1,) back into host
        # DRAM, then budget pressure demotes it straight back to disk at
        # the path the reserved promote is about to delete
        host.put((1,) * 8, 8, k, v)
        host.put((3,) * 8, 8, k, v)
        assert (1,) * 8 in host._disk
        return ent

    host._load_disk_file = racing_load
    hit = host.lookup([1] * 12, 8)
    assert hit is not None and hit.length == 8
    # no disk row may reference a deleted file
    for path, _ln, _nb, _pg in host._disk.values():
        assert os.path.exists(path), path
    # and every remaining disk entry still promotes cleanly
    assert host.lookup([3] * 12, 8) is not None or (3,) * 8 not in host._disk


def test_migration_survives_replica_dying_mid_drain():
    """THE race: the scale-down victim dies under the drain.  The warm-state
    export is a host-memory snapshot (numpy, not device state), so migration
    still lands on the survivor and the scale-down completes."""
    router = _mk_fleet()
    rng = np.random.default_rng(33)
    prefix = rng.integers(1, 255, 100).tolist()
    try:
        router.submit(
            prefix + rng.integers(1, 255, 20).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        holders = router.prefix_registry.holders(prefix + [1], len(prefix))
        holder_name = next(iter(holders))
        idx = [rep.name for rep in router.replicas].index(holder_name)
        # kill it the hard way, then scale it down: the drain sees a dead
        # engine (reads idle), the migration exports host numpy anyway
        router.kill_replica(idx)
        deadline = time.monotonic() + 10
        while router.replicas[idx].engine._thread.is_alive():
            assert time.monotonic() < deadline
            time.sleep(0.01)
        report = router.remove_replica(idx, deadline_s=5.0)
        assert report["died_mid_drain"] is True
        assert report["migrated_entries"] == 1
        assert report["lost_pages"] == 0
        survivor = router.replicas[0].engine
        assert survivor.kv_stats()["kv_migrated_in"] == 1
        # fleet keeps serving the warm prefix via restore
        r = router.submit(
            prefix + rng.integers(1, 255, 30).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(prefix),
        ).result(timeout=300)
        assert len(r.token_ids) == 4
        assert survivor.kv_stats()["kv_restores"] >= 1
    finally:
        router.stop()


def _stall(engine, delay_s=0.1, fires=16):
    """Arm slow_tick so the engine's loop holds work in flight token-less
    (the test_router discipline)."""
    inj = engine._faults
    inj.arm("slow_tick", fires)
    with inj._lock:
        inj._sites["slow_tick"].delay_s = delay_s


def test_restore_racing_replica_kill_reroutes_tokenless():
    """Chaos: a request whose prefix is HOST-tier-only on one replica is
    routed there (warm affinity) and the replica is killed inside the
    restore/admission window, before any client token.  The token-less
    re-route lands it on the survivor — goodput 1.0; the dead replica's
    restore is lost state, not a lost request."""
    engines = [
        _tiny_engine(
            kv_host_bytes=1 << 26, prefix_cache_size=1, name=f"r{i}",
            faults=FaultInjector({}),
        ).start()
        for i in range(2)
    ]
    router = EngineRouter(engines, names=["r0", "r1"], breaker_reset_s=0.2)
    rng = np.random.default_rng(34)
    pref1 = rng.integers(1, 255, 100).tolist()
    pref2 = rng.integers(1, 255, 100).tolist()
    try:
        router.replicas[1].draining = True  # pin warmup onto r0
        for pf in (pref1, pref2):  # pref2 evicts pref1 to r0's host tier
            router.submit(
                pf + rng.integers(1, 255, 20).tolist(),
                max_tokens=2, temperature=0.0, prefix_len=len(pf),
            ).result(timeout=300)
        router.replicas[1].draining = False
        assert router.prefix_registry.holders(pref1 + [1], len(pref1)) == {
            "r0": "host"
        }
        _stall(engines[0])
        _stall(engines[1])
        fut = router.submit(
            pref1 + rng.integers(1, 255, 30).tolist(),
            max_tokens=4, temperature=0.0, prefix_len=len(pref1),
        )
        time.sleep(0.05)  # inside the stalled window: no host tokens yet
        router.kill_replica(0)
        r = fut.result(timeout=300)
        assert len(r.token_ids) == 4  # goodput 1.0
        assert router.router_stats()["reroutes"] >= 1
        assert router.rerouted_failed == 0
    finally:
        router.stop()


def test_disk_tier_restore_through_engine(tmp_path):
    """A host budget of ~one entry + a spill dir: warming a second prefix
    demotes the first to disk; hitting it again promotes + restores, and
    the output matches the tiering-off reference."""
    rng = np.random.default_rng(35)
    pref1 = rng.integers(1, 255, 100).tolist()
    pref2 = rng.integers(1, 255, 100).tolist()
    seq = [
        (pref1 + rng.integers(1, 255, 20).tolist(), len(pref1)),
        (pref2 + rng.integers(1, 255, 20).tolist(), len(pref2)),
        (pref1 + rng.integers(1, 255, 25).tolist(), len(pref1)),
    ]

    def run(**kw):
        eng = _tiny_engine(prefix_cache_size=1, **kw).start()
        try:
            outs = [
                eng.submit(
                    t, max_tokens=8, temperature=0.0, prefix_len=pl
                ).result(timeout=300).token_ids
                for t, pl in seq
            ]
            return outs, eng.kv_stats()
        finally:
            eng.stop()

    ref, _ = run()
    # a 100-token prefix spans 2 pages, so one entry is 2 * page_bytes; a
    # 3-page budget holds exactly one entry and the second warm prefix
    # demotes the first to disk
    probe = _tiny_engine(kv_host_bytes=1 << 26)
    page_bytes = probe._kv_host.page_bytes
    del probe
    got, st = run(kv_host_bytes=3 * page_bytes, kv_spill_dir=str(tmp_path))
    assert got == ref
    assert st["kv_disk_spills"] >= 1
    assert st["kv_disk_promotes"] >= 1
    assert st["kv_restores"] >= 1
    assert any(f.startswith("kvspill-") for f in os.listdir(tmp_path))


def test_env_gate_dabt_kv_spill_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("DABT_KV_SPILL_DIR", str(tmp_path))
    eng = _tiny_engine()
    assert eng.kv_host_tier is not None
    assert eng.kv_host_tier.spill_dir == str(tmp_path)
    monkeypatch.delenv("DABT_KV_SPILL_DIR")
    eng2 = _tiny_engine()
    assert eng2.kv_host_tier is None
    del eng, eng2
