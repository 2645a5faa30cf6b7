"""Cross-process fleet plane (serving/fleet.py; docs/FLEET.md).

Evidence layers, all CPU:

- wire codec property tests: fp8/bf16/int8/f32 page snapshots encode→decode
  BIT-identical (including the boundary partial tail page) under the pinned
  DABT_KV_FUZZ_SEED; malformed and cross-build payloads fail loudly;
- the versioned-snapshot contract: HostKVTier.absorb refuses entries
  stamped by a different build (all-or-nothing), the disk tier refuses
  tampered/foreign .npz files;
- FleetRouter policy under stub peers (no sockets): precedence, token-less
  re-route + breaker feed, shed aggregation, the pool-role force retry,
  gossip application (delta + reset), prefix pull, the two-stage
  disaggregated handoff;
- live two-peer integration over REAL aiohttp servers (each hosted on its
  own thread's event loop): KV pages shipped over the wire land bit-exact
  on the receiver, a decode-pool peer serves a session whose prefill ran in
  the prefill pool with output identical to the unified arm, peer death
  re-routes token-lessly and degrades /fleet/healthz, and the dabt_fleet_*
  exposition parses;
- a @slow two-SUBPROCESS smoke (the CI step): boot two `serve --tiny`
  processes, route a dialog, kill one, assert re-route + fleet-degraded;
- fleet-wire hardening: CRC-32C integrity (truncation at every envelope
  boundary, flipped-byte rejection, v1<->v2 cross-version compat, disk
  tamper), PeerClient failure phases + injected net_* chaos, partition
  tolerance (TTL aging, digest-forced reconcile, refresh-failure reasons),
  the idempotency ledger, and live "netchaos" tests (CI's -k netchaos
  smoke): corrupt-put rejection, dedup, drop-retry, partition re-route,
  and the pull-miss -> cold-prefill fallthrough.
"""

import asyncio
import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from django_assistant_bot_tpu.serving.engine import EngineUnavailable
from django_assistant_bot_tpu.serving.faults import FaultInjector
from django_assistant_bot_tpu.serving.fleet import (
    FleetPeer,
    FleetPlane,
    FleetRouter,
    PeerHTTPError,
    PeerUnreachable,
    _chain_digest,
    _flip_one_byte,
    decode_kv_entry,
    encode_kv_entry,
)
from django_assistant_bot_tpu.serving.kv_pool import (
    KV_WIRE_VERSION,
    TIER_HOST,
    HostKVTier,
    HostPrefixEntry,
    WireDecodeError,
    WireIntegrityError,
    WireVersionError,
    entry_crc32c,
)
from django_assistant_bot_tpu.serving.scheduler import SchedulerRejected

FUZZ_SEED = int(os.environ.get("DABT_KV_FUZZ_SEED", "0"))


# ---------------------------------------------------------------- wire codec
def _entry(dtype, *, length=37, page=16, layers=2, kh=1, d=4, seed=FUZZ_SEED):
    """A HostPrefixEntry with random page contents in `dtype`.  length=37
    with page=16 exercises the boundary shape: two full pages plus a
    partial COW tail page."""
    rng = np.random.default_rng(seed)
    n_pages = -(-length // page)
    shape = (layers, n_pages, kh, page, d)
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    # draw raw bytes, then view as dtype: every bit pattern (NaNs, denormals,
    # fp8 codes) must survive the wire — value-space draws would miss them
    k = rng.integers(0, 256, nbytes, np.uint8).view(dtype).reshape(shape)
    v = rng.integers(0, 256, nbytes, np.uint8).view(dtype).reshape(shape)
    key = tuple(int(t) for t in rng.integers(1, 255, length))
    return HostPrefixEntry(
        key=key, length=length, k=k, v=v, nbytes=2 * nbytes, pages=n_pages
    )


def _wire_dtypes():
    import ml_dtypes

    return [
        np.float32,
        np.int8,
        np.dtype(ml_dtypes.bfloat16),
        np.dtype(ml_dtypes.float8_e4m3fn),
        np.dtype(ml_dtypes.float8_e5m2),
    ]


@pytest.mark.parametrize("dtype", _wire_dtypes(), ids=str)
def test_wire_roundtrip_bit_identical(dtype):
    ent = _entry(dtype)
    out = decode_kv_entry(encode_kv_entry(ent))
    assert out.key == ent.key and out.length == ent.length
    assert out.k.dtype == np.dtype(dtype) and out.v.dtype == np.dtype(dtype)
    assert out.k.shape == ent.k.shape and out.v.shape == ent.v.shape
    # BIT identity, not value identity: NaN payloads and fp8 codes included
    assert out.k.tobytes() == ent.k.tobytes()
    assert out.v.tobytes() == ent.v.tobytes()


def test_wire_roundtrip_fuzz_shapes():
    """Pinned-seed shape fuzz: page-aligned, single-page, and ragged-tail
    entries all round-trip bit-exactly."""
    rng = np.random.default_rng(1000 + FUZZ_SEED)
    for _ in range(10):
        length = int(rng.integers(1, 80))
        page = int(rng.choice([8, 16, 32]))
        ent = _entry(
            np.float32, length=length, page=page, seed=int(rng.integers(1 << 31))
        )
        out = decode_kv_entry(encode_kv_entry(ent))
        assert out.key == ent.key
        assert out.k.tobytes() == ent.k.tobytes()
        assert out.v.tobytes() == ent.v.tobytes()


def test_wire_rejects_malformed():
    ent = _entry(np.float32)
    data = encode_kv_entry(ent)
    with pytest.raises(ValueError):
        decode_kv_entry(b"NOTKV!" + data[6:])  # bad magic
    with pytest.raises(ValueError):
        decode_kv_entry(data[:-8])  # truncated body
    with pytest.raises(ValueError):
        decode_kv_entry(data[: len(data) // 4])  # truncated header/body


def test_wire_rejects_cross_build_version():
    ent = _entry(np.float32)
    data = bytearray(encode_kv_entry(ent))
    hlen = int.from_bytes(data[6:10], "little")
    header = json.loads(bytes(data[10 : 10 + hlen]).decode())
    header["wire_version"] = KV_WIRE_VERSION + 1
    hb = json.dumps(header, separators=(",", ":")).encode()
    tampered = data[:6] + len(hb).to_bytes(4, "little") + hb + data[10 + hlen :]
    with pytest.raises(WireVersionError):
        decode_kv_entry(bytes(tampered))


def test_absorb_rejects_unknown_wire_version_all_or_nothing():
    """A snapshot carrying even ONE cross-build entry must absorb NOTHING —
    failing loudly beats corrupting pages (the satellite contract)."""
    tier = HostKVTier(1 << 20, page_size=16)
    good = _entry(np.float32, length=16)
    bad = _entry(np.float32, length=32, seed=FUZZ_SEED + 1)
    bad.wire_version = KV_WIRE_VERSION + 1
    with pytest.raises(WireVersionError):
        tier.absorb([good, bad])
    assert tier.stats()["kv_host_entries"] == 0


def test_disk_file_rejects_cross_build_version(tmp_path):
    """A .npz written by a different build (tampered wire_version) loads as
    a MISS, never as reinterpreted pages."""
    tier = HostKVTier(
        1536, page_size=16, spill_dir=str(tmp_path), name="wire-test"
    )
    ent = _entry(np.float32, length=16, page=16)  # 1 page, 2*512B = 1024B
    assert tier.put(ent.key, ent.length, ent.k, ent.v)
    # a second entry evicts the first to disk (budget fits one)
    ent2 = _entry(np.float32, length=16, page=16, seed=FUZZ_SEED + 2)
    assert tier.put(ent2.key, ent2.length, ent2.k, ent2.v)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert files, "expected a disk demotion"
    path = tmp_path / files[0]
    with np.load(path, allow_pickle=False) as z:
        blob = {name: z[name] for name in z.files}
    assert int(blob["wire_version"]) == KV_WIRE_VERSION
    blob["wire_version"] = np.asarray(KV_WIRE_VERSION + 1, np.int64)
    np.savez(path, **blob)
    # the demoted key must now MISS (and not crash): lookup promotes from
    # disk only after the version gate passes
    assert tier.lookup(list(ent.key) + [9], ent.length) is None


# ------------------------------------------- wire integrity (CRC) + versions
def _tamper_header(data: bytes, mutate) -> bytes:
    """Re-encode a wire payload with its JSON header passed through
    ``mutate`` (header-length field rewritten to match)."""
    hlen = int.from_bytes(data[6:10], "little")
    header = json.loads(bytes(data[10 : 10 + hlen]).decode())
    mutate(header)
    hb = json.dumps(header, separators=(",", ":")).encode()
    return data[:6] + len(hb).to_bytes(4, "little") + hb + data[10 + hlen :]


def test_wire_truncation_every_envelope_boundary():
    """Truncation at EVERY envelope boundary raises a clean WireDecodeError
    (a ValueError subclass — pre-CRC callers keep catching it), never an
    IndexError/struct garbage or a silently short array."""
    ent = _entry(np.float32)
    data = encode_kv_entry(ent)
    hlen = int.from_bytes(data[6:10], "little")
    k_nbytes = int(np.ascontiguousarray(ent.k).nbytes)
    cuts = [
        0,  # empty payload
        3,  # mid-magic
        6,  # magic only, header-length field missing
        8,  # mid header-length field
        10 + hlen // 2,  # mid-header JSON
        10 + hlen,  # header complete, body missing entirely
        10 + hlen + k_nbytes // 2,  # mid-K pages
        len(data) - 5,  # mid-V pages
    ]
    for cut in cuts:
        with pytest.raises(WireDecodeError):
            decode_kv_entry(data[:cut])
        with pytest.raises(ValueError):  # the hierarchy contract
            decode_kv_entry(data[:cut])


def test_wire_crc_rejects_flipped_body_byte():
    """A single flipped bit anywhere in the k/v body fails the CRC-32C and
    raises WireIntegrityError BEFORE any bytes become pages."""
    ent = _entry(np.float32)
    data = encode_kv_entry(ent)
    hlen = int.from_bytes(data[6:10], "little")
    for idx in (10 + hlen + 3, len(data) - 3):  # one in K, one in V
        bad = bytearray(data)
        bad[idx] ^= 0x01
        with pytest.raises(WireIntegrityError, match="CRC-32C"):
            decode_kv_entry(bytes(bad))
    # the injector's own mutation is exactly this failure class
    corrupted = (
        data[: 10 + hlen] + _flip_one_byte(data[10 + hlen :])
    )
    with pytest.raises(WireIntegrityError):
        decode_kv_entry(corrupted)
    # flip-of-flip restores the payload bit-exactly
    assert _flip_one_byte(_flip_one_byte(data)) == data
    assert decode_kv_entry(data).k.tobytes() == ent.k.tobytes()


def test_wire_v1_payload_accepted_by_new_decoder():
    """Cross-version compat, old->new: a v1 payload (no checksum) still
    decodes bit-identically — and, documenting the compat window's tradeoff,
    v1 corruption is NOT detectable."""
    ent = _entry(np.float32)
    v1 = _tamper_header(
        encode_kv_entry(ent),
        lambda h: (h.pop("crc32c"), h.update(wire_version=1)),
    )
    out = decode_kv_entry(v1)
    assert out.wire_version == 1 and out.crc32c is None
    assert out.k.tobytes() == ent.k.tobytes()
    assert out.v.tobytes() == ent.v.tobytes()
    # no checksum -> a flipped v1 body byte decodes silently (why v2 exists)
    hlen = int.from_bytes(v1[6:10], "little")
    flipped = v1[: 10 + hlen] + _flip_one_byte(v1[10 + hlen :])
    assert decode_kv_entry(flipped).key == ent.key


def test_wire_v2_payload_rejected_by_old_decoder(monkeypatch):
    """Cross-version compat, new->old: a decoder whose accept-set predates
    v2 refuses the CRC-stamped payload loudly (WireVersionError), never
    guesses at the header it half-understands."""
    import django_assistant_bot_tpu.serving.fleet as fleet_mod

    data = encode_kv_entry(_entry(np.float32))
    monkeypatch.setattr(fleet_mod, "WIRE_ACCEPT_VERSIONS", (1,))
    with pytest.raises(WireVersionError):
        decode_kv_entry(data)


def test_wire_v2_missing_crc_rejected():
    """A v2 header without its crc32c field is malformed, not 'optional
    integrity': WireDecodeError (a tampered header must not bypass the
    checksum by deleting it)."""
    data = _tamper_header(
        encode_kv_entry(_entry(np.float32)), lambda h: h.pop("crc32c")
    )
    with pytest.raises(WireDecodeError):
        decode_kv_entry(data)


def test_absorb_rejects_crc_mismatch_all_or_nothing():
    """A snapshot with one CRC-failed entry absorbs NOTHING, and the reject
    is counted where the bench reads it (kv_integrity_rejects)."""
    tier = HostKVTier(1 << 20, page_size=16)
    good = _entry(np.float32, length=16)
    bad = _entry(np.float32, length=32, seed=FUZZ_SEED + 1)
    bad.crc32c = entry_crc32c(bad.k, bad.v) ^ 1
    with pytest.raises(WireIntegrityError):
        tier.absorb([good, bad])
    assert tier.stats()["kv_host_entries"] == 0
    assert tier.stats()["kv_integrity_rejects"] == 1
    # honest entries (checksum intact, or none attached) absorb fine
    bad.crc32c = entry_crc32c(bad.k, bad.v)
    tier.absorb([good, bad])
    assert tier.stats()["kv_host_entries"] == 2


def _demote_one_to_disk(tmp_path, tier_name):
    """A tier sized for one entry, with a second put demoting the first to
    disk; returns (tier, demoted_entry, npz_path)."""
    tier = HostKVTier(
        1536, page_size=16, spill_dir=str(tmp_path), name=tier_name
    )
    ent = _entry(np.float32, length=16, page=16)
    assert tier.put(ent.key, ent.length, ent.k, ent.v)
    ent2 = _entry(np.float32, length=16, page=16, seed=FUZZ_SEED + 2)
    assert tier.put(ent2.key, ent2.length, ent2.k, ent2.v)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert files, "expected a disk demotion"
    return tier, ent, tmp_path / files[0]


def test_disk_file_rejects_tampered_crc(tmp_path):
    """At-rest corruption: a .npz whose stored CRC no longer matches its
    bytes loads as a MISS, counted in kv_integrity_rejects."""
    tier, ent, path = _demote_one_to_disk(tmp_path, "crc-tamper")
    with np.load(path, allow_pickle=False) as z:
        blob = {name: z[name] for name in z.files}
    assert int(blob["crc32c"]) == entry_crc32c(ent.k, ent.v)
    blob["crc32c"] = np.asarray(int(blob["crc32c"]) ^ 1, np.int64)
    np.savez(path, **blob)
    assert tier.lookup(list(ent.key) + [9], ent.length) is None
    assert tier.stats()["kv_integrity_rejects"] == 1


def test_disk_file_pre_crc_layout_still_loads(tmp_path):
    """A spill file from the pre-CRC layout (no crc32c member) promotes as
    before — the integrity gate is additive, not a flag-day break."""
    tier, ent, path = _demote_one_to_disk(tmp_path, "crc-legacy")
    with np.load(path, allow_pickle=False) as z:
        blob = {name: z[name] for name in z.files}
    del blob["crc32c"]
    np.savez(path, **blob)
    got = tier.lookup(list(ent.key) + [9], ent.length)
    assert got is not None
    assert np.asarray(got.k).tobytes() == ent.k.tobytes()
    assert tier.stats()["kv_integrity_rejects"] == 0


# ------------------------------------------------------- stub-peer policy
class _StubClient:
    """In-memory PeerClient: per-path handlers, call log, no sockets."""

    def __init__(self):
        self.calls = []
        self.generate = lambda body: {
            "token_ids": [1, 2],
            "result": "ok",
            "usage": {"prompt_tokens": 3, "completion_tokens": 2},
            "length_limited": False,
        }
        self.healthz = lambda: {
            "status": "ok",
            "load": {"queued": 0, "active": 0},
            "fleet": {"pool": "unified", "seq": 0},
        }
        self.prefix = lambda since: {"seq": 0, "events": []}
        self.kv_get = lambda body: None
        self.kv_put = lambda data: {"stored": True, "pages": 0}

    def get_json(self, path, timeout_s=None):
        self.calls.append(("GET", path))
        if path.startswith("/fleet/healthz"):
            return self.healthz()
        if path.startswith("/fleet/prefix"):
            return self.prefix(int(path.rsplit("=", 1)[1]))
        raise AssertionError(path)

    def post_json(self, path, body, timeout_s=None):
        self.calls.append(("POST", path, body))
        if path == "/fleet/generate":
            return self.generate(body)
        raise AssertionError(path)

    def post_for_bytes(self, path, body, timeout_s=None):
        self.calls.append(("POST", path, body))
        if path == "/fleet/kv/get":
            return self.kv_get(body)
        raise AssertionError(path)

    def post_bytes(self, path, data, timeout_s=None):
        self.calls.append(("POST-BYTES", path))
        if path.startswith("/fleet/kv/put"):
            return self.kv_put(data)
        raise AssertionError(path)


def _mk_router(n=2, pools=None, **kw):
    peers = [
        FleetPeer(
            f"p{i}",
            f"http://stub{i}",
            client=_StubClient(),
            pool=(pools[i] if pools else "unified"),
        )
        for i in range(n)
    ]
    kw.setdefault("refresh_interval_s", 1e9)  # tests drive refresh() directly
    kw.setdefault("breaker_reset_s", 1e9)
    router = FleetRouter(peers, model="tiny-chat", **kw)
    router._last_refresh = router._clock()  # suppress the lazy first refresh
    return router, peers


def test_fleet_router_dispatch_and_contract():
    router, peers = _mk_router()
    fut = router.submit([1, 2, 3], max_tokens=4, temperature=0.0)
    res = fut.result(timeout=10)
    assert res.token_ids == [1, 2] and res.text == "ok"
    assert res.peer in ("p0", "p1") and res.reroutes == 0
    assert res.trace_id
    body = next(
        c[2] for p in peers for c in p.client.calls if c[0] == "POST"
    )
    assert body["model"] == "tiny-chat" and body["trace_id"] == res.trace_id
    with pytest.raises(ValueError):
        router.submit([1, 2], stream=object())
    router.close()


def test_fleet_router_reroutes_token_less_on_peer_death():
    router, peers = _mk_router()
    peers[1].queued = 100  # p0 is least-loaded -> chosen first

    def _dead(body):
        raise PeerUnreachable("connection refused")

    peers[0].client.generate = _dead
    res = router.submit([1, 2, 3]).result(timeout=10)
    assert res.peer == "p1" and res.reroutes == 1
    assert router.reroutes == 1
    assert not peers[0].healthy
    # breaker fed: repeated failures open it so dispatch skips the corpse
    for _ in range(3):
        peers[0].breaker.record_failure()
    assert not peers[0].breaker.allow()
    router.close()


def test_fleet_router_exhausted_reroutes_raises():
    router, peers = _mk_router(n=2, max_reroutes=1)
    for p in peers:
        p.client.generate = lambda body: (_ for _ in ()).throw(
            PeerUnreachable("dead")
        )
    with pytest.raises(EngineUnavailable):
        router.submit([1, 2, 3]).result(timeout=10)
    assert router.rerouted_failed == 1
    router.close()


def test_fleet_router_shed_aggregation():
    router, peers = _mk_router()
    for i, p in enumerate(peers):
        p.client.generate = lambda body, _i=i: (_ for _ in ()).throw(
            PeerHTTPError(
                429, "queue full", retry_after_s=2.0 + _i, reason="queue_full"
            )
        )
    with pytest.raises(SchedulerRejected) as ei:
        router.submit([1, 2, 3]).result(timeout=10)
    # the hint is the MINIMUM across sheds: retry when the first peer might
    assert ei.value.retry_after_s == 2.0
    assert router.sheds == 1
    router.close()


def test_fleet_router_pool_role_force_retry():
    """When every reject is pool_role, availability beats role purity: one
    force retry, counted."""
    router, peers = _mk_router(pools=("decode", "decode"))

    def _guarded(body):
        if body.get("force"):
            return {
                "token_ids": [7],
                "result": "forced",
                "usage": {"prompt_tokens": 3, "completion_tokens": 1},
                "length_limited": False,
            }
        raise PeerHTTPError(
            429, "pool role", retry_after_s=1.0, reason="pool_role"
        )

    for p in peers:
        p.client.generate = _guarded
    res = router.submit([1, 2, 3]).result(timeout=10)
    assert res.token_ids == [7]
    assert router.pool_role_bypasses == 1
    router.close()


def test_fleet_router_gossip_affinity_and_reset():
    router, peers = _mk_router()
    key = tuple(range(1, 9))
    peers[1].client.prefix = lambda since: {
        "seq": 3,
        "events": [
            {
                "model": "tiny-chat",
                "replica": "tiny-chat/r0",
                "event": "host_put",
                "key": list(key),
                "length": len(key),
            },
            # other models' gossip must not leak into this router's registry
            {
                "model": "other",
                "replica": "other/r0",
                "event": "host_put",
                "key": [9, 9],
                "length": 2,
            },
        ],
    }
    router.refresh()
    assert peers[1].prefix_seq == 3
    holders = router._peer_holders(list(key) + [99], len(key))
    assert set(holders) == {"p1"}
    # affinity: p1 wins dispatch for the warm session despite equal load
    res = router.submit(list(key) + [50, 51], prefix_len=len(key)).result(10)
    assert res.peer == "p1"
    assert router.affinity_hits == 1
    # reset: the peer's log was trimmed/restarted -> drop and re-apply
    peers[1].client.prefix = lambda since: {
        "seq": 10,
        "reset": True,
        "holdings": [],
    }
    router.refresh()
    assert router._peer_holders(list(key) + [99], len(key)) == {}
    router.close()


def test_fleet_router_prefix_pull():
    router, peers = _mk_router()
    key = tuple(range(1, 9))
    ent = _entry(np.float32, length=len(key))
    ent = HostPrefixEntry(
        key=key, length=len(key), k=ent.k, v=ent.v, nbytes=ent.nbytes, pages=1
    )
    peers[1].client.prefix = lambda since: {
        "seq": 1,
        "events": [
            {
                "model": "tiny-chat",
                "replica": "tiny-chat/r0",
                "event": "host_put",
                "key": list(key),
                "length": len(key),
            }
        ],
    }
    router.refresh()
    # the holder sheds, so dispatch falls to p0 — which pulls the prefix
    # from p1 before the request lands
    peers[1].client.generate = lambda body: (_ for _ in ()).throw(
        PeerHTTPError(429, "busy", retry_after_s=1.0, reason="queue_full")
    )
    peers[1].client.kv_get = lambda body: encode_kv_entry(ent)
    peers[0].client.kv_put = lambda data: {"stored": True, "pages": 1}
    res = router.submit(list(key) + [50, 51], prefix_len=len(key)).result(10)
    assert res.peer == "p0"
    assert router.prefix_pulls == 1 and router.pages_shipped == 1
    assert any(
        c[1].startswith("/fleet/kv/put") for c in peers[0].client.calls
    )
    router.close()


def test_fleet_router_disagg_handoff_two_stage():
    router, peers = _mk_router(pools=("prefill", "decode"))
    prompt = list(range(1, 101))  # suffix 100 >= handoff threshold 64
    seen = {}

    def _prefill(body):
        seen["prefill"] = body
        assert body["prefill_only"] and body["max_tokens"] == 1
        assert body["priority"] == "background"
        assert body["push_to"] == peers[1].base_url
        return {
            "token_ids": [5],
            "result": "",
            "usage": {"prompt_tokens": 100, "completion_tokens": 1},
            "length_limited": False,
            "handoff": {"pushed": True, "pages": 7, "key_tokens": 99},
        }

    def _decode(body):
        seen["decode"] = body
        assert body["prefix_len"] == 99 and not body.get("prefill_only")
        return {
            "token_ids": [5, 6, 7],
            "result": "xyz",
            "usage": {"prompt_tokens": 100, "completion_tokens": 3},
            "length_limited": False,
        }

    peers[0].client.generate = _prefill
    peers[1].client.generate = _decode
    res = router.submit(prompt, max_tokens=3, temperature=0.0).result(10)
    assert res.peer == "p1" and res.token_ids == [5, 6, 7]
    assert router.handoffs == 1 and router.pages_shipped == 7
    assert "prefill" in seen and "decode" in seen
    router.close()


# ------------------------------------------------------ plane policy units
class _StubEngine:
    replicas = None
    num_active = 0

    def __init__(self, warm=False):
        self._warm = warm

    def queued_depth(self):
        return 0

    def holds_prefix(self, prompt_ids, prefix_len):
        return self._warm


class _StubRegistry:
    def __init__(self):
        self.generators = {}
        self.embedders = {}
        self.specs = {}

    def get_generator(self, model):
        return self.generators.get(model)


def test_plane_admission_guard_roles():
    reg = _StubRegistry()
    cold = _StubEngine(warm=False)
    reg.generators["m"] = cold
    plane = FleetPlane(reg, pool="prefill", decode_max_prefill_tokens=8)
    ids = list(range(40))
    rej = plane.admission_guard(
        "m", cold, ids, 0, prefill_only=False, force=False
    )
    assert rej is not None and rej.reason == "pool_role"
    assert (
        plane.admission_guard("m", cold, ids, 0, prefill_only=True, force=False)
        is None
    )
    plane.pool = "decode"
    # long cold suffix: shed
    assert (
        plane.admission_guard("m", cold, ids, 0, prefill_only=False, force=False)
        is not None
    )
    # prefill_only never runs in the decode pool
    assert (
        plane.admission_guard("m", cold, ids, 0, prefill_only=True, force=False)
        is not None
    )
    # warm prefix covering all but a short suffix: admitted via restore
    warm = _StubEngine(warm=True)
    assert (
        plane.admission_guard(
            "m", warm, ids, len(ids) - 4, prefill_only=False, force=False
        )
        is None
    )
    # force bypasses (counted): availability beats purity
    assert (
        plane.admission_guard("m", cold, ids, 0, prefill_only=False, force=True)
        is None
    )
    assert plane.pool_bypasses == 1 and plane.pool_rejects >= 3


def test_plane_gossip_log_delta_and_reset():
    plane = FleetPlane(_StubRegistry(), pool="unified", log_size=16)
    for i in range(3):
        plane.on_tier_event("m", "m/r0", "host_put", (1, 2, i), 3)
    out = plane.prefix_events(0)
    assert out["seq"] == 3 and len(out["events"]) == 3
    assert plane.prefix_events(2)["events"][0]["key"] == [1, 2, 2]
    assert plane.prefix_events(3)["events"] == []
    # overflow the bounded log: an ancient cursor gets a reset snapshot
    for i in range(40):
        plane.on_tier_event("m", "m/r0", "host_put", (9, i), 2)
    out = plane.prefix_events(1)
    assert out.get("reset") and out["seq"] == 43
    assert "holdings" in out


# ------------------------------------------------- live two-peer integration
def _serve_app_in_thread(app):
    """Host an aiohttp app on its OWN thread's event loop (TestClient can't
    serve cross-thread traffic — its loop isn't running between requests).
    Returns (base_url, stop)."""
    from aiohttp import web

    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def _run():
        asyncio.set_event_loop(loop)

        async def _up():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            state["runner"] = runner
            state["port"] = runner.addresses[0][1]

        loop.run_until_complete(_up())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    assert started.wait(30), "fleet peer server failed to start"

    def _stop():
        async def _down():
            await state["runner"].cleanup()

        try:
            asyncio.run_coroutine_threadsafe(_down(), loop).result(20)
        except Exception:
            pass
        loop.call_soon_threadsafe(loop.stop)
        t.join(10)

    return f"http://127.0.0.1:{state['port']}", _stop


def _tiny_fleet_config():
    return {
        "tiny-chat": {
            "kind": "decoder",
            "tiny": True,
            "max_slots": 2,
            "max_seq_len": 128,
            "kv_host_bytes": 1 << 26,
            "prefix_min_tokens": 4,
            "prefix_cache": 8,
        }
    }


@pytest.fixture(scope="module")
def fleet_pair():
    """Two REAL serve stacks (registry + engine + fleet plane + aiohttp app)
    on localhost — separate engines and KV pools, same tiny weights
    (llama.init is seed-deterministic), exactly the cross-process shape
    minus the fork."""
    from django_assistant_bot_tpu.serving.registry import ModelRegistry
    from django_assistant_bot_tpu.serving.server import create_app

    regs, planes, urls, stops = [], [], [], []
    for name in ("a", "b"):
        reg = ModelRegistry.from_config(_tiny_fleet_config())
        plane = FleetPlane(reg, name=name, pool="unified")
        reg.fleet_plane = plane
        url, stop = _serve_app_in_thread(create_app(reg))
        regs.append(reg)
        planes.append(plane)
        urls.append(url)
        stops.append(stop)
    planes[0].peers = [("b", urls[1])]
    planes[1].peers = [("a", urls[0])]
    yield regs, planes, urls
    for stop in stops:
        stop()
    for reg in regs:
        reg.stop()


def _fleet_generate(url, body, timeout=120.0):
    from django_assistant_bot_tpu.serving.fleet import PeerClient

    return PeerClient(url, timeout_s=timeout).post_json("/fleet/generate", body)


def test_fleet_kv_ships_bit_identical_across_processes(fleet_pair):
    """The acceptance bit-identity arm: register a prefix on peer A, ship it
    over /fleet/kv/get -> /fleet/kv/put to peer B, and assert B's host tier
    holds byte-identical pages — then B serves the same dialog with token
    ids identical to A's (restore across the process boundary)."""
    from django_assistant_bot_tpu.serving.fleet import PeerClient

    regs, planes, urls = fleet_pair
    prompt = [1 + (i % 250) for i in range(40)]
    plen = 16
    body = {
        "model": "tiny-chat",
        "prompt_ids": prompt,
        "max_tokens": 8,
        "temperature": 0.0,
        "prefix_len": plen,
    }
    ra = _fleet_generate(urls[0], body)
    assert ra["token_ids"], ra
    # A registered prompt[:16]; export it over the wire
    data = PeerClient(urls[0]).post_for_bytes(
        "/fleet/kv/get",
        {"model": "tiny-chat", "prompt_ids": prompt, "prefix_len": plen},
    )
    assert data is not None, "peer A should hold the registered prefix"
    ent = decode_kv_entry(data)
    assert ent.key == tuple(prompt[:plen])
    out = PeerClient(urls[1]).post_bytes(
        "/fleet/kv/put?model=tiny-chat", data
    )
    assert out["stored"], out
    # receiver-side bytes are BIT-identical to the wire payload
    tier_b = regs[1].generators["tiny-chat"].kv_host_tier
    got = tier_b.export_entry(ent.key)
    assert got is not None
    assert np.asarray(got.k).tobytes() == np.asarray(ent.k).tobytes()
    assert np.asarray(got.v).tobytes() == np.asarray(ent.v).tobytes()
    # and B serves the same dialog via restore with identical output
    restores_before = tier_b.stats()["kv_host_restores"]
    rb = _fleet_generate(urls[1], body)
    assert rb["token_ids"] == ra["token_ids"]
    assert tier_b.stats()["kv_host_restores"] > restores_before


def test_fleet_router_live_dispatch_and_gossip(fleet_pair):
    regs, planes, urls = fleet_pair
    router = FleetRouter(
        [("a", urls[0]), ("b", urls[1])],
        model="tiny-chat",
        refresh_interval_s=1e9,
        request_timeout_s=120.0,
    )
    try:
        router.refresh()
        assert all(p.healthy for p in router.peers)
        res = router.submit(
            [2 + (i % 200) for i in range(24)],
            max_tokens=6,
            temperature=0.0,
            prefix_len=8,
        ).result(timeout=120)
        assert res.completion_tokens > 0 and res.peer in ("a", "b")
        # the serving peer registered the prefix; gossip makes the router's
        # registry point affinity at it
        router.refresh()
        holders = router._peer_holders([2 + (i % 200) for i in range(24)], 8)
        assert res.peer in holders
    finally:
        router.close()


def test_fleet_peer_kill_reroute_and_degraded_healthz(fleet_pair):
    """The chaos arm: a dead peer re-routes token-lessly (goodput 1.0) and
    the survivor's /fleet/healthz reports the fleet degraded."""
    from django_assistant_bot_tpu.serving.fleet import PeerClient
    from django_assistant_bot_tpu.serving.registry import ModelRegistry
    from django_assistant_bot_tpu.serving.server import create_app

    regs, planes, urls = fleet_pair
    reg_c = ModelRegistry.from_config(_tiny_fleet_config())
    reg_c.fleet_plane = FleetPlane(reg_c, name="c", pool="unified")
    url_c, stop_c = _serve_app_in_thread(create_app(reg_c))
    router = FleetRouter(
        [("c", url_c), ("a", urls[0])],
        model="tiny-chat",
        refresh_interval_s=1e9,
        request_timeout_s=120.0,
        health_timeout_s=2.0,
    )
    old_peers = list(planes[0].peers)
    try:
        # warm path through c first (deterministic: a looks loaded; suppress
        # the lazy refresh so the fake load survives until dispatch)
        router._last_refresh = router._clock()
        router.peers[1].queued = 100
        res = router.submit([3] * 12, max_tokens=4, temperature=0.0).result(120)
        assert res.peer == "c"
        stop_c()
        reg_c.stop()
        # token-less re-route: every request still completes (goodput 1.0)
        done = [
            router.submit([4] * 12, max_tokens=4, temperature=0.0).result(120)
            for _ in range(2)
        ]
        assert all(r.peer == "a" for r in done)
        assert router.reroutes >= 1
        # the survivor's fleet healthz degrades on the unreachable peer
        planes[0].peers = [("c", url_c)]
        hz = PeerClient(urls[0]).get_json("/fleet/healthz")
        assert hz["fleet"]["status"] == "degraded"
        assert hz["fleet"]["peers_reachable"] == 0
    finally:
        planes[0].peers = old_peers
        router.close()


@pytest.mark.parametrize("first, n, restored", [(11, 80, False), (101, 64, True)])
def test_fleet_disagg_prefill_decode_output_identity(fleet_pair, first, n, restored):
    """The acceptance disaggregation arm: a decode-pool replica serves a
    session whose prefill ran in the prefill pool, output identical to the
    unified arm, with pages shipped over the wire and admitted via restore.

    The decode peer's one-token suffix rides its smallest prefill bucket, 64,
    whose write window has to end inside the 128-token context
    (``_paged_usable_hit``): 63 + 64 does, 79 + 64 does not, so the 80-token
    prompt's pages are shipped and then not used: the decode peer prefills it
    in full, to the same tokens (PERF.md section 7 has the loss)."""
    regs, planes, urls = fleet_pair
    # token alphabets disjoint from each other and from every other test in
    # this module: a shared first-token prefix would let B serve from its
    # device prefix registry (warmed by an earlier test) and skip the
    # host-tier restore under test
    prompt = [first + (i % 180) for i in range(n)]
    # unified reference first (pools still unified)
    ref = _fleet_generate(
        urls[0],
        {
            "model": "tiny-chat",
            "prompt_ids": prompt,
            "max_tokens": 8,
            "temperature": 0.0,
        },
    )
    assert ref["token_ids"]
    tier_b = regs[1].generators["tiny-chat"].kv_host_tier
    restores_before = tier_b.stats()["kv_host_restores"]
    planes[0].pool = "prefill"
    planes[1].pool = "decode"
    router = FleetRouter(
        [
            FleetPeer("a", urls[0], pool="prefill", timeout_s=120.0),
            FleetPeer("b", urls[1], pool="decode", timeout_s=120.0),
        ],
        model="tiny-chat",
        refresh_interval_s=1e9,
        request_timeout_s=120.0,
        handoff_suffix_tokens=64,
    )
    try:
        res = router.submit(prompt, max_tokens=8, temperature=0.0).result(120)
        assert res.token_ids == ref["token_ids"], (
            "disaggregated output must match the unified arm bit-for-bit"
        )
        assert res.peer == "b"
        assert router.handoffs == 1 and router.pages_shipped > 0
        assert planes[1].kv_puts >= 1
        assert (tier_b.stats()["kv_host_restores"] > restores_before) == restored
    finally:
        planes[0].pool = "unified"
        planes[1].pool = "unified"
        router.close()


def test_fleet_metrics_exposition_parses(fleet_pair):
    from django_assistant_bot_tpu.serving.fleet import PeerClient
    from django_assistant_bot_tpu.serving.obs import (
        parse_prometheus_text,
        render_prometheus,
    )

    regs, planes, urls = fleet_pair
    # attach a fleet router so BOTH gauge families render
    router = FleetRouter(
        [("b", urls[1])], model="tiny-chat", refresh_interval_s=1e9
    )
    regs[0].fleet_router = router
    try:
        text = render_prometheus(regs[0])
    finally:
        del regs[0].fleet_router
        router.close()
    names = set(parse_prometheus_text(text))
    for want in (
        "dabt_fleet_pool_info",
        "dabt_fleet_kv_puts_total",
        "dabt_fleet_peers_total",
        "dabt_fleet_reroutes_total",
        "dabt_fleet_pages_shipped_total",
    ):
        assert want in names, (want, sorted(names)[:8])


def test_traces_endpoint_and_workload_export(fleet_pair, tmp_path):
    """Satellite: the obs trace ring exports to the workload JSONL format
    and replays structurally (sorted arrivals, positive budgets)."""
    import argparse

    from django_assistant_bot_tpu.cli import trace_export
    from django_assistant_bot_tpu.serving.fleet import PeerClient
    from django_assistant_bot_tpu.workload.generator import load_trace

    regs, planes, urls = fleet_pair
    # ensure at least two finished requests ride the ring
    for i in range(2):
        _fleet_generate(
            urls[0],
            {
                "model": "tiny-chat",
                "prompt_ids": [5 + i] * 10,
                "max_tokens": 3,
                "temperature": 0.0,
            },
        )
    body = PeerClient(urls[0]).get_json("/traces")
    assert body["traces"], "expected finished traces on the ring"
    src = tmp_path / "traces.json"
    src.write_text(json.dumps(body))
    out = tmp_path / "trace.jsonl"
    rc = trace_export.run(
        argparse.Namespace(
            url=None, input=str(src), output=str(out), longctx_threshold=None
        )
    )
    assert rc == 0
    reqs = load_trace(str(out))
    assert len(reqs) >= 2
    assert reqs[0].t_s == 0.0
    assert all(r.prompt_tokens > 0 and r.max_tokens >= 1 for r in reqs)
    ts = [r.t_s for r in reqs]
    assert ts == sorted(ts)


# --------------------------------------------------- two-subprocess smoke
@pytest.mark.slow
def test_fleet_two_subprocess_smoke(tmp_path):
    """The CI smoke: two REAL serve processes on localhost, a dialog routed
    through the FleetRouter, one peer killed mid-session — the request
    re-routes and the survivor's fleet healthz degrades."""
    import socket
    import subprocess
    import sys

    from django_assistant_bot_tpu.serving.fleet import PeerClient

    def _free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = [_free_port(), _free_port()]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # CI sets DABT_FLIGHT_DIR so a red run uploads the subprocess dumps
    env.setdefault("DABT_FLIGHT_DIR", str(tmp_path / "flight"))
    procs = []
    try:
        for i, port in enumerate(ports):
            other = ports[1 - i]
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        "-m",
                        "django_assistant_bot_tpu.cli",
                        "serve",
                        "--tiny",
                        "--host",
                        "127.0.0.1",
                        "--port",
                        str(port),
                        "--fleet-name",
                        f"peer{i}",
                        "--fleet-peers",
                        f"peer{1 - i}=http://127.0.0.1:{other}",
                    ],
                    env=env,
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.STDOUT,
                )
            )
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        deadline = time.monotonic() + 300
        for url in urls:
            while True:
                try:
                    if PeerClient(url, timeout_s=5.0).get_json("/healthz")[
                        "status"
                    ] == "ok":
                        break
                except Exception:
                    pass
                assert time.monotonic() < deadline, "peers failed to boot"
                time.sleep(1.0)
        router = FleetRouter(
            [("peer0", urls[0]), ("peer1", urls[1])],
            model="tiny-chat",
            refresh_interval_s=1e9,
            request_timeout_s=120.0,
            health_timeout_s=3.0,
        )
        try:
            router.refresh()
            res = router.submit(
                [7] * 16, max_tokens=4, temperature=0.0
            ).result(timeout=180)
            assert res.completion_tokens > 0
            # chaos: kill peer0, keep serving through peer1
            procs[0].kill()
            procs[0].wait(30)
            router.peers[1].queued = 0
            router.peers[0].queued = 0
            done = router.submit(
                [8] * 16, max_tokens=4, temperature=0.0
            ).result(timeout=180)
            assert done.peer == "peer1"
            assert router.reroutes + router.refresh_failures >= 0
            hz = PeerClient(urls[1], timeout_s=10.0).get_json("/fleet/healthz")
            assert hz["fleet"]["status"] == "degraded"
        finally:
            router.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(30)


# --------------------------------------------- peer client: phases + chaos
def _closed_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_peer_client_connect_refused_is_connect_phase():
    from django_assistant_bot_tpu.serving.fleet import PeerClient

    cli = PeerClient(
        f"http://127.0.0.1:{_closed_port()}", timeout_s=2.0,
        connect_timeout_s=1.0,
    )
    with pytest.raises(PeerUnreachable) as ei:
        cli.get_json("/fleet/healthz")
    assert ei.value.phase == "connect"


def test_peer_client_read_timeout_is_read_phase():
    """A peer that accepts the connection but never answers dies in the READ
    phase — the request may have executed, so the router must dedup, not
    re-route."""
    from aiohttp import web

    from django_assistant_bot_tpu.serving.fleet import PeerClient

    async def slow(request):
        await asyncio.sleep(5.0)
        return web.json_response({})

    app = web.Application()
    app.router.add_get("/slow", slow)
    url, stop = _serve_app_in_thread(app)
    try:
        cli = PeerClient(url, timeout_s=0.2, connect_timeout_s=2.0)
        with pytest.raises(PeerUnreachable) as ei:
            cli.get_json("/slow")
        assert ei.value.phase == "read"
    finally:
        stop()


def test_peer_client_retries_connect_phase_with_backoff():
    """Connect-phase retries back off exponentially through the INJECTABLE
    sleep; the injected partition consumes every attempt, so no socket is
    ever touched."""
    from django_assistant_bot_tpu.serving.fleet import PeerClient

    inj = FaultInjector({})
    inj.arm("net_partition", 3, key="r->p")
    sleeps = []
    cli = PeerClient(
        "http://127.0.0.1:1", timeout_s=1.0, sleep=sleeps.append,
        injector=inj, fault_key="r->p",
    )
    with pytest.raises(PeerUnreachable) as ei:
        cli._request("GET", "/x", retries=2)
    assert ei.value.phase == "connect"
    assert sleeps == [0.05, 0.1]
    assert inj.stats()["net_partition[r->p]"]["fires"] == 3


def test_peer_client_never_retries_read_phase():
    """Read-phase failures are NOT blindly re-sent by the client (the peer
    may have executed the request); recovery belongs to the router's
    idempotency-keyed same-peer retry."""
    from django_assistant_bot_tpu.serving.fleet import PeerClient

    sleeps = []
    cli = PeerClient("http://127.0.0.1:1", sleep=sleeps.append)
    cli._request_once = lambda *a, **k: (_ for _ in ()).throw(
        PeerUnreachable("connection reset mid-read", phase="read")
    )
    with pytest.raises(PeerUnreachable) as ei:
        cli._request("GET", "/x", retries=3)
    assert ei.value.phase == "read" and sleeps == []


def test_peer_client_net_delay_injected_through_sleep():
    from django_assistant_bot_tpu.serving.fleet import PeerClient

    inj = FaultInjector({"net_delay": {"fire_on": [1], "delay_s": 0.7}})
    sleeps = []
    cli = PeerClient(
        f"http://127.0.0.1:{_closed_port()}", timeout_s=1.0,
        connect_timeout_s=0.5, sleep=sleeps.append, injector=inj,
    )
    with pytest.raises(PeerUnreachable):
        cli.get_json("/x")
    assert sleeps == [0.7]


# ----------------------------------- router: partition tolerance (stubbed)
def test_fleet_router_refresh_failure_reasons_classified():
    """The operator triaging a partition needs WHY refresh failed — each
    failure shape lands under its own reason label and on the peer row."""
    router, peers = _mk_router(n=1)

    def _raiser(exc):
        def _f(path, timeout_s=None, retries=0):
            raise exc

        return _f

    cases = [
        (PeerUnreachable("connection refused"), "conn_refused"),
        (PeerUnreachable("read timed out", phase="read"), "timeout"),
        (PeerUnreachable("no route to host"), "unreachable"),
        (PeerHTTPError(503, "upstream sad"), "http_5xx"),
        (ValueError("bogus json"), "bad_payload"),
    ]
    for exc, want in cases:
        peers[0].client.get_json = _raiser(exc)
        router.refresh()
        assert peers[0].last_failure_reason == want
        assert not peers[0].healthy
    st = router.stats()
    assert st["refresh_failures"] == len(cases)
    assert st["refresh_failure_reasons"] == {
        "conn_refused": 1, "timeout": 1, "unreachable": 1,
        "http_5xx": 1, "bad_payload": 1,
    }
    assert st["peers"][0]["last_failure_reason"] == "bad_payload"
    assert any(
        r["event"] == "peer_unhealthy" and r.get("reason") == "conn_refused"
        for r in router.flight.events()
    )
    router.close()


def test_fleet_router_ttl_drop_and_heal_reconcile():
    """Partition tolerance end-to-end on a fake clock: gossip-learned
    affinity ages out once the holder is unreachable past registry_ttl_s,
    and the heal forces a reset-snapshot reconcile whose convergence time
    lands in reconcile_last_s."""
    t = [0.0]
    router, peers = _mk_router(registry_ttl_s=10.0, clock=lambda: t[0])
    key = tuple(range(1, 9))
    ev = {
        "model": "tiny-chat", "replica": "tiny-chat/r0",
        "event": "host_put", "key": list(key), "length": len(key),
    }
    peers[1].client.prefix = lambda since: {"seq": 1, "events": [ev]}
    router.refresh()
    assert set(router._peer_holders(list(key) + [99], len(key))) == {"p1"}

    healthz_ok = peers[1].client.get_json

    def _dead(path, timeout_s=None, retries=0):
        raise PeerUnreachable("connection refused")

    peers[1].client.get_json = _dead
    t[0] = 1.0
    router.refresh()  # failure starts the unreachable streak, no drop yet
    assert set(router._peer_holders(list(key) + [99], len(key))) == {"p1"}
    assert router.ttl_drops == 0 and peers[1].unreachable_since == 1.0
    t[0] = 11.0
    router.refresh()  # 10s unreachable: affinity claims age out, ONCE
    assert router._peer_holders(list(key) + [99], len(key)) == {}
    assert router.ttl_drops == 1 and peers[1].ttl_dropped
    t[0] = 12.0
    router.refresh()
    assert router.ttl_drops == 1  # not re-counted while still down
    assert any(
        r["event"] == "registry_ttl_drop" for r in router.flight.events()
    )

    # heal: the next successful refresh forces the anti-entropy reset
    def _reset_snapshot(since):
        assert since == -1, "heal after TTL drop must force the reset path"
        t[0] += 0.5  # the exchange itself takes measurable time
        return {
            "seq": 9, "digest": 4242, "reset": True,
            "holdings": [
                {
                    "model": "tiny-chat", "replica": "tiny-chat/r0",
                    "key": list(key), "length": len(key), "tier": TIER_HOST,
                }
            ],
        }

    peers[1].client.get_json = healthz_ok
    peers[1].client.prefix = _reset_snapshot
    t[0] = 20.0
    router.refresh()
    assert set(router._peer_holders(list(key) + [99], len(key))) == {"p1"}
    assert router.reconciles == 1
    assert router.reconcile_last_s == pytest.approx(0.5)
    assert peers[1].prefix_seq == 9 and peers[1].prefix_digest == 4242
    assert not peers[1].ttl_dropped and peers[1].unreachable_since is None
    assert any(
        r["event"] == "gossip_reconciled" for r in router.flight.events()
    )
    router.close()


def test_fleet_router_gossip_digest_mismatch_forces_reset():
    """A delta whose chained digest disagrees with the server's forces the
    reset-snapshot path in the SAME refresh — diverged logs never skew
    affinity silently."""
    router, peers = _mk_router()
    key = tuple(range(1, 9))
    ev = {
        "model": "tiny-chat", "replica": "tiny-chat/r0",
        "event": "host_put", "key": list(key), "length": len(key),
    }
    assert _chain_digest(0, ev) != 999999  # the advertised digest is wrong

    def _prefix(since):
        if since >= 0:
            return {"seq": 2, "digest": 999999, "events": [ev]}
        return {
            "seq": 5, "digest": 4242, "reset": True,
            "holdings": [
                {
                    "model": "tiny-chat", "replica": "tiny-chat/r0",
                    "key": list(key), "length": len(key), "tier": TIER_HOST,
                }
            ],
        }

    peers[1].client.prefix = _prefix
    router.refresh()
    assert router.gossip_digest_mismatches == 1
    assert router.reconciles == 1  # the forced reset IS a reconcile
    assert peers[1].prefix_seq == 5 and peers[1].prefix_digest == 4242
    assert set(router._peer_holders(list(key) + [99], len(key))) == {"p1"}
    assert any(
        r["event"] == "gossip_digest_mismatch"
        for r in router.flight.events()
    )
    router.close()


def test_plane_prefix_events_digest_matches_follower_chain():
    """Both delta and reset shapes carry the rolling digest, and a follower
    chaining _chain_digest over the delta events reproduces it exactly —
    the divergence check is sound, not a tautology."""
    plane = FleetPlane(_StubRegistry(), pool="unified", log_size=16)
    for i in range(3):
        plane.on_tier_event("m", "m/r0", "host_put", (1, 2, i), 3)
    out = plane.prefix_events(0)
    d = 0
    for ev in out["events"]:
        d = _chain_digest(d, ev)
    assert d == out["digest"] != 0
    for i in range(40):  # overflow the log -> reset shape
        plane.on_tier_event("m", "m/r0", "host_put", (9, i), 2)
    out2 = plane.prefix_events(1)
    assert out2.get("reset") and isinstance(out2["digest"], int)
    assert out2["digest"] != out["digest"]


# -------------------------------------- router: idempotent read-phase retry
def test_fleet_router_read_failure_retries_same_peer_same_key():
    """A read-phase death retries the SAME peer under the SAME idempotency
    key (the peer may have executed it — re-routing is what double-executes);
    no breaker failure, no reroute counted."""
    router, peers = _mk_router(timeout_retries=1)
    peers[1].queued = 100  # p0 is chosen first
    calls = {"n": 0}

    def _flaky(body):
        calls["n"] += 1
        if calls["n"] == 1:
            raise PeerUnreachable("connection reset by peer", phase="read")
        return {
            "token_ids": [1, 2], "result": "ok",
            "usage": {"prompt_tokens": 3, "completion_tokens": 2},
            "length_limited": False,
        }

    peers[0].client.generate = _flaky
    res = router.submit([1, 2, 3]).result(timeout=10)
    assert res.peer == "p0" and res.reroutes == 0
    assert router.timeout_retries_total == 1 and router.reroutes == 0
    bodies = [c[2] for c in peers[0].client.calls if c[0] == "POST"]
    assert len(bodies) == 2
    assert bodies[0]["idem_key"] == bodies[1]["idem_key"]
    assert bodies[0]["idem_key"] == f"{res.trace_id}:0"
    assert peers[0].healthy and peers[0].breaker.allow()
    assert any(
        r["event"] == "timeout_retry" for r in router.flight.events()
    )
    router.close()


def test_fleet_router_read_retries_exhausted_falls_to_reroute():
    router, peers = _mk_router(timeout_retries=0)
    peers[1].queued = 100
    peers[0].client.generate = lambda body: (_ for _ in ()).throw(
        PeerUnreachable("connection reset by peer", phase="read")
    )
    res = router.submit([1, 2, 3]).result(timeout=10)
    assert res.peer == "p1" and res.reroutes == 1
    assert router.timeout_retries_total == 0
    router.close()


def test_fleet_router_caller_attempt_feeds_idem_key():
    """submit(attempt=) is the CALLER's retry ordinal: bumping it asks for a
    fresh execution, reusing it dedups server-side."""
    router, peers = _mk_router()
    router.submit([1, 2, 3], trace_id="t-idem", attempt=0).result(10)
    router.submit([1, 2, 3], trace_id="t-idem", attempt=1).result(10)
    keys = {
        c[2]["idem_key"]
        for p in peers
        for c in p.client.calls
        if c[0] == "POST"
    }
    assert keys == {"t-idem:0", "t-idem:1"}
    router.close()


# ------------------------------------------ router: pull integrity re-fetch
def _pull_setup(router, peers):
    """Gossip p1 as holder of an 8-token prefix, p1 shedding, so dispatch
    lands on p0 which pulls from p1 first (mirrors the prefix-pull test)."""
    key = tuple(range(1, 9))
    ent = _entry(np.float32, length=len(key))
    ent = HostPrefixEntry(
        key=key, length=len(key), k=ent.k, v=ent.v, nbytes=ent.nbytes, pages=1
    )
    peers[1].client.prefix = lambda since: {
        "seq": 1,
        "events": [
            {
                "model": "tiny-chat", "replica": "tiny-chat/r0",
                "event": "host_put", "key": list(key), "length": len(key),
            }
        ],
    }
    router.refresh()
    peers[1].client.generate = lambda body: (_ for _ in ()).throw(
        PeerHTTPError(429, "busy", retry_after_s=1.0, reason="queue_full")
    )
    peers[1].client.kv_get = lambda body: encode_kv_entry(ent)
    return key


def test_fleet_router_pull_integrity_reject_refetches_once():
    """A pull whose payload rots in flight re-fetches ONCE from the holder
    (which still has the intact entry) before giving up — counted on both
    the reject and refetch gauges."""
    router, peers = _mk_router()
    key = _pull_setup(router, peers)
    puts = {"n": 0}

    def _put(data):
        puts["n"] += 1
        if puts["n"] == 1:
            raise PeerHTTPError(
                422, "CRC-32C mismatch", reason="wire_integrity"
            )
        return {"stored": True, "pages": 1}

    peers[0].client.kv_put = _put
    res = router.submit(list(key) + [50, 51], prefix_len=len(key)).result(10)
    assert res.peer == "p0"
    assert router.pull_integrity_rejects == 1 and router.pull_refetches == 1
    assert router.prefix_pulls == 1 and router.pages_shipped == 1
    assert router.pull_failures == 0
    fetches = [
        c for c in peers[1].client.calls if c[1] == "/fleet/kv/get"
    ]
    assert len(fetches) == 2
    router.close()


def test_fleet_router_pull_double_corruption_cold_prefills():
    """Two corrupt transfers in a row: give up on the pull (cold prefill on
    the target), NEVER absorb garbage — and the request still succeeds."""
    router, peers = _mk_router()
    key = _pull_setup(router, peers)
    peers[0].client.kv_put = lambda data: (_ for _ in ()).throw(
        PeerHTTPError(422, "CRC-32C mismatch", reason="wire_integrity")
    )
    res = router.submit(list(key) + [50, 51], prefix_len=len(key)).result(10)
    assert res.peer == "p0"
    assert router.pull_integrity_rejects == 2 and router.pull_refetches == 1
    assert router.prefix_pulls == 0 and router.pull_failures == 1
    router.close()


# ------------------------------------------------- plane: idempotency ledger
def test_plane_idem_claim_complete_hit_and_coalesce():
    plane = FleetPlane(_StubRegistry(), pool="unified")
    state, fut = plane.idem_claim("k1")
    assert state == "mine"
    # a dup arriving while in flight coalesces onto the SAME future
    state2, fut2 = plane.idem_claim("k1")
    assert state2 == "wait" and fut2 is fut
    assert plane.idem_coalesced == 1
    plane.idem_complete("k1", fut, {"result": "done"})
    assert fut.result(1) == {"result": "done"}
    # a dup arriving after completion is a hit on the recorded payload
    state3, fut3 = plane.idem_claim("k1")
    assert state3 == "wait" and fut3.result(1) == {"result": "done"}
    assert plane.idem_hits == 1 and plane.idem_executions == 1


def test_plane_idem_release_reexecutes():
    """A failed execution releases the key: waiters get None (their cue to
    claim afresh) and a retry re-executes instead of replaying a failure."""
    plane = FleetPlane(_StubRegistry(), pool="unified")
    _, fut = plane.idem_claim("k2")
    _, waiter = plane.idem_claim("k2")
    plane.idem_release("k2", fut)
    assert waiter.result(1) is None
    state, fut2 = plane.idem_claim("k2")
    assert state == "mine" and fut2 is not fut
    assert plane.idem_executions == 2


def test_plane_idem_ledger_bounded_done_first_eviction():
    """The ledger is bounded; COMPLETED entries evict before in-flight ones
    (an in-flight execution must never be forgotten while a dup could still
    arrive)."""
    plane = FleetPlane(_StubRegistry(), pool="unified", idem_ledger_size=8)
    _, done_fut = plane.idem_claim("done")
    plane.idem_complete("done", done_fut, {"ok": True})
    inflight = [plane.idem_claim(f"x{i}")[1] for i in range(9)]
    assert plane.idem_evictions == 2  # "done" first, then the oldest x
    assert "done" not in plane._idem and "x0" not in plane._idem
    assert all(f"x{i}" in plane._idem for i in range(1, 9))
    for i, f in enumerate(inflight):
        plane.idem_release(f"x{i}", f)


# ---------------------------------------- live network chaos (CI -k netchaos)
def test_fleet_netchaos_corrupt_kv_put_rejected_live(fleet_pair):
    """An in-flight bit flip on /fleet/kv/put fails the CRC on the RECEIVER:
    422 with reason=wire_integrity, counted, and nothing absorbed."""
    from django_assistant_bot_tpu.serving.fleet import PeerClient

    regs, planes, urls = fleet_pair
    inj = FaultInjector({})
    cli = PeerClient(urls[1], injector=inj, fault_key="probe")
    data = encode_kv_entry(_entry(np.float32, length=16))
    rejects_before = planes[1].kv_integrity_rejects
    puts_before = planes[1].kv_puts
    inj.arm("net_corrupt", 1, key="probe")
    with pytest.raises(PeerHTTPError) as ei:
        cli.post_bytes("/fleet/kv/put?model=tiny-chat", data)
    assert ei.value.status == 422 and ei.value.reason == "wire_integrity"
    assert planes[1].kv_integrity_rejects == rejects_before + 1
    assert planes[1].kv_puts == puts_before  # nothing absorbed
    # the same payload clean passes the CRC gate (geometry may still refuse
    # storage — that is a different, non-integrity verdict)
    try:
        cli.post_bytes("/fleet/kv/put?model=tiny-chat", data)
    except PeerHTTPError as e:
        assert e.reason != "wire_integrity"
    assert planes[1].kv_integrity_rejects == rejects_before + 1


def test_fleet_netchaos_idem_dedup_live(fleet_pair):
    """Two /fleet/generate POSTs under one idem_key execute ONCE: the second
    returns the recorded response marked deduped, under its own request id."""
    regs, planes, urls = fleet_pair
    body = {
        "model": "tiny-chat",
        "prompt_ids": [21 + (i % 160) for i in range(12)],
        "max_tokens": 3,
        "temperature": 0.0,
        "idem_key": "netchaos-dedup:0",
    }
    exec_before = planes[0].idem_executions
    r1 = _fleet_generate(urls[0], body)
    r2 = _fleet_generate(urls[0], body)
    assert r2.get("deduped") is True and not r1.get("deduped")
    assert r2["token_ids"] == r1["token_ids"]
    assert r2["request_id"] != r1["request_id"]
    assert planes[0].idem_executions == exec_before + 1
    assert planes[0].idem_hits >= 1


def test_fleet_netchaos_drop_read_retry_dedup_live(fleet_pair):
    """net_drop mid-request: the router retries the SAME peer under the same
    idem key; the peer (which DID execute the first send) dedups — goodput 1,
    duplicate executions 0."""
    regs, planes, urls = fleet_pair
    inj = FaultInjector({})
    router = FleetRouter(
        [("a", urls[0]), ("b", urls[1])],
        model="tiny-chat", name="netchaos", refresh_interval_s=1e9,
        request_timeout_s=120.0, injector=inj, timeout_retries=1,
    )
    exec_before = planes[0].idem_executions
    dups_before = planes[0].idem_hits + planes[0].idem_coalesced
    try:
        router._last_refresh = router._clock()
        router.peers[1].queued = 100  # a is chosen first
        inj.arm("net_drop", 1, key="netchaos->a")
        res = router.submit(
            [31 + (i % 140) for i in range(12)], max_tokens=4, temperature=0.0
        ).result(timeout=120)
        assert res.peer == "a" and res.reroutes == 0
        assert res.completion_tokens > 0
        assert router.timeout_retries_total == 1
        assert planes[0].idem_executions == exec_before + 1  # no double exec
        assert planes[0].idem_hits + planes[0].idem_coalesced >= dups_before + 1
    finally:
        router.close()


def test_fleet_netchaos_partition_reroute_live(fleet_pair):
    """An injected partition on one router edge re-routes token-lessly to
    the reachable peer: goodput stays 1.0."""
    regs, planes, urls = fleet_pair
    inj = FaultInjector({})
    router = FleetRouter(
        [("a", urls[0]), ("b", urls[1])],
        model="tiny-chat", name="netchaos", refresh_interval_s=1e9,
        request_timeout_s=120.0, injector=inj,
    )
    try:
        router._last_refresh = router._clock()
        router.peers[1].queued = 100  # a preferred... but partitioned
        inj.arm("net_partition", 1, key="netchaos->a")
        res = router.submit(
            [41] * 12, max_tokens=4, temperature=0.0
        ).result(timeout=120)
        assert res.peer == "b" and res.reroutes == 1
        assert router.reroutes == 1
    finally:
        router.close()


def test_fleet_netchaos_pull_miss_cold_prefill_live(fleet_pair):
    """Satellite: the /fleet/kv/get pull-miss path.  Gossip claims a holder
    whose entry is gone (evicted) — the 404 is an honest miss, the target
    falls through to cold prefill, and the CLIENT request never errors."""
    regs, planes, urls = fleet_pair
    router = FleetRouter(
        [("a", urls[0]), ("b", urls[1])],
        model="tiny-chat", name="netchaos", refresh_interval_s=1e9,
        request_timeout_s=120.0,
    )
    key = tuple(51 + (i % 100) for i in range(8))
    try:
        router._last_refresh = router._clock()
        # a STALE gossip claim: b never actually stored this prefix
        router.prefix_registry.apply_holding(
            "b/tiny-chat/r0", key, len(key), TIER_HOST
        )
        router._note_rep("b", "b/tiny-chat/r0")
        # open b's breaker so dispatch lands on a (the non-holder) while b
        # stays healthy enough to be pulled from
        for _ in range(3):
            router.peers[1].breaker.record_failure()
        assert not router.peers[1].breaker.allow()
        res = router.submit(
            list(key) + [60, 61, 62, 63],
            max_tokens=4, temperature=0.0, prefix_len=len(key),
        ).result(timeout=120)
        assert res.peer == "a" and res.completion_tokens > 0
        assert router.pull_misses == 1 and router.prefix_pulls == 0
        assert router.pull_failures == 0
    finally:
        router.close()
