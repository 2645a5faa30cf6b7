"""Durability plane tests (storage/durable.py + storage/integrity.py).

The WAL/snapshot/recovery contract, exercised the way crashes actually land:
torn tails healed at open (not trusted), corrupt snapshots detected by digest
walk and FALLEN BACK from (never loaded), tombstones that cannot resurrect
across a snapshot boundary, idempotency-ledger dedup across restarts, and the
headline SIGKILL-mid-ingest kill-replay (slow-marked; also CI's smoke step).
Fault schedules are armed/exact (serving/faults.py), fuzz seeds pinned — all
deterministic.
"""

import argparse
import json
import os
import struct

import numpy as np
import pytest

from django_assistant_bot_tpu.serving.faults import (
    ALL_SITES,
    FaultInjected,
    FaultInjector,
    reset_global_injector,
    set_global_injector,
)
from django_assistant_bot_tpu.storage.ann import make_clustered
from django_assistant_bot_tpu.storage.durable import (
    _HDR,
    REC_APPEND,
    REC_INSTALL,
    REC_TOMBSTONE,
    DurableANN,
    MmapRowStore,
    SnapshotStore,
    WriteAheadLog,
    verify_dir,
)
from django_assistant_bot_tpu.storage.integrity import crc32c, entry_crc32c, file_crc32c

DIM = 32


@pytest.fixture(autouse=True)
def _clean_injector():
    reset_global_injector()
    yield
    reset_global_injector()


def _corpus(n, seed=7):
    return make_clustered(n, DIM, seed=seed)


def _topk(index, queries, k=10):
    return [[int(i) for i, _ in index.search(q, k=k)] for q in queries]


# ------------------------------------------------------------------ CRC-32C
def test_crc32c_known_vector_and_chaining():
    # RFC 3720 check value for "123456789"
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0
    whole = crc32c(b"hello world")
    assert crc32c(b" world", crc32c(b"hello")) == whole
    assert entry_crc32c(b"k", b"v") == crc32c(b"v", crc32c(b"k"))


def test_crc32c_unified_across_planes():
    """Satellite 1: one implementation — the KV-pool and fleet-wire checksums
    ARE storage.integrity's, not copies that could drift."""
    from django_assistant_bot_tpu.serving import fleet, kv_pool
    from django_assistant_bot_tpu.storage import integrity

    assert kv_pool.crc32c is integrity.crc32c
    assert kv_pool.entry_crc32c is integrity.entry_crc32c
    assert fleet.crc32c is integrity.crc32c


def test_file_crc32c_matches_buffer(tmp_path):
    p = tmp_path / "blob"
    data = bytes(range(256)) * 77
    p.write_bytes(data)
    assert file_crc32c(str(p), chunk_bytes=1000) == crc32c(data)
    assert file_crc32c(str(tmp_path / "missing")) is None


# ---------------------------------------------------------------------- WAL
def test_wal_roundtrip_property_fuzz(tmp_path):
    """Pinned-seed property test: random record types/sizes through tiny
    segments (forced rotation), reopened, must replay byte-identically."""
    rng = np.random.default_rng(int(os.environ.get("DABT_FAULT_SEED", "0")))
    wal = WriteAheadLog(str(tmp_path / "wal"), segment_bytes=256, fsync="never")
    written = []
    for _ in range(120):
        rtype = int(rng.integers(1, 4))
        payload = rng.bytes(int(rng.integers(0, 200)))
        seq = wal.append(rtype, payload)
        written.append((seq, rtype, payload))
    assert wal.segment_count > 1  # rotation actually exercised
    assert wal.last_seq == 120
    wal.close()

    back = WriteAheadLog(str(tmp_path / "wal"), segment_bytes=256, fsync="never")
    assert back.torn_tail_truncations == 0
    assert list(back.replay()) == written
    # replay(after_seq) resumes mid-stream
    assert list(back.replay(after_seq=100)) == written[100:]
    assert back.append(REC_APPEND, b"after-reopen") == 121
    back.close()


@pytest.mark.parametrize("cut", ["mid_header", "mid_payload", "garbage_tail"])
def test_wal_torn_tail_truncated_on_open(tmp_path, cut):
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    for i in range(5):
        wal.append(REC_APPEND, f"rec-{i}".encode() * 10)
    path = wal._segments[-1]["path"]
    size = os.path.getsize(path)
    wal.close()
    with open(path, "r+b") as f:
        if cut == "mid_header":
            f.seek(0, os.SEEK_END)
            f.write(_HDR.pack(0x4C415744, 6, REC_APPEND, 50, 0)[:7])
        elif cut == "mid_payload":
            f.seek(0, os.SEEK_END)
            f.write(_HDR.pack(0x4C415744, 6, REC_APPEND, 50, 0) + b"x" * 20)
        else:
            f.seek(0, os.SEEK_END)
            f.write(b"\xde\xad\xbe\xef" * 8)

    healed = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    assert healed.torn_tail_truncations == 1
    assert os.path.getsize(path) == size  # truncated back to the good bytes
    assert [seq for seq, _, _ in healed.replay()] == [1, 2, 3, 4, 5]
    assert healed.append(REC_APPEND, b"resumes") == 6  # seq continues, no gap
    healed.close()


def test_wal_mid_stream_corruption_fails_replay_loudly(tmp_path):
    """Corruption BEFORE the tail is new damage, not a torn write — replay
    must surface it, never silently skip records."""
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    for i in range(10):
        wal.append(REC_APPEND, f"payload-{i}".encode() * 5)
    path = wal._segments[-1]["path"]
    wal.close()
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 3)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    # the healing open truncates at the first bad record; everything after
    # the flipped byte is unreachable, so the heal drops it
    healed = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    assert healed.torn_tail_truncations == 1
    seqs = [seq for seq, _, _ in healed.replay()]
    assert seqs == list(range(1, len(seqs) + 1)) and len(seqs) < 10
    healed.close()


def test_wal_single_writer_flock_reader_semantics(tmp_path):
    writer = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    writer.append(REC_APPEND, b"one")
    writer.append(REC_TOMBSTONE, b"two")
    reader = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    assert writer.writable and not reader.writable
    # readers replay the committed records but may not mutate anything
    assert [p for _, _, p in reader.replay()] == [b"one", b"two"]
    with pytest.raises(OSError):
        reader.append(REC_APPEND, b"nope")
    assert reader.prune_through(2) == 0
    reader.close()
    writer.close()
    # the writer's close released the flock: next opener owns the log
    heir = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    assert heir.writable
    heir.close()


def test_wal_fsync_interval_policy_uses_injected_clock(tmp_path, monkeypatch):
    """DABT104 discipline: the interval policy reads the injected clock, so a
    fake clock drives the sync schedule deterministically."""
    now = [0.0]
    real_fsync, calls = os.fsync, []
    monkeypatch.setattr(os, "fsync", lambda fd: (calls.append(fd), real_fsync(fd)))
    wal = WriteAheadLog(
        str(tmp_path / "wal"),
        fsync="interval",
        sync_every=1000,
        sync_interval_s=5.0,
        clock=lambda: now[0],
    )
    wal.append(REC_APPEND, b"a")  # first append opens the segment (dir fsync)
    base = len(calls)
    wal.append(REC_APPEND, b"b")
    wal.append(REC_APPEND, b"c")
    assert len(calls) == base  # clock never moved: no fsync yet
    now[0] = 6.0
    wal.append(REC_APPEND, b"d")
    assert len(calls) == base + 1  # interval elapsed on the fake clock
    wal.close()


def test_wal_prune_keeps_active_segment(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal"), segment_bytes=200, fsync="never")
    for i in range(30):
        wal.append(REC_APPEND, b"x" * 64)
    segs = wal.segment_count
    assert segs > 2
    removed = wal.prune_through(wal.last_seq)
    assert removed == segs - 1 and wal.segment_count == 1
    assert wal.append(REC_APPEND, b"still-appendable") == 31
    wal.close()


# -------------------------------------------------------------- fault sites
def test_storage_fault_sites_registered():
    for site in ("disk_write_fail", "disk_torn_write", "snapshot_corrupt"):
        assert site in ALL_SITES


def test_disk_write_fail_fault(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    set_global_injector(FaultInjector({"disk_write_fail": {"fire_on": [1]}}))
    with pytest.raises(OSError):
        wal.append(REC_APPEND, b"doomed")
    # the failed append logged NOTHING; the next one lands at seq 1
    assert wal.append(REC_APPEND, b"fine") == 1
    assert [p for _, _, p in wal.replay()] == [b"fine"]
    wal.close()


def test_disk_torn_write_fault_poisons_then_heals(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    wal.append(REC_APPEND, b"committed")
    set_global_injector(FaultInjector({"disk_torn_write": {"fire_on": [1]}}))
    with pytest.raises(FaultInjected):
        wal.append(REC_APPEND, b"torn-in-half" * 10)
    reset_global_injector()
    with pytest.raises(OSError):  # poisoned: this writer is "dead"
        wal.append(REC_APPEND, b"refused")
    wal.close()
    healed = WriteAheadLog(str(tmp_path / "wal"), fsync="always")
    assert healed.torn_tail_truncations == 1
    assert [p for _, _, p in healed.replay()] == [b"committed"]
    assert healed.append(REC_APPEND, b"recovered") == 2
    healed.close()


def test_snapshot_corrupt_fault_detected_not_trusted(tmp_path):
    store = SnapshotStore(str(tmp_path / "snaps"))
    arrays = {"ids": np.arange(10, dtype=np.int64)}
    store.write(arrays, {"wal_seq": 1})
    set_global_injector(FaultInjector({"snapshot_corrupt": {"fire_on": [1]}}))
    store.write(arrays, {"wal_seq": 2})
    reset_global_injector()
    assert store.verify(os.path.join(store.dir, store.list_snapshots()[0])) != []
    best, fallbacks = store.latest_valid()
    assert fallbacks == 1 and best is not None and best.endswith("snap-000000000001")
    # the corrupt dir was quarantined, not deleted: evidence survives
    assert any(n.endswith(".corrupt") for n in os.listdir(store.dir))


# ---------------------------------------------------------------- snapshots
def test_snapshot_atomicity_tmp_dir_ignored(tmp_path):
    store = SnapshotStore(str(tmp_path / "snaps"))
    store.write({"ids": np.arange(4, dtype=np.int64)}, {"wal_seq": 3})
    # a crashed writer's leftover tmp dir must be invisible to recovery
    os.makedirs(os.path.join(store.dir, ".tmp-snap-000000000009-1234"))
    assert store.list_snapshots() == ["snap-000000000003"]
    best, fallbacks = store.latest_valid()
    assert best is not None and fallbacks == 0


def test_snapshot_manifest_digests_cover_every_artifact(tmp_path):
    store = SnapshotStore(str(tmp_path / "snaps"))
    arrays = {
        "ids": np.arange(6, dtype=np.int64),
        "vectors": np.ones((6, DIM), np.float32),
    }
    path = store.write(arrays, {"wal_seq": 5})
    manifest = json.load(open(os.path.join(path, "manifest.json")))
    assert set(manifest["artifacts"]) == {"ids.npy", "vectors.npy"}
    for fname, spec in manifest["artifacts"].items():
        assert spec["crc32c"] == file_crc32c(os.path.join(path, fname))
    assert store.verify(path) == []


# --------------------------------------------------------------- DurableANN
def test_durable_crash_reopen_search_identity(tmp_path):
    rows = _corpus(300)
    q = rows[::40][:6]
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    dur.ingest(range(200), rows[:200], ledger_key="doc0")
    dur.train(nlist=8, seed=7)
    dur.ingest(range(200, 300), rows[200:], ledger_key="doc1")
    before = _topk(dur, q)
    dur.close()  # close WITHOUT snapshot: recovery is pure WAL replay

    back = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    st = back.durability_stats()
    assert back.recovered and st["replayed_records"] == 3
    assert len(back) == 300 and back.ledger_has("doc0") and back.ledger_has("doc1")
    assert _topk(back, q) == before
    back.close()


def test_durable_snapshot_restore_identity_and_drift_reset(tmp_path):
    """Satellite 3: a restore resets the drift gauge — advisory retrain
    starts from a clean slate on the recovered placement."""
    rows = _corpus(300)
    q = rows[::40][:6]
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    dur.ingest(range(300), rows, ledger_key="doc0")
    dur.train(nlist=8, seed=7)
    before = _topk(dur, q)
    assert dur.snapshot() is not None
    dur.close()

    back = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    st = back.durability_stats()
    assert back.recovered and st["replayed_records"] == 0  # all from snapshot
    assert st["snapshot_count"] == 1 and st["snapshot_age_s"] is not None
    assert _topk(back, q) == before
    ist = back.index.stats()
    assert ist["trained"] and not ist["retrain_advised"]
    assert float(ist["drift_frac"] or 0.0) == 0.0
    back.close()


def test_durable_tombstone_no_resurrection_across_snapshot(tmp_path):
    """Satellite 4: removed rows stay removed when the remove preceded the
    snapshot (compaction point: only live rows are written) AND when it
    landed after it (tombstone replayed from the WAL tail)."""
    rows = _corpus(300)
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    dur.ingest(range(300), rows, ledger_key="doc0")
    dur.train(nlist=8, seed=7)
    dur.remove(list(range(0, 40)))  # before the snapshot boundary
    dur.snapshot()
    dur.remove(list(range(40, 60)))  # after it, lives only in the WAL tail
    dur.close()

    back = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    live = set(back.index.live_ids())
    assert live == set(range(60, 300))
    assert len(back) == 240
    # a broad search never returns a resurrected id
    for q in rows[:60:7]:
        assert not {int(i) for i, _ in back.search(q, k=50)} & set(range(60))
    # the snapshot itself holds only live rows: compaction, not tombstone-list
    snaps = back.snapshots.list_snapshots()
    arrays, _ = back.snapshots.load(os.path.join(back.snapshots.dir, snaps[0]))
    assert set(arrays["ids"].tolist()) == set(range(40, 300))
    back.close()


def test_durable_corrupt_snapshot_falls_back_to_previous(tmp_path):
    rows = _corpus(300)
    q = rows[::40][:6]
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7, snapshot_keep=4)
    dur.ingest(range(200), rows[:200], ledger_key="doc0")
    dur.train(nlist=8, seed=7)
    dur.snapshot()  # good snapshot
    dur.ingest(range(200, 300), rows[200:], ledger_key="doc1")
    set_global_injector(FaultInjector({"snapshot_corrupt": {"fire_on": [1]}}))
    dur.snapshot()  # newest snapshot is silently rotten
    reset_global_injector()
    before = _topk(dur, q)
    dur.close()

    back = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7, snapshot_keep=4)
    st = back.durability_stats()
    assert st["snapshot_fallbacks"] == 1  # detected by digest walk, skipped
    assert len(back) == 300 and _topk(back, q) == before
    back.close()


def test_durable_ledger_dedup_survives_restart(tmp_path):
    rows = _corpus(120)
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    assert dur.ingest(range(60), rows[:60], ledger_key="doc:1:v1") == 60
    assert dur.ingest(range(60), rows[:60], ledger_key="doc:1:v1") == 0
    dur.snapshot()
    dur.close()
    back = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    assert back.ingest(range(60), rows[:60], ledger_key="doc:1:v1") == 0
    assert back.durability_stats()["ledger_dedup_hits"] == 1
    assert back.ingest(range(60, 120), rows[60:], ledger_key="doc:1:v2") == 60
    live = back.index.live_ids()
    assert len(live) == len(set(live)) == 120  # zero duplicate vectors
    back.close()


def test_durable_untrained_roundtrip_exact_tier(tmp_path):
    rows = _corpus(50)
    q = rows[::9][:4]
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    dur.ingest(range(50), rows)
    before = _topk(dur, q, k=5)
    dur.snapshot()
    dur.close()
    back = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    assert not back.index.stats()["trained"]
    assert _topk(back, q, k=5) == before
    back.close()


def test_durable_read_only_opener_serves_without_mutating(tmp_path):
    rows = _corpus(80)
    writer = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    writer.ingest(range(80), rows, ledger_key="doc0")
    reader = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    assert writer.writable and not reader.writable
    assert len(reader) == 80  # recovered the committed state
    with pytest.raises(OSError):
        reader.ingest(range(80, 90), rows[:10])
    with pytest.raises(OSError):
        reader.snapshot()
    reader.close()
    writer.close()


# -------------------------------------------------------------- mmap tier
def test_mmap_row_store_grow_preserves_rows(tmp_path):
    store = MmapRowStore(str(tmp_path / "rows.mmap"))
    a = store.alloc((4, 8))
    a[:] = np.arange(32, dtype=np.float32).reshape(4, 8)
    a.flush()
    b = store.alloc((16, 8))
    assert isinstance(b, np.memmap)
    np.testing.assert_array_equal(b[:4], np.arange(32, dtype=np.float32).reshape(4, 8))


def test_durable_mmap_rows_roundtrip_and_restage(tmp_path):
    rows = _corpus(200)
    q = rows[::40][:4]
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7, mmap_rows=True)
    dur.ingest(range(200), rows, ledger_key="doc0")
    dur.train(nlist=8, seed=7)
    # the disk tier must survive the retrain's restage, not revert to RAM
    assert isinstance(dur.index._mat, np.memmap)
    before = _topk(dur, q)
    dur.snapshot()
    dur.close()
    back = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7, mmap_rows=True)
    assert isinstance(back.index._mat, np.memmap)
    assert _topk(back, q) == before
    back.close()


# ------------------------------------------------------------------ verify
def test_verify_dir_clean_and_corrupt(tmp_path):
    rows = _corpus(100)
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    dur.ingest(range(100), rows, ledger_key="doc0")
    dur.snapshot()
    dur.ingest(range(100, 110), _corpus(10, seed=9), ledger_key="doc1")
    dur.close()
    report = verify_dir(str(tmp_path / "d"))
    assert report["ok"] and report["wal_records"] >= 1 and report["snapshots"]

    # flip one byte inside a snapshot artifact: the digest walk must object
    snap = os.path.join(str(tmp_path / "d"), "snapshots", report["snapshots"][0]["name"])
    victim = next(
        os.path.join(snap, n) for n in sorted(os.listdir(snap)) if n.endswith(".npy")
    )
    with open(victim, "r+b") as f:
        f.seek(os.path.getsize(victim) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    report = verify_dir(str(tmp_path / "d"))
    assert not report["ok"] and report["problems"]


def test_verify_dir_flags_wal_crc_damage(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "d" / "wal"), fsync="always")
    for i in range(6):
        wal.append(REC_APPEND, f"record-{i}".encode() * 8)
    path = wal._segments[-1]["path"]
    wal.close()
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    report = verify_dir(str(tmp_path / "d"))
    assert not report["ok"] and any("wal-" in p for p in report["problems"])


# --------------------------------------------------------------------- CLI
def _cli_args(argv):
    from django_assistant_bot_tpu.cli import ann as ann_cli

    p = argparse.ArgumentParser()
    ann_cli.add_parser(p.add_subparsers(dest="command"))
    return p.parse_args(["ann", *argv])


def test_cli_snapshot_restore_verify_roundtrip(tmp_path, capsys):
    from django_assistant_bot_tpu.cli import ann as ann_cli

    rows = _corpus(150)
    d = str(tmp_path / "d")
    dur = DurableANN(d, dim=DIM, fsync="always", seed=7)
    dur.ingest(range(150), rows, ledger_key="doc0")
    dur.train(nlist=8, seed=7)
    dur.close()

    assert ann_cli.run(_cli_args(["verify", "--dir", d])) == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True

    assert ann_cli.run(_cli_args(["snapshot", "--dir", d, "--dim", str(DIM)])) == 0
    st = json.loads(capsys.readouterr().out)
    assert st["snapshot_count"] == 1 and st["rows"] == 150

    assert ann_cli.run(_cli_args(["restore", "--dir", d, "--dim", str(DIM)])) == 0
    st = json.loads(capsys.readouterr().out)
    assert st["recovered"] and st["rows"] == 150 and st["retrain_advised"] is False

    # corrupt an artifact: verify must exit non-zero (satellite 2's contract)
    snaps = os.listdir(os.path.join(d, "snapshots"))
    snap = os.path.join(d, "snapshots", sorted(snaps)[0])
    victim = next(
        os.path.join(snap, n) for n in sorted(os.listdir(snap)) if n.endswith(".npy")
    )
    with open(victim, "r+b") as f:
        f.seek(os.path.getsize(victim) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    assert ann_cli.run(_cli_args(["verify", "--dir", d])) == 1
    assert json.loads(capsys.readouterr().out)["ok"] is False


# ----------------------------------------------------------------- metrics
def test_durability_gauges_rendered(tmp_path):
    from django_assistant_bot_tpu.rag import index_registry
    from django_assistant_bot_tpu.serving.obs import (
        _Exposition,
        _render_rag_plane,
        parse_prometheus_text,
    )

    rows = _corpus(150)
    dur = DurableANN(str(tmp_path / "d"), dim=DIM, fsync="always", seed=7)
    dur.ingest(range(150), rows, ledger_key="doc0")
    dur.train(nlist=8, seed=7)
    dur.snapshot()
    index_registry.reset_indexes()
    try:
        with index_registry._lock:
            index_registry._indexes[("Question", "embedding")] = dur
        x = _Exposition()
        _render_rag_plane(x)
        fams = parse_prometheus_text(x.render())
        lab = {"index": "Question.embedding"}
        assert fams["dabt_ann_wal_records"]["samples"][0][1:] == (lab, 2.0)
        assert fams["dabt_ann_snapshot_age_s"]["samples"][0][1] == lab
        assert fams["dabt_ann_writable"]["samples"][0][2] == 1.0
        assert fams["dabt_ann_snapshot_count"]["samples"][0][2] == 1.0
        assert fams["dabt_ann_snapshot_fallbacks_total"]["samples"][0][2] == 0.0
        assert fams["dabt_ann_ledger_entries"]["samples"][0][2] == 1.0
    finally:
        index_registry.reset_indexes()
        dur.close()


# ---------------------------------------------------------------- registry
def test_registry_routes_durable_and_ingest_document(tmp_db, tmp_path):
    import asyncio

    from django_assistant_bot_tpu.ai.providers.echo import HashEmbedder
    from django_assistant_bot_tpu.conf import settings
    from django_assistant_bot_tpu.rag.index_registry import (
        get_index,
        ingest_document,
        invalidate_index,
        remove_rows,
        reset_indexes,
    )
    from django_assistant_bot_tpu.storage import models

    reset_indexes()
    bot = models.Bot.objects.create(codename="dur-bot")
    wiki = models.WikiDocument.objects.create(bot=bot, title="w")
    doc = models.Document.objects.create(wiki=wiki, name="d0", content="c")
    emb = HashEmbedder(dim=settings.EMBEDDING_DIM)
    center = np.asarray(asyncio.run(emb.embeddings(["topic"]))[0])
    rng = np.random.default_rng(0)
    for i in range(24):
        models.Question.objects.create(
            document=doc, text=f"q{i}", order=i,
            embedding=(center + rng.normal(size=center.shape) * 0.05).astype(np.float32),
        )
    try:
        with settings.override(
            ANN_THRESHOLD=1, ANN_DURABLE_DIR=str(tmp_path / "durable")
        ):
            idx = get_index(models.Question)
            assert isinstance(idx, DurableANN) and idx.writable and len(idx) == 24

            doc2 = models.Document.objects.create(wiki=wiki, name="d1", content="c")
            ids2, vecs2 = [], []
            for i in range(6):
                q = models.Question.objects.create(
                    document=doc2, text=f"r{i}", order=i,
                    embedding=(center + rng.normal(size=center.shape) * 0.05).astype(np.float32),
                )
                ids2.append(q.id)
                vecs2.append(q.embedding)
            key = f"Question:{doc2.id}:{max(ids2)}:{len(ids2)}"
            assert ingest_document(models.Question, "embedding", key, ids2, np.stack(vecs2))
            # a worker re-run after crash: same key no-ops on the ledger
            assert not ingest_document(models.Question, "embedding", key, ids2, np.stack(vecs2))
            # the in-place ingest adopted its own generation: NO rebuild
            assert get_index(models.Question) is idx and len(idx) == 30

            drop = ids2[:2]
            for q in models.Question.objects.filter(id__in=drop):
                q.delete()
            remove_rows(models.Question, "embedding", drop)
            assert get_index(models.Question) is idx and len(idx) == 28

            # an EXTERNAL invalidation (another worker moved the DB): this
            # process owns the flock, so refresh reconciles in place rather
            # than deadlocking into a read-only second instance
            invalidate_index(models.Question)
            assert get_index(models.Question) is idx
    finally:
        reset_indexes()
        idx.close()


# -------------------------------------------------------------- kill-replay
# kill-replay child: ingests ledgered documents one at a time into a durable
# index, logging each applied doc's top-k AFTER the WAL fsync — the parent
# SIGKILLs it mid-stream, so the last complete line is the pre-crash truth
# the recovered index must reproduce (storage/durable.py, docs/DURABILITY.md)
_DURABLE_CHILD = """
import json, os, sys, time
import numpy as np
from django_assistant_bot_tpu.storage.ann import make_clustered
from django_assistant_bot_tpu.storage.durable import DurableANN

dirp, progress, docs, rows_per, dim = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5])
)
rows = make_clustered(docs * rows_per, dim, seed=7)
q = rows[:: max(1, docs * rows_per // 8)][:8]
dur = DurableANN(dirp, dim=dim, fsync="always", snapshot_every_records=6, seed=7)
pf = open(progress, "a")
for d in range(docs):
    ids = list(range(d * rows_per, (d + 1) * rows_per))
    dur.ingest(ids, rows[ids], ledger_key=f"doc{d}")
    if d == 3:
        dur.train(nlist=8, seed=7)
    topk = [[int(i) for i, _ in dur.search(qq, k=10)] for qq in q]
    pf.write(json.dumps({"doc": d, "n": len(dur), "topk": topk}) + "\\n")
    pf.flush()
    os.fsync(pf.fileno())
    time.sleep(0.05)
"""


def _kill_replay() -> dict:
    """Durability kill-replay (storage/durable.py evidence).

    A child process live-ingests 24 ledgered documents into a WAL+snapshot
    backed index and is SIGKILLed mid-stream (>= 8 applied).  The parent then
    recovers the SAME directory — latest valid snapshot + WAL-tail replay —
    and asserts the three durability claims: (1) recovered top-k is identical
    to the child's last fsynced pre-crash answer on the pinned corpus, (2)
    zero duplicate vectors, (3) re-ingesting EVERY document with the original
    ledger keys no-ops exactly the already-applied ones and lands the rest,
    finishing at the full corpus.  Recovery wall time and replayed-record
    counts ride along as the operator-facing cost of the crash.
    """
    import signal
    import subprocess
    import sys
    import tempfile
    import time

    docs, rows_per, dim = 24, 32, 64
    out: dict = {"durable_ingested_docs": docs}
    with tempfile.TemporaryDirectory(prefix="dabt-durable-") as tmp:
        dur_dir = os.path.join(tmp, "index")
        progress = os.path.join(tmp, "progress.jsonl")
        open(progress, "w").close()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        child = subprocess.Popen(
            [sys.executable, "-c", _DURABLE_CHILD, dur_dir, progress, str(docs), str(rows_per), str(dim)],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE,
        )
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            lines = open(progress).read().splitlines()
            if len(lines) >= 8 or child.poll() is not None:
                break
            time.sleep(0.02)
        if child.poll() is None:
            child.send_signal(signal.SIGKILL)  # no atexit, no flush — a real crash
        else:
            err = (child.stderr.read() or b"").decode(errors="replace")
            raise RuntimeError(f"durable child exited early rc={child.returncode}: {err[-2000:]}")
        child.wait()
        pre_crash = [
            json.loads(ln) for ln in open(progress).read().splitlines() if ln.strip()
        ]

        rows = make_clustered(docs * rows_per, dim, seed=7)
        q = rows[:: max(1, docs * rows_per // 8)][:8]
        t0 = time.perf_counter()
        dur = DurableANN(dur_dir, dim=dim, fsync="always", seed=7)
        st = dur.durability_stats()
        out["durable_recovery_s"] = round(time.perf_counter() - t0, 3)
        out["durable_replayed_records"] = st["replayed_records"]
        out["durable_wal_records"] = st["wal_records"]
        out["durable_snapshot_count"] = st["snapshot_count"]
        applied = sum(1 for d in range(docs) if dur.ledger_has(f"doc{d}"))
        out["durable_recovered_docs"] = applied

        live = dur.index.live_ids()
        expect = set(range(applied * rows_per))
        out["durable_duplicate_vectors"] = len(live) - len(set(live))
        assert set(live) == expect, "recovered id set != ledgered documents"

        topk = [[int(i) for i, _ in dur.search(qq, k=10)] for qq in q]
        truth = next((p["topk"] for p in pre_crash if p["doc"] == applied - 1), None)
        if truth is None:
            # crash landed between the WAL fsync and the progress fsync: the
            # last applied doc has no logged answer, so rebuild the pre-crash
            # index from scratch (same data/order/seed => same placement)
            ctl = DurableANN(os.path.join(tmp, "control"), dim=dim, fsync="never", snapshot_every_records=6, seed=7)
            for d in range(applied):
                ids = list(range(d * rows_per, (d + 1) * rows_per))
                ctl.ingest(ids, rows[ids], ledger_key=f"doc{d}")
                if d == 3:
                    ctl.train(nlist=8, seed=7)
            truth = [[int(i) for i, _ in ctl.search(qq, k=10)] for qq in q]
            ctl.close()
        out["durable_topk_identical"] = bool(topk == truth)

        # crash-resume: the worker re-runs its WHOLE ingest loop; applied
        # docs must no-op on the ledger, the rest must land exactly once
        deduped = 0
        for d in range(docs):
            ids = list(range(d * rows_per, (d + 1) * rows_per))
            n = dur.ingest(ids, rows[ids], ledger_key=f"doc{d}")
            deduped += int(n == 0)
        out["durable_resume_dedup_docs"] = deduped
        assert deduped == applied, "ledger dedup did not cover the applied docs"
        live = dur.index.live_ids()
        assert len(live) == docs * rows_per and len(set(live)) == len(live)
        out["durable_duplicate_vectors"] += len(live) - len(set(live))
        dur.close()
    return out


@pytest.mark.slow
def test_durable_kill_replay_subprocess():
    """A child process is SIGKILLed mid-ingest, the parent recovers the
    directory and must reproduce the child's last fsynced pre-crash top-k
    exactly, with zero duplicate vectors, and a full re-run of the ingest
    loop must dedup every already-applied document."""
    out = _kill_replay()
    assert out["durable_recovered_docs"] >= 8
    assert out["durable_recovered_docs"] < out["durable_ingested_docs"]
    assert out["durable_topk_identical"] is True
    assert out["durable_duplicate_vectors"] == 0
    assert out["durable_resume_dedup_docs"] == out["durable_recovered_docs"]
    assert out["durable_recovery_s"] < 60


def test_wal_record_header_layout_pinned():
    """The on-disk header is a contract (docs/DURABILITY.md): magic u32, seq
    u64, type u8, payload-len u32, crc u32 — little-endian, 21 bytes."""
    assert _HDR.size == struct.calcsize("<IQBII") == 21
    assert (REC_APPEND, REC_TOMBSTONE, REC_INSTALL) == (1, 2, 3)
