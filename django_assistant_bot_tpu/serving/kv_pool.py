"""Host-side page allocator for the paged KV memory plane.

A cache that reserves one contiguous ``[max_seq_len]`` KV region per decode
slot lets HBM *capacity* — not bandwidth — cap concurrency at long context: a
slot serving a 200-token dialog turn pins the same multi-MB cache row as one
serving a 16k-token RAG prompt.  The paged plane (vLLM-style block tables)
carves the byte budget into fixed-size pages and reserves only
``ceil((prompt_len + max_tokens) / page_size)`` pages per request, so short
traffic packs many more concurrent slots into the same HBM.

This module is the *host* half: pure-Python page bookkeeping (free list,
refcounts, the shareable-prefix registry), unit-testable without a device.
The device half — the ``[L, P, KH, page, D]`` pool tensors, block-table gather
attention, page-granular prefill writes — lives in ``models/llama.py`` and
``ops/attention.py``; the engine (``serving/engine.py``) wires the two
together.  See docs/KV_PAGING.md for the full layout contract.

Prefix sharing (subsumes the r4 whole-prefix LRU):

- After a request with a declared shared prefix (system prompt + packed RAG
  context — the reference re-sends that block every turn) finishes its
  prefill, the engine *registers* the pages covering the prefix here.  The
  registry holds one refcount per page, so the pages stay alive after the
  owning request frees its slot.
- A later request whose prompt starts with a registered prefix *shares* the
  fully-covered pages read-only (one incref each, zero copies, zero model
  compute) and takes a **copy-on-write** clone of the boundary page the
  prefix only partially fills — its own suffix K/V lands there, so the page
  cannot be shared physically.  Positions below the prefix length in the
  clone are the owner's prefix K/V (valid for every consumer — RoPE is
  absolute-position), positions at/above it are overwritten by the sharer's
  own suffix prefill before they are ever unmasked.
- Entries LRU-evict past ``max_shared_bytes`` (or ``max_entries``), and
  :meth:`alloc` evicts on demand when the free list alone cannot satisfy a
  request — cached prefixes are a *scavengeable* use of free HBM, never a
  reason to shed traffic.

Two-tier durability (docs/KV_PAGING.md "Tiered KV"): with a
:class:`HostKVTier` bound, an evicted registry entry's pages are *spilled* to
host DRAM (numpy buffers under their own byte budget, then optionally disk
under ``DABT_KV_SPILL_DIR``) instead of dropped, and registration
write-through keeps a host copy of every warm prefix — so a crash-only engine
restart (which resets the device pool) or plain LRU pressure loses the HBM
copy but not the 0.9 s of prefill it encodes.  The engine restores host
entries into fresh pages ahead of a suffix prefill (bit-identical to a cold
full prefill — the bytes are the bytes).

Thread contract: all methods are engine-thread-only except :meth:`stats`,
:meth:`available` and :meth:`holds_prefix`, which only read counters and take
the internal lock (the scheduler's KV-pressure admission test calls them from
client threads).  Tier-transition events (``on_event``) always fire OUTSIDE
the allocator/tier locks, so a listener (the engine's flight recorder, the
router's fleet prefix registry) can take its own lock without creating a
cross-component lock order — runtime-checked by the lock witness.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import logging
import os
import re
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..storage.integrity import crc32c, entry_crc32c  # noqa: F401 - re-exported

logger = logging.getLogger(__name__)

# tier names as they appear in events, the fleet registry, and /metrics
TIER_HBM = "hbm"
TIER_HOST = "host"
TIER_DISK = "disk"

# Version stamp carried by every HostPrefixEntry that crosses a process or
# build boundary: the snapshot/absorb migration path, the fleet wire codec
# (serving/fleet.py), and disk spill files.  Bump whenever the entry layout
# or dtype-tagging scheme changes; absorb and the wire decoder REJECT
# unknown versions (WireVersionError) instead of reinterpreting bytes a
# different build wrote — a silently misread fp8 page corrupts generations,
# a loud failure re-prefills.
#
# v1: magic + JSON header + raw k/v bytes.
# v2: v1 + a CRC-32C of the page payload in the header/file, verified on
#     decode, absorb, and disk promote.  Decoders ACCEPT the prior version
#     (a v1 payload simply carries no checksum) so a rolling fleet upgrade
#     never partitions on wire format; encoders always write the current one.
KV_WIRE_VERSION = 2
KV_WIRE_COMPAT_VERSIONS = (1, 2)


class WireDecodeError(ValueError):
    """A KV wire payload failed to decode: truncated envelope, bad magic,
    unreadable header, or body/metadata mismatch.  Subclasses ValueError so
    pre-existing callers that caught ValueError keep working."""


class WireVersionError(WireDecodeError):
    """A KV snapshot/wire payload carries an unknown ``wire_version`` — the
    writer was a different build.  Failing loudly beats corrupting pages."""


class WireIntegrityError(WireDecodeError):
    """A KV payload's CRC-32C does not match its bytes — corruption in
    flight or at rest.  The payload is rejected wholesale: a garbage page
    absorbed into the pool poisons every generation that shares the prefix,
    while a loud reject costs one re-fetch or one cold prefill."""


# The CRC-32C implementation itself (``crc32c`` / ``entry_crc32c``) lives in
# storage/integrity.py — one copy shared by this disk-spill path, the fleet
# wire v2 codec, and the ANN durability WAL.  Imported + re-exported above so
# pre-unification importers of ``kv_pool.crc32c`` keep working.

# process-wide sequence for unique spill tmp filenames (itertools.count is
# GIL-atomic; the pid in the final path isolates across processes)
_TMP_SEQ = itertools.count()


@dataclasses.dataclass
class SharedPrefix:
    """One registered shareable prefix.

    ``pages`` are the physical pages covering prompt positions
    ``[0, length)`` in logical order; all but possibly the last are full
    (``page_size`` tokens).  ``full_pages`` of them are safe to share
    physically; a partial tail page must be COW-cloned by consumers."""

    pages: Tuple[int, ...]
    length: int  # true token count of the prefix
    full_pages: int  # pages fully covered by the prefix (shareable in place)


@dataclasses.dataclass
class HostPrefixEntry:
    """One prefix spilled to the host tier: the page contents as numpy arrays
    (``[L, n_pages, KH, page, D]`` each, the device pool's dtype — fp8 pools
    spill as ml_dtypes float8, bit-exact), plus the metadata a restore needs.
    ``nbytes`` is the byte-ledger charge; ``pages`` the page count a restore
    will re-occupy in HBM."""

    key: tuple
    length: int
    k: Any  # np.ndarray
    v: Any  # np.ndarray
    nbytes: int
    pages: int
    # build-compatibility stamp (see KV_WIRE_VERSION): absorb() refuses
    # entries stamped by a different layout generation
    wire_version: int = KV_WIRE_VERSION
    # CRC-32C over the k+v page bytes (entry_crc32c) for entries that crossed
    # a wire or disk boundary; None for entries minted in-process.  absorb()
    # re-verifies any entry that carries one.
    crc32c: Optional[int] = None


class HostKVTier:
    """Host-DRAM (and optional disk) store for spilled prefix K/V.

    LRU over ``max_bytes`` of numpy buffers; entries evicted past the budget
    *demote to disk* when ``spill_dir`` is set (one ``.npz`` per entry, raw
    byte views so fp8/bf16 dtypes round-trip without numpy support), else
    drop.  ``lookup`` promotes a disk hit back to host DRAM before returning
    it, so a restore always reads from memory.

    Thread contract: every method takes the internal lock and is safe from
    any thread (the engine thread spills/restores; the router's migration
    path snapshots/absorbs; /healthz reads stats).  ``on_event`` callbacks
    fire OUTSIDE the lock.  Two tiers never nest locks: migration snapshots
    the source (copy under its lock, release) before absorbing into the
    target — the lock witness would convict same-class nesting otherwise.
    """

    def __init__(
        self,
        max_bytes: int,
        *,
        page_size: int = 0,
        page_bytes: int = 0,
        spill_dir: Optional[str] = None,
        max_disk_bytes: int = 4 << 30,
        name: str = "kv-host",
    ):
        self.max_bytes = max(0, int(max_bytes))
        self.page_size = max(1, int(page_size) or 1)
        # informational metadata only (one HBM page's byte size, for sizing
        # probes/tests): every tier budget charges an entry's OWN nbytes —
        # this never changes eviction or accounting behavior
        self.page_bytes = max(0, int(page_bytes))
        self.spill_dir = spill_dir or None
        self.max_disk_bytes = max(0, int(max_disk_bytes))
        self.name = name
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[tuple, HostPrefixEntry]" = (
            collections.OrderedDict()
        )
        self._bytes = 0
        # disk index: key -> (path, length, nbytes, pages); LRU like the host
        # dict so the disk budget evicts the coldest file first
        self._disk: "collections.OrderedDict[tuple, tuple]" = (
            collections.OrderedDict()
        )
        self._disk_bytes = 0
        # counters (kv_stats / /metrics dabt_kv_tier_*)
        self.spills = 0  # entries written into the host tier
        self.restores = 0  # entries served back for a device restore
        self.host_evictions = 0  # entries leaving host DRAM (to disk or dropped)
        self.disk_spills = 0  # entries demoted to disk files
        self.disk_promotes = 0  # disk entries promoted back to host DRAM
        self.dropped = 0  # entries lost (no disk tier / disk failure / budget)
        self.migrated_in = 0  # entries absorbed from a dying replica
        self.integrity_rejects = 0  # CRC-mismatched entries refused (wire/disk)
        # tier-transition listener: fn(event, key, length, pages).  Fired
        # OUTSIDE the lock; set once at wiring time (engine/router).
        self.on_event: Optional[Callable[..., None]] = None
        # the disk index is in-memory: files left by a PREVIOUS process
        # under this tier's namespace are unreachable (and would otherwise
        # accumulate past max_disk_bytes forever) — sweep them at boot.
        # Other replicas' namespaces in a shared spill dir are untouched.
        if self.spill_dir:
            self._sweep_stale_namespace()

    def _sweep_stale_namespace(self) -> None:
        """Reclaim files a previous PROCESS left under this tier's name.

        Filenames carry the writing process's pid (``-p<pid>-``), so a file
        is stale only when that process is gone (or the pid is ours — we
        just booted, so anything under our recycled pid is a dead
        predecessor's).  A LIVE sibling process serving the same replica
        name out of a shared spill dir keeps its files; pidless old-format
        names are always stale."""
        prefix = f"kvspill-{self._safe_name()}-"
        me = os.getpid()
        try:
            for entry in os.scandir(self.spill_dir):
                if not (
                    entry.name.startswith(prefix)
                    and entry.name.endswith((".npz", ".tmp.npz"))
                ):
                    continue
                m = re.match(r"^p(\d+)-", entry.name[len(prefix):])
                if m is not None:
                    pid = int(m.group(1))
                    if pid != me and self._pid_alive(pid):
                        continue
                try:
                    os.remove(entry.path)
                except OSError:
                    pass
        except OSError:
            pass  # dir may not exist yet — created on first demote

    @staticmethod
    def _pid_alive(pid: int) -> bool:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return False
        except OSError:
            return True  # EPERM and friends: someone lives there
        return True

    def _safe_name(self) -> str:
        return "".join(
            c if (c.isalnum() or c in "._-") else "_" for c in self.name
        )

    # ------------------------------------------------------------------ events
    def _fire(self, events: List[tuple]) -> None:
        cb = self.on_event
        if cb is None:
            return
        for ev, key, length, pages in events:
            try:
                cb(ev, key, length, pages)
            except Exception:  # listener bugs must never break the tier
                logger.exception("host-tier event listener failed (%s)", ev)

    # ------------------------------------------------------------------- write
    def has(self, key: tuple) -> bool:
        with self._lock:
            return key in self._entries or key in self._disk

    def put(self, key: tuple, length: int, k, v) -> bool:
        """Store one spilled prefix (an existing key is LRU-touched only —
        the bytes are the same bytes).  Returns False when the tier is
        disabled, the entry alone exceeds the budget, or the key was already
        present.  Demotion file writes happen OUTSIDE the lock."""
        if self.max_bytes <= 0:
            return False
        k = np.asarray(k)
        v = np.asarray(v)
        nbytes = int(k.nbytes) + int(v.nbytes)
        pages = -(-int(length) // self.page_size)
        events: List[tuple] = []
        demote: List[Tuple[tuple, HostPrefixEntry]] = []
        stale: List[str] = []
        stored = False
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return False
            if key in self._disk:
                # a fresher device copy supersedes the disk file; the
                # disk_drop event clears the stale TIER_DISK holding in the
                # fleet registry (the host_put below re-adds TIER_HOST)
                path, ln, nb, pg = self._disk.pop(key)
                self._disk_bytes -= nb
                events.append(("disk_drop", key, ln, pg))
                stale.append(path)
            if nbytes > self.max_bytes:
                self.dropped += 1
                events.append(("host_put_too_large", key, length, pages))
            else:
                self._entries[key] = HostPrefixEntry(
                    key=key, length=int(length), k=k, v=v, nbytes=nbytes, pages=pages
                )
                self._bytes += nbytes
                self.spills += 1
                events.append(("host_put", key, length, pages))
                self._evict_host_locked(events, demote)
                stored = True
        self._remove_files(stale)
        self._demote(demote, events)
        self._fire(events)
        return stored

    def _evict_host_locked(
        self,
        events: List[tuple],
        demote: List[Tuple[tuple, HostPrefixEntry]],
    ) -> None:
        """Pop entries past the byte budget.  With a disk tier the victims
        are handed to the caller for demotion AFTER the lock releases (the
        file write must not stall dispatch peeks / admission stats /
        scrapes, which all take this lock); without one they drop here."""
        while self._entries and self._bytes > self.max_bytes:
            old_key, ent = self._entries.popitem(last=False)
            self._bytes -= ent.nbytes
            self.host_evictions += 1
            if self.spill_dir:
                demote.append((old_key, ent))
            else:
                self.dropped += 1
                events.append(("host_evict_dropped", old_key, ent.length, ent.pages))

    # -------------------------------------------------------------------- disk
    @staticmethod
    def _key_digest(key: tuple) -> str:
        h = hashlib.sha1()
        for t in key:
            h.update(int(t).to_bytes(4, "little", signed=True))
        return h.hexdigest()[:24]

    @staticmethod
    def _remove_files(paths: List[str]) -> None:
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass

    def _write_disk_file(self, key: tuple, ent: HostPrefixEntry) -> Optional[str]:
        """Write one entry to a ``.npz`` under ``spill_dir`` (no lock held).
        Raw uint8 views + dtype strings: fp8/bf16 pools round-trip
        bit-exactly even where numpy's own save path would balk.  The
        filename is namespaced by this TIER's name AND the process pid:
        replicas sharing one spill dir (one DABT_KV_SPILL_DIR for the
        fleet) — or two processes serving the SAME replica name out of it —
        must not overwrite, promote-and-delete, or boot-sweep each other's
        files.  Returns None (the caller drops the entry) on any I/O
        failure — disk is best-effort durability, never a crash path."""
        path = os.path.join(
            self.spill_dir,
            f"kvspill-{self._safe_name()}-p{os.getpid()}-"
            f"{self._key_digest(key)}.npz",
        )
        try:
            os.makedirs(self.spill_dir, exist_ok=True)
            # per-write unique tmp name: two concurrent demotes of the SAME
            # key (evict → absorb re-put → evict again) must not interleave
            # writes into one tmp file and os.replace a corrupt archive
            tmp = f"{path}.{next(_TMP_SEQ)}.tmp.npz"
            np.savez(
                tmp,
                key=np.asarray(key, np.int64),
                length=np.asarray(ent.length, np.int64),
                k_bytes=np.ascontiguousarray(ent.k).view(np.uint8),
                v_bytes=np.ascontiguousarray(ent.v).view(np.uint8),
                k_shape=np.asarray(ent.k.shape, np.int64),
                v_shape=np.asarray(ent.v.shape, np.int64),
                dtype=np.asarray(str(ent.k.dtype)),
                wire_version=np.asarray(KV_WIRE_VERSION, np.int64),
                crc32c=np.asarray(entry_crc32c(ent.k, ent.v), np.int64),
            )
            os.replace(tmp, path)
        except (OSError, ValueError) as e:
            logger.warning("KV disk spill failed (%s): %s", path, e)
            return None
        return path

    def _demote(
        self,
        demote: List[Tuple[tuple, HostPrefixEntry]],
        events: List[tuple],
    ) -> None:
        """Demote evicted entries to disk: file writes run with NO lock
        held, then each file is indexed under the lock (a demoting entry is
        briefly in neither map — a concurrent lookup sees an honest miss,
        which costs at worst one redundant prefill)."""
        stale: List[str] = []
        for key, ent in demote:
            path = self._write_disk_file(key, ent)
            with self._lock:
                if path is None:
                    self.dropped += 1
                    events.append(
                        ("host_evict_dropped", key, ent.length, ent.pages)
                    )
                    continue
                if key in self._entries:
                    # a concurrent put re-stored the key while the file was
                    # being written — the host copy supersedes the file
                    stale.append(path)
                    continue
                if key in self._disk:
                    old_path, _, nb, _ = self._disk.pop(key)
                    self._disk_bytes -= nb
                    if old_path != path:
                        stale.append(old_path)
                self._disk[key] = (path, ent.length, ent.nbytes, ent.pages)
                self._disk_bytes += ent.nbytes
                self.disk_spills += 1
                events.append(("host_evict_disk", key, ent.length, ent.pages))
                while self._disk and self._disk_bytes > self.max_disk_bytes:
                    old_key, (old_path, ln, nb, pg) = self._disk.popitem(
                        last=False
                    )
                    self._disk_bytes -= nb
                    self.dropped += 1
                    events.append(("disk_drop", old_key, ln, pg))
                    stale.append(old_path)
        self._remove_files(stale)

    def _load_disk_file(self, path: str, key: tuple, length: int, nbytes: int, pages: int):
        """Read one demoted entry back (no lock held).  None on failure.
        A file stamped with an unknown ``wire_version`` (a different build
        wrote into a shared spill dir) is dropped loudly — an honest miss
        costs one re-prefill, a misread dtype layout corrupts pages.  A file
        whose stored CRC-32C no longer matches its bytes (at-rest corruption)
        is likewise dropped, counted in ``integrity_rejects``; files from the
        pre-CRC layout carry no checksum and load as before."""
        try:
            with np.load(path, allow_pickle=False) as z:
                if "wire_version" in z.files:
                    ver = int(z["wire_version"])
                    if ver not in KV_WIRE_COMPAT_VERSIONS:
                        logger.error(
                            "KV disk file %s has wire_version %d (this build "
                            "accepts %s) — written by a different build; "
                            "dropping entry",
                            path, ver, KV_WIRE_COMPAT_VERSIONS,
                        )
                        return None
                stored_crc = int(z["crc32c"]) if "crc32c" in z.files else None
                dtype = np.dtype(str(z["dtype"]))
                k = z["k_bytes"].view(dtype).reshape(z["k_shape"])
                v = z["v_bytes"].view(dtype).reshape(z["v_shape"])
            if stored_crc is not None and entry_crc32c(k, v) != stored_crc:
                logger.error(
                    "KV disk file %s failed its CRC-32C — corrupt at rest; "
                    "dropping entry (re-prefill beats a garbage page)", path,
                )
                with self._lock:
                    self.integrity_rejects += 1
                return None
            return HostPrefixEntry(
                key=key, length=int(length), k=k, v=v,
                nbytes=int(nbytes), pages=int(pages),
                crc32c=stored_crc,
            )
        except (OSError, ValueError, KeyError) as e:
            logger.warning("KV disk promote failed (%s): %s", path, e)
            return None

    # -------------------------------------------------------------------- read
    def _best_match_locked(
        self, prompt_ids: Sequence[int], n: int
    ) -> Tuple[Optional[tuple], int, bool]:
        """LONGEST stored prefix of ``prompt_ids`` across host DRAM and the
        disk index (caller holds the lock; ``n = len(prompt_ids) > 0``).
        Returns ``(key, length, on_disk)`` or ``(None, -1, False)``.  O(1)
        first/last-token rejection ahead of the O(length) tuple slice — a
        queued head re-runs this scan every admission attempt, and the
        router fallback peek runs it per dispatch, under the tier lock."""
        first = prompt_ids[0]
        best_key, best_len, on_disk = None, -1, False
        for key, ent in self._entries.items():
            ln = ent.length
            if (
                ln < n
                and ln > best_len
                and key[0] == first
                and key[-1] == prompt_ids[ln - 1]
                and tuple(prompt_ids[:ln]) == key
            ):
                best_key, best_len, on_disk = key, ln, False
        for key, (_path, length, _nbytes, _pages) in self._disk.items():
            if (
                length < n
                and length > best_len
                and key[0] == first
                and key[-1] == prompt_ids[length - 1]
                and tuple(prompt_ids[:length]) == key
            ):
                best_key, best_len, on_disk = key, length, True
        return best_key, best_len, on_disk

    def lookup(
        self, prompt_ids: Sequence[int], prefix_len: int, *, min_tokens: int = 1
    ) -> Optional[HostPrefixEntry]:
        """LONGEST stored prefix this prompt starts with (host DRAM first,
        then disk — a disk winner is promoted back to host DRAM, the
        one-time file read running OUTSIDE the lock).  Deliberately does NOT
        count a restore or LRU-touch: a queued head re-runs the lookup on
        every admission attempt, so the engine reports the serve via
        :meth:`note_restored` only when the restore actually lands in
        pages."""
        if prefix_len < min_tokens:
            return None
        n = len(prompt_ids)
        events: List[tuple] = []
        demote: List[Tuple[tuple, HostPrefixEntry]] = []
        reserved = None  # disk-index row popped for promotion
        try:
            if n == 0:
                return None
            with self._lock:
                best_key, best_len, on_disk = self._best_match_locked(
                    prompt_ids, n
                )
                if best_key is None:
                    return None
                if not on_disk:
                    return self._entries[best_key]
                # reserve the disk row (briefly in neither map — an honest
                # transient miss for concurrent readers), then load the file
                # without the lock
                row = self._disk.pop(best_key)
                self._disk_bytes -= row[2]
                reserved = (best_key,) + row
            key, path, length, nbytes, pages = reserved
            ent = self._load_disk_file(path, key, length, nbytes, pages)
            with self._lock:
                # a concurrent demote may have re-written THIS key's file at
                # the same deterministic path and re-indexed it while we held
                # the row reserved — absorb that row here so the index can
                # never point at the file the finally below deletes
                row2 = self._disk.pop(key, None)
                if row2 is not None:
                    self._disk_bytes -= row2[2]
                if ent is None:
                    if row2 is not None:
                        # our read failed but the re-demote's write is fresh:
                        # restore its row and leave the file alone
                        self._disk[key] = row2
                        self._disk_bytes += row2[2]
                        reserved = None
                        return None  # honest transient miss
                    self.dropped += 1
                    events.append(("disk_drop", key, length, pages))
                    return None  # unreadable file: dropped, honest miss
                if key in self._entries:
                    # a concurrent put won the race — its copy is fresher
                    return self._entries[key]
                self.disk_promotes += 1
                self._entries[key] = ent
                self._bytes += ent.nbytes
                events.append(("disk_promote", key, ent.length, ent.pages))
                self._evict_host_locked(events, demote)
                return ent
        finally:
            if reserved is not None:
                self._remove_files([reserved[1]])
            self._demote(demote, events)
            self._fire(events)

    def note_restored(self, key: tuple) -> None:
        """Count one SERVED restore and LRU-touch the entry — called by the
        engine once the restore has actually landed in device pages (the
        lookup itself is repeatable and side-effect-free, see there)."""
        with self._lock:
            self.restores += 1
            if key in self._entries:
                self._entries.move_to_end(key)

    def holds(self, prompt_ids: Sequence[int], prefix_len: int) -> bool:
        """LRU-neutral any-thread peek (the router fallback's tier check)."""
        if prefix_len < 1:
            return False
        n = len(prompt_ids)
        if n == 0:
            return False
        with self._lock:
            return self._best_match_locked(prompt_ids, n)[0] is not None

    # -------------------------------------------------------------- migration
    def snapshot(self) -> List[HostPrefixEntry]:
        """Copy of every host-DRAM entry in LRU order (disk entries are NOT
        loaded — see :meth:`export_all` for the full migration export).
        Pure host memory: valid even after the owning engine dies, which is
        exactly why scale-down migration survives the replica-dies-mid-drain
        race."""
        with self._lock:
            return list(self._entries.values())

    def warm_keys(self) -> List[Tuple[tuple, int]]:
        """(key, pages) for every entry this tier holds across host DRAM
        AND disk — the detach loss-accounting union (no file reads)."""
        with self._lock:
            out = [(k, e.pages) for k, e in self._entries.items()]
            out += [(k, row[3]) for k, row in self._disk.items()]
            return out

    def export_all(
        self,
    ) -> Tuple[List[HostPrefixEntry], List[Tuple[tuple, int, int]]]:
        """The full migration export: every warm entry this tier holds,
        with disk entries loaded back into memory (file reads run OUTSIDE
        the lock).  Ordered coldest-first — disk rows, then the host LRU —
        so :meth:`absorb` preserves recency under the target's budget.
        Returns ``(entries, unreadable)``; ``unreadable`` lists
        ``(key, length, pages)`` for disk rows whose file could not be read
        (the caller charges them lost).  The disk index is left intact: the
        source replica is detaching, and its namespace is swept on reuse."""
        with self._lock:
            disk_rows = [(k,) + row for k, row in self._disk.items()]
            host_entries = list(self._entries.values())
        entries: List[HostPrefixEntry] = []
        unreadable: List[Tuple[tuple, int, int]] = []
        for key, path, length, nbytes, pages in disk_rows:
            ent = self._load_disk_file(path, key, length, nbytes, pages)
            if ent is not None:
                entries.append(ent)
            else:
                unreadable.append((key, int(length), int(pages)))
        return entries + host_entries, unreadable

    def export_entry(self, key: tuple) -> Optional[HostPrefixEntry]:
        """Read-only export of ONE entry for the fleet wire (``/fleet/kv/get``
        and the prefill-pool push — serving/fleet.py): a host-DRAM hit is
        returned as-is (LRU-neutral, no restore counters), a disk hit is
        loaded from its file WITHOUT promotion or index mutation — the
        exporting process keeps its tiers exactly as they were.  None on a
        miss or an unreadable file."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                return ent
            row = self._disk.get(key)
        if row is None:
            return None
        path, length, nbytes, pages = row
        return self._load_disk_file(path, key, length, nbytes, pages)

    def export_match(
        self, prompt_ids: Sequence[int], prefix_len: int, *, min_tokens: int = 1
    ) -> Optional[HostPrefixEntry]:
        """LONGEST stored prefix of ``prompt_ids``, exported read-only (see
        :meth:`export_entry`) — the ``/fleet/kv/get`` by-prompt lookup, which
        must not perturb the serving process's LRU or promotion state."""
        if prefix_len < min_tokens:
            return None
        n = len(prompt_ids)
        if n == 0:
            return None
        with self._lock:
            best_key, _best_len, on_disk = self._best_match_locked(
                prompt_ids, n
            )
            if best_key is None:
                return None
            if not on_disk:
                return self._entries[best_key]
            row = self._disk.get(best_key)
        if row is None:  # demote/promote race — honest miss
            return None
        path, length, nbytes, pages = row
        return self._load_disk_file(path, best_key, length, nbytes, pages)

    def absorb(self, entries: Sequence[HostPrefixEntry]) -> List[tuple]:
        """Import a dying replica's snapshot in its LRU order (oldest first,
        the snapshot's own order), so under THIS tier's budget the source's
        most-recently-used entries are the last inserted — and therefore the
        last evicted.  Returns the snapshot KEYS this tier actually RETAINS
        (host DRAM or disk) after the import — a later put may evict an
        earlier one, and an oversized entry is refused wherever it sits in
        the order, so only per-key presence makes the caller's
        migrated/lost-pages split exact.

        Every entry's ``wire_version`` — and, for entries that crossed a
        wire or disk boundary, its CRC-32C — is checked BEFORE anything is
        absorbed (all-or-nothing): a snapshot stamped by a different build
        raises :class:`WireVersionError`, a checksum mismatch raises
        :class:`WireIntegrityError`, and in neither case are pages
        half-imported whose bytes this build would misread."""
        entries = list(entries)
        for ent in entries:
            ver = getattr(ent, "wire_version", KV_WIRE_VERSION)
            if ver not in KV_WIRE_COMPAT_VERSIONS:
                raise WireVersionError(
                    f"KV snapshot entry has wire_version {ver} "
                    f"(this build accepts {KV_WIRE_COMPAT_VERSIONS}); refusing "
                    "to absorb pages written by a different build"
                )
            crc = getattr(ent, "crc32c", None)
            if crc is not None and entry_crc32c(ent.k, ent.v) != crc:
                with self._lock:
                    self.integrity_rejects += 1
                raise WireIntegrityError(
                    f"KV entry {ent.key[:4]!r}... failed its CRC-32C; refusing "
                    "to absorb a corrupt page payload"
                )
        for ent in entries:
            self.put(ent.key, ent.length, ent.k, ent.v)
        keys = [e.key for e in entries]
        with self._lock:
            retained = [
                key
                for key in keys
                if key in self._entries or key in self._disk
            ]
            self.migrated_in += len(retained)
        return retained

    # ------------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._lock:
            host_pages = sum(e.pages for e in self._entries.values())
            disk_pages = sum(pg for (_, _, _, pg) in self._disk.values())
            return {
                "kv_host_entries": len(self._entries),
                "kv_host_bytes": self._bytes,
                "kv_host_max_bytes": self.max_bytes,
                "kv_host_pages": host_pages,
                "kv_disk_entries": len(self._disk),
                "kv_disk_bytes": self._disk_bytes,
                "kv_disk_pages": disk_pages,
                "kv_spills": self.spills,
                "kv_host_restores": self.restores,
                "kv_host_evictions": self.host_evictions,
                "kv_disk_spills": self.disk_spills,
                "kv_disk_promotes": self.disk_promotes,
                "kv_tier_dropped": self.dropped,
                "kv_migrated_in": self.migrated_in,
                "kv_integrity_rejects": self.integrity_rejects,
            }


class PageAllocator:
    """Refcounted fixed-size page pool with a shareable-prefix LRU.

    Invariants (property-tested in tests/test_kv_paging.py):

    - every page is either on the free list or has refcount >= 1, never both;
    - ``pages_free + pages_used == n_pages`` at all times;
    - a page referenced by k live holders and m registry entries has
      refcount k + m.
    """

    def __init__(
        self,
        n_pages: int,
        page_size: int,
        *,
        page_bytes: int = 0,
        max_shared_bytes: int = 1 << 30,
        max_shared_entries: int = 8,
        min_prefix_tokens: int = 32,
        host_tier: Optional[HostKVTier] = None,
        writethrough: bool = True,
    ):
        if n_pages <= 0 or page_size <= 0:
            raise ValueError(
                f"PageAllocator needs n_pages > 0 and page_size > 0, got "
                f"({n_pages}, {page_size})"
            )
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.page_bytes = max(0, int(page_bytes))
        self.max_shared_bytes = int(max_shared_bytes)
        self.max_shared_entries = max(0, int(max_shared_entries))
        self.min_prefix_tokens = max(1, int(min_prefix_tokens))
        self._lock = threading.Lock()
        # LIFO free list: the most recently freed pages are re-used first, so
        # a steady workload keeps touching a warm working set of HBM
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._refs: Dict[int, int] = {}
        self._shared: "collections.OrderedDict[tuple, SharedPrefix]" = (
            collections.OrderedDict()
        )
        self._shared_bytes = 0
        # counters (read by tick_stats / healthz); prefix hit/miss counting
        # lives with the ENGINE (once per admitted request — lookup() runs on
        # every admission peek and would overcount while a head waits)
        self.evictions = 0  # shared entries dropped (LRU or on-demand)
        self.cow_copies = 0  # boundary pages cloned for a sharer
        # --- host tier (spill/restore durability; docs/KV_PAGING.md) ------
        # An evicted registry entry SPILLS its page contents to the host
        # tier before its pages free; with writethrough, register() also
        # copies every new entry down, so the host tier holds every warm
        # prefix and a crash-only reset() loses only the HBM copy.  The
        # fetch callback (device pages -> host numpy K/V) is engine-owned
        # (bind_spill_fetch) because only the engine can touch the device
        # cache; it runs on the engine thread, OUTSIDE this allocator's
        # lock, and never on the decode hot path (dabtlint DABT104).
        self.host = host_tier
        self.writethrough = bool(writethrough)
        self._spill_fetch: Optional[Callable[[Sequence[int]], Optional[tuple]]] = None
        # evictions collected under the lock, spilled after release — the
        # freed pages' contents stay valid until the engine thread issues
        # the next device write, which is strictly after alloc() returns
        self._pending_spill: List[Tuple[tuple, SharedPrefix]] = []
        self.spill_failures = 0
        # tier-transition listener: fn(event, key, length, pages); fired
        # outside the lock (see module docstring)
        self.on_event: Optional[Callable[..., None]] = None

    # ------------------------------------------------------------ core alloc
    def bind_spill_fetch(
        self, fetch: Callable[[Sequence[int]], Optional[tuple]]
    ) -> "PageAllocator":
        """Wire the engine's device->host page reader: ``fetch(pages)``
        returns ``(k, v)`` numpy arrays of shape ``[L, n, KH, page, D]`` (or
        None on failure).  Engine-thread-only, called outside this lock."""
        self._spill_fetch = fetch
        return self

    def _emit(self, event: str, key: tuple, length: int, pages: int) -> None:
        cb = self.on_event
        if cb is None:
            return
        try:
            cb(event, key, length, pages)
        except Exception:
            logger.exception("allocator event listener failed (%s)", event)

    def _drain_spills(self) -> None:
        """Spill evicted entries collected under the lock (engine thread,
        lock released).  The evicted pages' contents are still valid: the
        engine issues no device write to them until after the triggering
        alloc()/register() returns."""
        pending, self._pending_spill = self._pending_spill, []
        for key, ent in pending:
            spilled = False
            if (
                self.host is not None
                and self._spill_fetch is not None
                and not self.host.has(key)
            ):
                try:
                    fetched = self._spill_fetch(ent.pages)
                except Exception:
                    logger.exception("KV spill fetch failed; entry dropped")
                    fetched = None
                if fetched is not None:
                    k, v = fetched
                    spilled = self.host.put(key, ent.length, k, v)
                else:
                    self.spill_failures += 1
            elif self.host is not None and self.host.has(key):
                spilled = True  # write-through already holds the bytes
            self._emit(
                "evict_spilled" if spilled else "evict_dropped",
                key,
                ent.length,
                len(ent.pages),
            )

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` free pages (refcount 1 each), evicting LRU shared
        prefixes on demand (evicted entries spill to the host tier when one
        is bound).  Returns None — allocating nothing — when the pool cannot
        satisfy the request even after evicting every entry."""
        if n <= 0:
            return []
        try:
            with self._lock:
                while len(self._free) < n and self._shared:
                    self._evict_lru_locked()
                if len(self._free) < n:
                    return None
                pages = [self._free.pop() for _ in range(n)]
                for p in pages:
                    self._refs[p] = 1
                return pages
        finally:
            self._drain_spills()

    def incref(self, pages: Sequence[int]) -> None:
        with self._lock:
            for p in pages:
                if p not in self._refs:
                    raise ValueError(f"incref on free page {p}")
                self._refs[p] += 1

    def decref(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; pages reaching zero return to the
        free list (LIFO)."""
        with self._lock:
            self._decref_locked(pages)

    def _decref_locked(self, pages: Sequence[int]) -> None:
        for p in pages:
            r = self._refs.get(p)
            if r is None:
                raise ValueError(f"decref on free page {p}")
            if r <= 1:
                del self._refs[p]
                self._free.append(p)
            else:
                self._refs[p] = r - 1

    # ------------------------------------------------------- prefix registry
    def lookup(self, prompt_ids: Sequence[int], prefix_len: int) -> Optional[SharedPrefix]:
        """LONGEST registered prefix this prompt starts with, or None.

        Longest-match (not exact-key) keeps multi-turn dialogs hitting: turn
        N's prompt extends turn N-1's ``[system, ...history]`` block, so the
        previous turn's entry is a proper prefix of the new prompt even though
        the declared split point moved.  LRU-touches the winner."""
        if prefix_len < self.min_prefix_tokens:
            return None
        n = len(prompt_ids)
        with self._lock:
            best_key, best = None, None
            for key, ent in self._shared.items():
                if ent.length < n and (best is None or ent.length > best.length):
                    if tuple(prompt_ids[: ent.length]) == key:
                        best, best_key = ent, key
            if best_key is not None:
                self._shared.move_to_end(best_key)
            return best

    def holds_prefix(self, prompt_ids: Sequence[int], prefix_len: int) -> bool:
        """Would :meth:`lookup` hit for this prompt?  Read-only peek — no LRU
        touch, safe from ANY thread (the multi-replica router's affinity
        dispatch asks every replica's pool this before picking one; a peek
        that reordered the LRU would let routing probes evict real entries)."""
        if prefix_len < self.min_prefix_tokens:
            return False
        n = len(prompt_ids)
        with self._lock:
            for key, ent in self._shared.items():
                if ent.length < n and tuple(prompt_ids[: ent.length]) == key:
                    return True
        return False

    def register(
        self, prompt_ids: Sequence[int], prefix_len: int, pages: Sequence[int]
    ) -> bool:
        """Register the pages covering ``prompt_ids[:prefix_len]`` as a
        shareable prefix (increfs each — the registry is a holder like any
        live request).  ``pages`` must cover positions ``[0, prefix_len)`` in
        logical order: ``ceil(prefix_len / page_size)`` entries.  Returns
        False (no-op) for too-short prefixes, duplicates, or a disabled
        registry."""
        if (
            self.max_shared_entries <= 0
            or prefix_len < self.min_prefix_tokens
            or not pages
        ):
            return False
        need = -(-prefix_len // self.page_size)
        if len(pages) != need:
            raise ValueError(
                f"register: prefix of {prefix_len} tokens needs {need} pages, "
                f"got {len(pages)}"
            )
        key = tuple(prompt_ids[:prefix_len])
        try:
            with self._lock:
                if key in self._shared:
                    return False
                for p in pages:
                    if p not in self._refs:
                        raise ValueError(f"register with free page {p}")
                ent = SharedPrefix(
                    pages=tuple(pages),
                    length=int(prefix_len),
                    full_pages=int(prefix_len // self.page_size),
                )
                for p in ent.pages:
                    self._refs[p] += 1
                self._shared[key] = ent
                self._shared_bytes += len(ent.pages) * self.page_bytes
                while self._shared and (
                    len(self._shared) > self.max_shared_entries
                    or (self.page_bytes and self._shared_bytes > self.max_shared_bytes)
                ):
                    self._evict_lru_locked()
                registered = key in self._shared
        finally:
            self._drain_spills()
        if not registered:
            # pathological budget: the new entry itself was the LRU victim
            return False
        self._emit("register", key, int(prefix_len), len(ent.pages))
        if self.writethrough and self.host is not None and not self.host.has(key):
            # write-through: the durable host copy exists the moment the
            # prefix is warm, so a crash-only reset() (which cannot read the
            # possibly-poisoned device pool) still leaves the session warm.
            # One device->host page gather per NEW prefix, off the hot path.
            if self._spill_fetch is not None:
                try:
                    fetched = self._spill_fetch(ent.pages)
                except Exception:
                    logger.exception("KV write-through fetch failed")
                    fetched = None
                if fetched is not None:
                    self.host.put(key, int(prefix_len), *fetched)
                else:
                    self.spill_failures += 1
        return True

    def _evict_lru_locked(self) -> None:
        key, ent = self._shared.popitem(last=False)
        self._shared_bytes -= len(ent.pages) * self.page_bytes
        # spill BEFORE the refs drop?  No: collect now, fetch after the lock
        # releases — the page contents stay valid until the engine issues
        # its next device write (see _drain_spills)
        self._pending_spill.append((key, ent))
        self._decref_locked(ent.pages)
        self.evictions += 1

    def shared_keys(self) -> List[Tuple[tuple, int, int]]:
        """Snapshot of the device registry: (key, length, n_pages) per entry
        — the router's migration export uses this to find warm prefixes that
        never made it to the host tier (write-through off)."""
        with self._lock:
            return [
                (key, ent.length, len(ent.pages))
                for key, ent in self._shared.items()
            ]

    def shared_entries(self) -> List[Tuple[tuple, SharedPrefix]]:
        """Snapshot of (key, entry) pairs — engine-thread users that need the
        physical pages (spill_registered_to_host)."""
        with self._lock:
            return list(self._shared.items())

    def reset(self) -> None:
        """Forget everything (crash-only engine restart: the device pool is
        rebuilt from scratch, so every page is free again).  The HOST tier is
        deliberately untouched — its numpy copies were taken from a healthy
        pool, so warm sessions survive the crash and restore on their next
        hit; only the HBM tier drops (events tell the fleet registry)."""
        with self._lock:
            dropped = [
                (key, ent.length, len(ent.pages))
                for key, ent in self._shared.items()
            ]
            self._free = list(range(self.n_pages - 1, -1, -1))
            self._refs.clear()
            self._shared.clear()
            self._shared_bytes = 0
            self._pending_spill = []
        for key, length, pages in dropped:
            self._emit(
                "evict_spilled"
                if self.host is not None and self.host.has(key)
                else "evict_dropped",
                key,
                length,
                pages,
            )

    # ------------------------------------------------------------- telemetry
    @property
    def pages_free(self) -> int:
        with self._lock:
            return len(self._free)

    def available(self) -> int:
        """Pages a new request could obtain right now: the free list plus
        every cached-prefix page whose ONLY holder is the registry (evicting
        the entry would free it).  The scheduler's KV-pressure admission test
        compares projected demand against this."""
        with self._lock:
            evictable = sum(
                1
                for ent in self._shared.values()
                for p in ent.pages
                if self._refs.get(p) == 1
            )
            return len(self._free) + evictable

    def shared_page_ids(self) -> set:
        """Pages any registry entry references — holders of VALID prefix K/V
        that scratch writes (e.g. the decode probe's synthetic fill) must
        never touch."""
        with self._lock:
            return {p for ent in self._shared.values() for p in ent.pages}

    def stats(self) -> dict:
        with self._lock:
            used = self.n_pages - len(self._free)
            shared_pages = {p for ent in self._shared.values() for p in ent.pages}
            # free + evictable cached-prefix pages — the same quantity
            # available() reports.  Consumers judging POOL PRESSURE (the
            # autoscaler's kv_frac) must use this, not used/total: a warm
            # prefix cache legitimately occupies pages without denying them
            # to anyone (they evict on demand).
            evictable = sum(
                1
                for ent in self._shared.values()
                for p in ent.pages
                if self._refs.get(p) == 1
            )
            out = {
                "kv_pages_total": self.n_pages,
                "kv_page_size": self.page_size,
                "kv_pages_used": used,
                "kv_pages_free": len(self._free),
                "kv_pages_obtainable": len(self._free) + evictable,
                "kv_shared_pages": len(shared_pages),
                "kv_shared_page_frac": round(len(shared_pages) / max(1, used), 4)
                if used
                else 0.0,
                "kv_shared_entries": len(self._shared),
                "kv_shared_bytes": self._shared_bytes,
                "kv_evictions": self.evictions,
                "kv_cow_copies": self.cow_copies,
            }
        # host/disk tier gauges ride along (outside the allocator lock: the
        # tier locks itself, and nesting the two would order them needlessly)
        if self.host is not None:
            out["kv_spill_failures"] = self.spill_failures
            out.update(self.host.stats())
        return out
