"""MXU-resident exact cosine KNN — the pgvector HNSW replacement.

The reference approximates with an HNSW graph walked by the Postgres process
(reference: assistant/storage/models.py:32-58, search_service.py:185-196).  On TPU
the idiomatic design is the opposite: keep the whole embedding matrix device-
resident in bf16 and score every candidate with one [Q,D]x[D,N] matmul + top-k.
At the framework's scale (<= millions of 768-d vectors) this is *exact*, runs in
sub-millisecond MXU time, and has no index build cost — mutation is append/compact.

Serving discipline (everything the pgvector HNSW gives Postgres for free):

- **Bucketed shapes everywhere.** Query rows pad to a small bucket set, ``k``
  pads to a bucket and is sliced on host, appends pad to row buckets written
  with ``dynamic_update_slice`` (start is a traced operand), and capacity grows
  by powers of two — so every compiled kernel is reused and steady state never
  recompiles.
- **``warmup()``** pre-executes the query kernels for the common (rows, k)
  buckets and blocks until the corpus is actually resident in HBM.  JAX
  dispatch is async — without an explicit barrier the first live query would
  silently pay the whole corpus host->HBM transfer + compile.  Mirrors the
  generation/embedding engines' warmup (serving/engine.py).
- **Device-side appends.** Vectors that were just computed on device (the
  ingestion path) append without a host round trip: ``add_device`` normalizes
  and writes rows on device and materializes the host copy lazily, so bulk
  ingestion is compute-bound, not d2h-bound.
- **One fetch per search.** Scores and indices come back in a single
  ``device_get`` — per-call latency is one host<->device round trip.

Allow-listed searches (the reference's ``filter(id__in=...)`` + KNN) pass a
positions mask as the kernel's validity input — same compiled kernel, no
full-corpus ranking.

Corpora beyond one chip's HBM shard over the mesh ``data`` axis: rows are
scattered across devices, each device scores its local shard and takes a local
top-k, and one [Q, k*n_dev] ``all_gather`` + final top-k merges the shards —
the classic distributed exact-KNN reduction, riding ICI instead of host RAM.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.sampling import top_k_auto

# Compiled-shape buckets.  Queries and k snap to these so the jit cache stays
# tiny; results are sliced to the caller's true sizes on host.
_QUERY_BUCKETS = (8, 32, 128)
_K_BUCKETS = (16, 64, 256, 1024)
_APPEND_BUCKETS = (64, 256, 1024, 4096)


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return _next_cap(buckets[-1], n)


def _next_cap(base: int, target: int) -> int:
    """Smallest power-of-two multiple of ``base`` that is >= ``target``."""
    while base < target:
        base *= 2
    return base


def _topk_scores_impl(index: jnp.ndarray, queries: jnp.ndarray, valid: jnp.ndarray, k: int):
    # index: [N, D] bf16 row-normalized; queries: [Q, D]; valid: [N] bool.
    # top_k_auto switches to the exact hierarchical two-stage top-k at large N
    # (the sampler's fix): it cuts the device-side sort cost.
    scores = jnp.einsum(
        "qd,nd->qn", queries.astype(jnp.bfloat16), index, preferred_element_type=jnp.float32
    )
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    return top_k_auto(scores, k)


_topk_scores = jax.jit(_topk_scores_impl, static_argnums=(3,))


def _normalize(x: np.ndarray) -> np.ndarray:
    norms = np.linalg.norm(x, axis=-1, keepdims=True)
    return x / np.maximum(norms, 1e-12)


@jax.jit
def _normalize_rows_dev(x: jnp.ndarray) -> jnp.ndarray:
    """Row-normalize on device (f32 stats, bf16 out) — bulk ingestion skips the
    two O(N*D) host passes; row-wise, so it shards over 'data' untouched."""
    xf = x.astype(jnp.float32)
    norms = jnp.maximum(jnp.linalg.norm(xf, axis=-1, keepdims=True), 1e-12)
    return (xf / norms).astype(jnp.bfloat16)


def _append_rows_impl(index, valid, fresh, fresh_valid, start):
    """Write a padded row bucket at a *traced* start offset.

    ``start`` being an operand (not a Python int) means one compile per
    (capacity, bucket) pair covers every append position — the round-2 path
    compiled a new program per distinct ``.at[start:n]`` slice.  Zero pad rows
    normalize to zero-norm clamps and land under ``fresh_valid=False``.

    Rows are rounded to bf16 BEFORE normalization so every ingestion route
    (full stage, host append, device append) produces bit-identical index rows.
    """
    fresh = _normalize_rows_dev(fresh.astype(jnp.bfloat16))
    index = jax.lax.dynamic_update_slice(index, fresh, (start, 0))
    valid = jax.lax.dynamic_update_slice(valid, fresh_valid, (start,))
    return index, valid


_append_rows = jax.jit(_append_rows_impl)


def _grow_dev_impl(index, valid, new_cap: int):
    big = jnp.zeros((new_cap, index.shape[1]), index.dtype)
    big = jax.lax.dynamic_update_slice(big, index, (0, 0))
    big_valid = jnp.zeros((new_cap,), bool)
    big_valid = jax.lax.dynamic_update_slice(big_valid, valid, (0,))
    return big, big_valid


_grow_dev = jax.jit(_grow_dev_impl, static_argnums=(2,))


class VectorIndex:
    """Append/compact exact-KNN index over (id, vector) pairs.

    Thread-safe; the device copy is maintained incrementally: pure appends
    write padded row buckets in place on device (from host vectors or directly
    from device-resident embeddings via :meth:`add_device`), while
    overwrites/removes trigger a full re-stage.  Scores are cosine
    similarities in [-1, 1] — rows are normalized on device at staging time
    (host rows stay raw), queries on host at search time.

    Pass ``mesh`` to shard rows over the mesh's ``data`` axis: search then runs
    as a shard_map with a local top-k per device and an all-gather merge.
    """

    def __init__(self, dim: int, mesh=None):
        self.dim = dim
        self.mesh = mesh
        self._lock = threading.Lock()
        self._ids: list[int] = []
        self._id_pos: dict[int, int] = {}
        # contiguous row storage with capacity doubling — bulk ingestion is a
        # slice assignment, not a million-iteration Python loop, and staging
        # never needs an np.stack over per-row arrays
        self._mat = np.empty((0, dim), np.float32)
        self._n = 0
        self._device_index: Optional[jnp.ndarray] = None
        self._device_valid: Optional[jnp.ndarray] = None
        self._device_count = 0  # rows materialized on device
        self._snapshot_ids: list[int] = []
        self._dirty_full = True
        # device-born rows whose host copy hasn't been fetched yet:
        # [(start, device_rows)] — drained lazily (the serve path never needs
        # the d2h copy) but bounded, so a long ingestion run can't hold a
        # second full corpus copy in HBM
        self._pending_host: list[tuple[int, jnp.ndarray]] = []
        self._pending_bytes = 0
        self.pending_host_limit = 256 << 20

    def __len__(self) -> int:
        return self._n

    # ------------------------------------------------------------------ mutation
    def _grow_host(self, need: int) -> None:
        cap = _next_cap(max(1024, self._mat.shape[0]), need)
        if cap != self._mat.shape[0]:
            new = np.empty((cap, self.dim), np.float32)
            new[: self._n] = self._mat[: self._n]
            self._mat = new

    def _join_pending_host(self) -> None:
        """Materialize host copies of device-born rows (one batched fetch)."""
        if not self._pending_host:
            return
        fetched = jax.device_get([rows for _, rows in self._pending_host])
        for (start, _), host_rows in zip(self._pending_host, fetched):
            m = host_rows.shape[0]
            self._mat[start : start + m] = np.asarray(host_rows, np.float32)
        self._pending_host = []
        self._pending_bytes = 0

    def add(self, ids: Sequence[int], vectors: np.ndarray) -> None:
        # rows are stored raw; normalization happens on device at staging time
        vectors = np.asarray(vectors, np.float32).reshape(-1, self.dim)
        ids = [int(i) for i in ids]
        with self._lock:
            if len(set(ids)) == len(ids) and not any(i in self._id_pos for i in ids):
                # bulk append fast path (the ingestion case): one slice copy
                m = len(ids)
                self._grow_host(self._n + m)
                self._mat[self._n : self._n + m] = vectors
                for j, i in enumerate(ids):
                    self._id_pos[i] = self._n + j
                self._ids.extend(ids)
                self._n += m
                return
            self._join_pending_host()
            for i, vec in zip(ids, vectors):
                pos = self._id_pos.get(i)
                if pos is None:
                    self._grow_host(self._n + 1)
                    self._mat[self._n] = vec
                    self._id_pos[i] = self._n
                    self._ids.append(i)
                    self._n += 1
                else:
                    self._mat[pos] = vec
                    self._dirty_full = True  # in-place overwrite: re-stage

    def add_device(self, ids: Sequence[int], rows) -> None:
        """Append rows that already live on device (e.g. fresh encoder output).

        The device index is updated with a bucketed on-device write — no
        host->device or device->host traffic on the hot path; the host copy is
        fetched lazily only if a full re-stage later needs it.  Falls back to
        the host path when ids collide/overwrite or the index is sharded.
        """
        ids = [int(i) for i in ids]
        rows = jnp.asarray(rows)
        if rows.ndim != 2 or rows.shape[1] != self.dim:
            rows = rows.reshape(-1, self.dim)
        if rows.shape[0] != len(ids):
            raise ValueError(
                f"add_device: {len(ids)} ids for {rows.shape[0]} rows"
            )
        with self._lock:
            fresh_ok = len(set(ids)) == len(ids) and not any(i in self._id_pos for i in ids)
            if self.mesh is None and fresh_ok and self._n == 0 and self._device_index is None:
                self._stage_full(0)  # cold start: an empty staged buffer, no transfer
                self._dirty_full = False
            device_in_sync = (
                self.mesh is None
                and fresh_ok
                and not self._dirty_full
                and self._device_index is not None
                and self._device_count == self._n
            )
            if device_in_sync:
                m = len(ids)
                start = self._n
                self._write_bucketed(start, rows, m)
                self._grow_host(start + m)  # reserve host rows; filled lazily
                self._pending_host.append((start, rows[:m]))
                self._pending_bytes += int(rows[:m].size) * rows.dtype.itemsize
                if self._pending_bytes > self.pending_host_limit:
                    self._join_pending_host()  # bound the HBM held by raw rows
                for j, i in enumerate(ids):
                    self._id_pos[i] = start + j
                self._ids.extend(ids)
                self._n = start + m
                self._device_count = start + m
                self._snapshot_ids = list(self._ids)
                return
        # host fallback (sharded index, id collisions, or device not staged yet)
        self.add(ids, np.asarray(jax.device_get(rows), np.float32))

    def _write_bucketed(self, start: int, rows: jnp.ndarray, m: int) -> None:
        """Write ``m`` device rows at ``start``, padded to an append bucket.

        The single home of the clamp-safety invariant: the WHOLE padded bucket
        must fit capacity, because ``dynamic_update_slice`` clamps an
        out-of-range start and would silently overwrite row 0 onward.  Grows
        capacity by powers of two until it does.  Caller holds ``_lock``.
        """
        bkt = _bucket(m, _APPEND_BUCKETS)
        if start + bkt > self._capacity():
            self._device_index, self._device_valid = _grow_dev(
                self._device_index,
                self._device_valid,
                _next_cap(max(self._capacity(), 1), start + bkt),
            )
        if bkt != m:
            rows = jnp.concatenate([rows, jnp.zeros((bkt - m, self.dim), rows.dtype)])
        fresh_valid = np.zeros((bkt,), bool)
        fresh_valid[:m] = True
        self._device_index, self._device_valid = _append_rows(
            self._device_index, self._device_valid, rows, jnp.asarray(fresh_valid), start
        )

    def reserve(self, n: int) -> None:
        """Pre-grow device capacity for a known ingestion size, so a bulk
        device-append run compiles its write kernel once instead of once per
        power-of-two growth step."""
        if self.mesh is not None:
            return
        with self._lock:
            if self._dirty_full or self._device_index is None:
                self._stage_full(self._n)
                self._dirty_full = False
            cap = self._capacity()
            if n <= cap:
                return
            new_cap = _next_cap(cap, n)
            self._device_index, self._device_valid = _grow_dev(
                self._device_index, self._device_valid, new_cap
            )
            self._grow_host(new_cap)

    def remove(self, ids: Sequence[int]) -> None:
        with self._lock:
            drop = {int(i) for i in ids} & set(self._id_pos)
            if not drop:
                return
            self._join_pending_host()
            keep_mask = np.fromiter((i not in drop for i in self._ids), bool, self._n)
            kept = self._mat[: self._n][keep_mask]
            self._mat[: kept.shape[0]] = kept
            self._ids = [i for i in self._ids if i not in drop]
            self._id_pos = {i: p for p, i in enumerate(self._ids)}
            self._n = len(self._ids)
            self._dirty_full = True

    def clear(self) -> None:
        with self._lock:
            self._ids, self._id_pos = [], {}
            self._mat = np.empty((0, self.dim), np.float32)
            self._n = 0
            self._device_index = self._device_valid = None
            self._device_count = 0
            self._pending_host = []
            self._pending_bytes = 0
            self._dirty_full = True

    # ------------------------------------------------------------------- search
    def _row_multiple(self) -> int:
        # sharded rows must split evenly across the data axis
        shards = self.mesh.shape.get("data", 1) if self.mesh is not None else 1
        return 128 * shards

    def _capacity(self) -> int:
        return 0 if self._device_index is None else self._device_index.shape[0]

    def _stage_full(self, n: int) -> None:
        """Re-stage the whole corpus: pad N to the next power-of-two multiple of
        the row tile so the kernel shape (and its compilation) is reused.  The
        host->HBM transfer goes out as bf16 — half the bytes of the raw f32
        rows."""
        self._join_pending_host()
        n_pad = _next_cap(self._row_multiple(), n)
        mat = np.zeros((n_pad, self.dim), np.dtype(jnp.bfloat16))
        if n:
            # chunked cast keeps the f32->bf16 conversion cache-resident
            step = 1 << 16
            for s in range(0, n, step):
                e = min(n, s + step)
                mat[s:e] = self._mat[s:e].astype(np.dtype(jnp.bfloat16))
        valid = np.zeros((n_pad,), bool)
        valid[:n] = True
        self._device_index = _normalize_rows_dev(self._put(jnp.asarray(mat), sharded=True))
        self._device_valid = self._put(jnp.asarray(valid), sharded=True)
        self._device_count = n
        self._snapshot_ids = list(self._ids)

    def _put(self, arr: jnp.ndarray, sharded: bool) -> jnp.ndarray:
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P("data") if sharded else P()
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _ensure_device(self, allowed_ids: Optional[set] = None):
        """Returns (device matrix, valid mask, ids snapshot, allowed-positions
        mask or None).

        The ids snapshot AND the allowlist position mask are taken under the
        same lock that built the device copy, so concurrent remove()/add()
        compactions can't shift position→id mapping for an in-flight search.
        """
        with self._lock:
            n = self._n
            needs_full = (
                self._dirty_full
                or self._device_index is None
                # the sharded update path can't grow in place; plain indexes
                # grow on device inside _write_bucketed (no corpus re-transfer)
                or (self.mesh is not None and n > self._capacity())
            )
            if needs_full:
                self._stage_full(n)
                self._dirty_full = False
            elif n > self._device_count:
                start = self._device_count
                if self.mesh is not None:
                    # sharded copy: keep the replicated-update path (appends are
                    # rare relative to searches on a sharded corpus); same
                    # bf16-then-normalize rounding as every other route
                    fresh = _normalize_rows_dev(
                        jnp.asarray(self._mat[start:n].astype(np.dtype(jnp.bfloat16)))
                    )
                    self._device_index = self._put(
                        self._device_index.at[start:n].set(fresh), sharded=True
                    )
                    self._device_valid = self._put(
                        self._device_valid.at[start:n].set(True), sharded=True
                    )
                else:
                    # incremental append of host-added rows: bucketed device
                    # write, reusing one compile per (capacity, bucket); the
                    # h2d transfer carries only the real rows (bf16)
                    m = n - start
                    fresh = jnp.asarray(
                        self._mat[start:n].astype(np.dtype(jnp.bfloat16))
                    )
                    self._write_bucketed(start, fresh, m)
                self._device_count = n
                self._snapshot_ids = list(self._ids)
            allowed_mask = None
            if allowed_ids is not None:
                # inside the staging lock: _id_pos is consistent with the
                # just-(re)staged device matrix here and nowhere else
                allowed_mask = np.zeros((self._capacity(),), bool)
                for i in allowed_ids:
                    pos = self._id_pos.get(int(i))
                    if pos is not None and pos < allowed_mask.shape[0]:
                        allowed_mask[pos] = True
            return self._device_index, self._device_valid, self._snapshot_ids, allowed_mask

    def warmup(self, ks: Sequence[int] = _K_BUCKETS, q_rows: Sequence[int] = (8,)):
        """Stage the corpus and pre-execute the search kernels for the common
        (query-rows, k) buckets, BLOCKING until results are fetchable.

        Dispatch is async: without this, the first live query pays the whole
        corpus transfer + XLA compile.  Call after build (rag/index_registry.py does) — the analog of
        the serving engines' warmup (serving/engine.py).
        """
        if not self._n:
            return self
        index, valid, ids, _ = self._ensure_device()
        q = np.zeros((1, self.dim), np.float32)
        q[0, 0] = 1.0
        seen: set = set()
        for qr in q_rows:
            qb = _bucket(qr, _QUERY_BUCKETS)
            for k in ks:
                kb = min(_bucket(min(k, len(ids)), _K_BUCKETS), index.shape[0])
                if (qb, kb) in seen:
                    continue  # small corpora clamp several ks to one bucket
                seen.add((qb, kb))
                qp = np.repeat(q, qb, axis=0)
                if self.mesh is not None:
                    out = _sharded_topk(self.mesh, index, jnp.asarray(qp), valid, kb)
                else:
                    out = _topk_scores(index, jnp.asarray(qp), valid, kb)
                jax.block_until_ready(out)
        return self

    def search(
        self, query: np.ndarray, k: int = 10, allowed_ids: Optional[set] = None
    ) -> list[tuple[int, float]]:
        """Top-k (id, cosine_similarity) for one query vector."""
        pairs = self.search_batch(
            np.asarray(query, np.float32)[None, :], k, allowed_ids=allowed_ids
        )
        return pairs[0]

    def search_batch(
        self, queries: np.ndarray, k: int = 10, allowed_ids: Optional[set] = None
    ) -> list[list[tuple[int, float]]]:
        """Batched top-k.  ``allowed_ids`` restricts candidates to that subset
        by masking their row positions — the same compiled kernel as the
        unfiltered path (the mask rides the validity input), so no full-corpus
        ranking and no extra compile, unlike the reference's ``id__in`` +
        HNSW re-walk."""
        index, valid, ids, allowed_mask = self._ensure_device(allowed_ids)
        if not ids:
            return [[] for _ in range(len(queries))]
        n_live = len(ids)
        if allowed_mask is not None:
            hits = int(allowed_mask.sum())
            if not hits:
                return [[] for _ in range(len(queries))]
            valid = self._put(jnp.asarray(allowed_mask), sharded=True)
            n_live = hits
        k_eff = min(k, n_live)
        kb = min(_bucket(k_eff, _K_BUCKETS), index.shape[0])
        q = _normalize(np.asarray(queries, np.float32).reshape(-1, self.dim))
        q_pad = _bucket(q.shape[0], _QUERY_BUCKETS)
        if q_pad != q.shape[0]:
            q = np.concatenate([q, np.zeros((q_pad - q.shape[0], self.dim), np.float32)])
        if self.mesh is not None:
            out = _sharded_topk(self.mesh, index, jnp.asarray(q), valid, kb)
        else:
            out = _topk_scores(index, jnp.asarray(q), valid, kb)
        scores, idx = jax.device_get(out)  # one round trip for both outputs
        out_rows = []
        for qi in range(len(queries)):
            row = []
            for j in range(k_eff):
                p = int(idx[qi, j])
                if p < len(ids) and np.isfinite(scores[qi, j]):
                    row.append((ids[p], float(scores[qi, j])))
            out_rows.append(row)
        return out_rows

    # ----------------------------------------------------------------- loading
    @classmethod
    def from_model(
        cls, model_cls, field: str = "embedding", mesh=None, **filter_kw
    ) -> "VectorIndex":
        """Build from every non-null vector of an ORM model (e.g. Question)."""
        dim = model_cls._fields[field].dim
        index = cls(dim, mesh=mesh)
        qs = model_cls.objects.filter(**filter_kw).exclude(**{f"{field}__isnull": True})
        ids, rows = [], []
        for obj in qs:
            vec = getattr(obj, field)
            if vec is not None:
                ids.append(obj.id)
                rows.append(vec)
        if ids:
            index.add(ids, np.stack(rows))
        return index


class AsyncSearcher:
    """Coalesce concurrent async searches into one batched MXU dispatch.

    Each KNN dispatch through a host<->device round trip costs ~1 RTT; under
    concurrent RAG traffic N serial searches cost N RTTs while ONE batched
    [N, D] x [D, corpus] matmul costs the same single RTT (the query-row
    bucketing in :meth:`VectorIndex.search_batch` keeps the compiled kernel
    shared).  The same coalescing discipline as the serving engines'
    EmbeddingEngine, applied to retrieval.

    Allow-listed searches bypass coalescing — their position masks are
    per-query state the batched kernel shares across rows.
    """

    def __init__(self, index: "VectorIndex", window_s: float = 0.002, max_batch: int = 32):
        self.index = index
        self.window_s = window_s
        self.max_batch = max_batch
        self._pending: list = []  # [(vector, k, asyncio.Future)]
        self._flusher = None

    async def search(
        self, query: np.ndarray, k: int = 10, allowed_ids: Optional[set] = None
    ) -> list[tuple[int, float]]:
        import asyncio

        if allowed_ids is not None:
            return await asyncio.to_thread(
                self.index.search, query, k, allowed_ids=allowed_ids
            )
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._pending.append((np.asarray(query, np.float32), int(k), fut))
        if self._flusher is None or self._flusher.done():
            self._flusher = loop.create_task(self._flush_soon())
        if len(self._pending) >= self.max_batch:
            self._flush_now()
        return await fut

    async def _flush_soon(self):
        import asyncio

        await asyncio.sleep(self.window_s)
        self._flush_now()

    def _flush_now(self):
        import asyncio

        batch, self._pending = self._pending, []
        if not batch:
            return
        vecs = np.stack([v for v, _, _ in batch])
        k_max = max(k for _, k, _ in batch)
        loop = asyncio.get_running_loop()

        # Audited against the PR 7 resolve-under-lock rule (dabtlint DABT102):
        # these are *asyncio* futures resolved on the event-loop thread with
        # NO lock held — the batch list was detached from self._pending above,
        # VectorIndex._lock is only taken inside search_batch's to_thread
        # worker (released before results return), and asyncio callbacks are
        # scheduled via call_soon rather than run synchronously.  The deadlock
        # ingredients (held lock + synchronous done-callback) are both absent.
        async def run():
            try:
                rows = await asyncio.to_thread(self.index.search_batch, vecs, k_max)
            except Exception as e:  # pragma: no cover - propagate to every waiter
                for _, _, fut in batch:
                    if not fut.done():
                        fut.set_exception(e)
                return
            for (_, k, fut), hits in zip(batch, rows):
                if not fut.done():
                    fut.set_result(hits[:k])

        loop.create_task(run())


# --------------------------------------------------------------- sharded search
_sharded_topk_cache: dict = {}


def _sharded_topk(mesh, index: jnp.ndarray, queries: jnp.ndarray, valid: jnp.ndarray, k: int):
    """Distributed exact top-k over rows sharded on the mesh ``data`` axis.

    Each device scores its [N/d, D] shard against the replicated queries, takes
    a local top-k, converts local row positions to global ones with its
    ``axis_index`` offset, and one [Q, k*d] all_gather + final top-k merges the
    candidates.  ICI traffic per query is k*d score/index pairs — independent
    of corpus size.
    """
    from jax.sharding import PartitionSpec as P

    key = (id(mesh), k, index.shape, queries.shape)
    fn = _sharded_topk_cache.get(key)
    if fn is None:
        n_local = index.shape[0] // mesh.shape["data"]

        # a shard holds only n_local rows, so its local candidate list is capped
        # there; the merged pool (k_local * n_dev >= min(k, N)) stays exact
        k_local = min(k, n_local)

        def local_merge(idx_shard, q_rep, valid_shard):
            scores = jnp.einsum(
                "qd,nd->qn",
                q_rep.astype(jnp.bfloat16),
                idx_shard,
                preferred_element_type=jnp.float32,
            )
            scores = jnp.where(valid_shard[None, :], scores, -jnp.inf)
            s_loc, i_loc = top_k_auto(scores, k_local)  # hierarchical at large shards
            i_glob = i_loc + jax.lax.axis_index("data") * n_local
            s_all = jax.lax.all_gather(s_loc, "data", axis=1, tiled=True)
            i_all = jax.lax.all_gather(i_glob, "data", axis=1, tiled=True)
            s_fin, pos = jax.lax.top_k(s_all, k)
            i_fin = jnp.take_along_axis(i_all, pos, axis=1)
            return s_fin, i_fin

        fn = jax.jit(
            jax.shard_map(
                local_merge,
                mesh=mesh,
                in_specs=(P("data", None), P(None, None), P("data")),
                out_specs=(P(None, None), P(None, None)),
                # the all_gather + identical final top_k makes outputs
                # replicated over 'data', which the static VMA check can't prove
                check_vma=False,
            )
        )
        _sharded_topk_cache[key] = fn
    return fn(index, queries, valid)
