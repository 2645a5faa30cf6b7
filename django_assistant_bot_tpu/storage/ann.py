"""IVF-PQ approximate KNN — corpus-scale retrieval with recall accounting.

The exact plane (:mod:`storage.knn`) scores every row with one matmul — right
up to a few million vectors, wrong-shaped for the 100M-vector multi-bot corpus
the north star implies (O(N*D) FLOPs *and* O(N*D*2) HBM bytes per query).
This module is the classic IVF-PQ design, built from jitted JAX kernels so the
scan lives on the MXU and shards over the same mesh ``data`` axis as
``_sharded_topk``:

- **Training** (host-driven, off the hot path): a spherical mini-batch k-means
  coarse quantizer (``nlist`` centroids over normalized rows, assignment by max
  dot) and per-subspace PQ codebooks (``m`` subquantizers x 256 codes, Euclidean
  k-means over *residuals* ``x - centroid[list]``).  Both run as one jitted
  step function applied to seeded minibatches — the per-center-count learning
  rate is the standard MiniBatchKMeans update.
- **Storage**: uint8 PQ codes packed per IVF list in fixed-capacity device
  blocks ``[nlist, list_cap, m]`` with a validity mask and a row-position map.
  Appends stage on host and flush as ONE bucketed scatter per batch; padding
  slots target the out-of-range list ``nlist`` and rely on ``mode='drop'``
  (the default scatter mode CLAMPS — it would silently corrupt list 0).
  List capacity grows by doubling, same discipline as ``_grow_dev``.
- **Query**: ADC (asymmetric distance computation).  Per query: score the
  ``nlist`` centroids, take the ``nprobe`` best, build a ``[m, 256]`` dot LUT,
  gather the probed lists' codes and accumulate LUT entries with a
  ``fori_loop`` over subspaces (avoids materializing the [Q,P,L,M] f32
  intermediate), take a top-``shortlist``, then rerank the shortlist with
  exact bf16 dots against the row tier and cut to the final k.  The score of
  row x for query q approximates ``q . x = q . c_list + q . residual`` — the
  first term is the centroid score, the second the LUT sum.
- **Liveness**: ``add`` assigns-and-packs without retraining; ``remove``
  tombstones (validity scatter) and compacts lazily past a dead fraction; a
  drift gauge (fraction of sampled rows whose nearest *running-mean* list
  differs from their assigned list) advises retraining; ``probe_recall``
  measures recall@k against this index's own exact rerank tier so every speed
  claim carries an accuracy number.

Untrained indexes and allow-listed searches fall back to the exact kernel over
the rerank tier (identical results to ``VectorIndex``, no recall loss): the
allowlist case is typically a small candidate set where IVF pruning can only
hurt, and it keeps ``AsyncSearcher``'s allowlist bypass semantics intact.

Scores are cosine similarities in [-1, 1] on the same bf16-cast-then-normalize
discipline as ``VectorIndex``, so either index class returns interchangeable
result schemas to ``search_service``.
"""

from __future__ import annotations

import logging
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .knn import (
    _APPEND_BUCKETS,
    _K_BUCKETS,
    _QUERY_BUCKETS,
    _append_rows,
    _bucket,
    _grow_dev,
    _next_cap,
    _normalize,
    _topk_scores,
)

logger = logging.getLogger(__name__)

_CODES = 256  # codes per subquantizer: one uint8
_TRAIN_SAMPLE = 65_536
_TRAIN_BATCH = 4_096
_ENCODE_BATCH = 65_536
_DEF_RERANK = 256
_DEAD_COMPACT_FRAC = 0.25
_DRIFT_ADVISE_FRAC = 0.20


def make_clustered(
    n: int, dim: int, n_clusters: int = 64, seed: int = 0
) -> np.ndarray:
    """Seeded synthetic clustered corpus (the IVF-friendly geometry real
    embedding corpora have).  Shared by tests, bench, and the CLI's
    ``--synthetic`` probe so recall numbers are comparable across all three."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32)
    assign = rng.integers(0, n_clusters, size=n)
    rows = centers[assign] + 0.25 * rng.standard_normal((n, dim)).astype(np.float32)
    return rows.astype(np.float32)


# ------------------------------------------------------------------- training
def _kmeans_step_impl(centroids, counts, batch):
    """One spherical mini-batch k-means step (centroids stay row-normalized).

    counts carry across steps so the per-center learning rate decays like
    MiniBatchKMeans; centers a batch never hits keep their old value.
    """
    sims = batch @ centroids.T  # [B, C]
    assign = jnp.argmax(sims, axis=1)
    one = jax.nn.one_hot(assign, centroids.shape[0], dtype=jnp.float32)  # [B, C]
    n_b = one.sum(axis=0)  # [C]
    sum_b = one.T @ batch  # [C, D]
    new_counts = counts + n_b
    eta = jnp.where(new_counts > 0, n_b / jnp.maximum(new_counts, 1.0), 0.0)[:, None]
    batch_mean = sum_b / jnp.maximum(n_b, 1.0)[:, None]
    mixed = centroids * (1.0 - eta) + jnp.where(n_b[:, None] > 0, batch_mean, centroids) * eta
    norm = jnp.linalg.norm(mixed, axis=1, keepdims=True)
    return mixed / jnp.maximum(norm, 1e-12), new_counts


_kmeans_step = jax.jit(_kmeans_step_impl)


def _pq_step_impl(codebooks, counts, batch):
    """One mini-batch k-means step per PQ subspace, all m subspaces in one
    program.  batch is residuals reshaped [B, m, sub_dim]; Euclidean
    assignment via |c|^2 - 2 r.c (|r|^2 is constant per row)."""
    c2 = jnp.sum(codebooks * codebooks, axis=-1)  # [m, 256]
    rc = jnp.einsum("bms,mcs->bmc", batch, codebooks)  # [B, m, 256]
    assign = jnp.argmin(c2[None] - 2.0 * rc, axis=-1)  # [B, m]
    one = jax.nn.one_hot(assign, _CODES, dtype=jnp.float32)  # [B, m, 256]
    n_b = one.sum(axis=0)  # [m, 256]
    sum_b = jnp.einsum("bmc,bms->mcs", one, batch)
    new_counts = counts + n_b
    eta = jnp.where(new_counts > 0, n_b / jnp.maximum(new_counts, 1.0), 0.0)[..., None]
    batch_mean = sum_b / jnp.maximum(n_b, 1.0)[..., None]
    upd = jnp.where(n_b[..., None] > 0, batch_mean, codebooks)
    return codebooks * (1.0 - eta) + upd * eta, new_counts


_pq_step = jax.jit(_pq_step_impl)


def _assign_impl(centroids, rows):
    """Two nearest lists per row: [B,D] -> [B,2].  The runner-up is the spill
    target when the nearest list is at capacity (list balancing)."""
    sims = rows @ centroids.T
    _, lists2 = jax.lax.top_k(sims, 2)
    return lists2.astype(jnp.int32)


_assign = jax.jit(_assign_impl)


def _encode_assigned_impl(centroids, codebooks, rows, lists):
    """PQ-encode residuals against the list each row actually LIVES in (which
    may be its spill list): score reconstruction at query time is
    ``q.c_list + q.residual`` — encoding against any other centroid would
    shift every spilled row's score by ``q.(c_spill - c_nearest)``."""
    resid = rows - jnp.take(centroids, lists, axis=0)
    b = rows.shape[0]
    m, _, sub = codebooks.shape
    r = resid.reshape(b, m, sub)
    c2 = jnp.sum(codebooks * codebooks, axis=-1)
    rc = jnp.einsum("bms,mcs->bmc", r, codebooks)
    return jnp.argmin(c2[None] - 2.0 * rc, axis=-1).astype(jnp.uint8)


_encode_assigned = jax.jit(_encode_assigned_impl)


# -------------------------------------------------------------------- storage
def _scatter_codes_impl(codes, lvalid, rowpos, li, si, c, pos):
    """Pack an append batch into its list slots in one scatter.

    Padding entries carry ``li == nlist`` (out of range): ``mode='drop'``
    discards them.  The DEFAULT scatter mode clamps out-of-range indices and
    would overwrite real slots in the last list — never remove the mode here.
    """
    codes = codes.at[li, si].set(c, mode="drop")
    lvalid = lvalid.at[li, si].set(True, mode="drop")
    rowpos = rowpos.at[li, si].set(pos, mode="drop")
    return codes, lvalid, rowpos


_scatter_codes = jax.jit(_scatter_codes_impl)


def _tombstone_impl(lvalid, li, si):
    return lvalid.at[li, si].set(False, mode="drop")


_tombstone = jax.jit(_tombstone_impl)


def _mask_positions_impl(rvalid, pos):
    return rvalid.at[pos].set(False, mode="drop")


_mask_positions = jax.jit(_mask_positions_impl)


def _grow_lists_impl(codes, lvalid, rowpos, new_cap: int):
    pad = new_cap - codes.shape[1]
    codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)))
    lvalid = jnp.pad(lvalid, ((0, 0), (0, pad)))
    rowpos = jnp.pad(rowpos, ((0, 0), (0, pad)))
    return codes, lvalid, rowpos


_grow_lists = jax.jit(_grow_lists_impl, static_argnums=(3,))


# ---------------------------------------------------------------------- query
def _adc_body(lut, flat_codes, m: int):
    """Sum LUT entries over subspaces with a fori_loop — memory-bounded.

    A vectorized ``take_along_axis`` over all m at once materializes a
    [Q, P*L, m] f32 gather (~1.6 GB at 1M-row geometry); the loop keeps the
    live intermediate at [Q, P*L].
    """

    def body(j, acc):
        lut_j = jax.lax.dynamic_index_in_dim(lut, j, axis=1, keepdims=False)  # [Q,256]
        c_j = jax.lax.dynamic_slice_in_dim(flat_codes, j, 1, axis=2)[..., 0]  # [Q,PL]
        return acc + jnp.take_along_axis(lut_j, c_j, axis=1)

    init = jnp.zeros(flat_codes.shape[:2], jnp.float32)
    return jax.lax.fori_loop(0, m, body, init)


def _adc_shortlist_impl(centroids, codebooks, codes, lvalid, rowpos, q, nprobe: int, shortlist: int):
    """Scan the nprobe nearest lists' codes and return a top-``shortlist`` of
    (approximate score, row position) per query."""
    q_n = q.shape[0]
    nlist, list_cap, m = codes.shape
    sub = codebooks.shape[2]
    csim = q @ centroids.T  # [Q, nlist]
    top_c, top_ci = jax.lax.top_k(csim, nprobe)  # [Q, P]
    lut = jnp.einsum("qms,mcs->qmc", q.reshape(q_n, m, sub), codebooks)  # [Q, m, 256]
    pc = jnp.take(codes, top_ci, axis=0)  # [Q, P, L, m] uint8
    pv = jnp.take(lvalid, top_ci, axis=0)  # [Q, P, L]
    pp = jnp.take(rowpos, top_ci, axis=0)  # [Q, P, L]
    flat_codes = pc.reshape(q_n, nprobe * list_cap, m).astype(jnp.int32)
    adc = _adc_body(lut, flat_codes, m)  # [Q, P*L]
    # score ~= q.c_list + q.residual; repeat() lays centroid scores out in the
    # same (probe-major, slot-minor) order as the reshape above
    scores = jnp.repeat(top_c, list_cap, axis=1) + adc
    scores = jnp.where(pv.reshape(q_n, -1), scores, -jnp.inf)
    sl_scores, sl_i = jax.lax.top_k(scores, shortlist)
    sl_pos = jnp.take_along_axis(pp.reshape(q_n, -1), sl_i, axis=1)
    return sl_scores, sl_pos


_adc_shortlist = jax.jit(_adc_shortlist_impl, static_argnums=(6, 7))


def _rerank_impl(rerank, rvalid, q, sl_scores, sl_pos, k: int):
    """Exact bf16 dot over the shortlist rows, final top-k.

    Shortlist entries that were -inf (mask padding) gather row 0 via the
    clipped take — the finiteness/validity mask drops them before top_k."""
    rows = jnp.take(rerank, sl_pos, axis=0)  # [Q, S, D] bf16
    exact = jnp.einsum(
        "qd,qsd->qs", q.astype(jnp.bfloat16), rows, preferred_element_type=jnp.float32
    )
    ok = jnp.isfinite(sl_scores) & jnp.take(rvalid, sl_pos, axis=0)
    exact = jnp.where(ok, exact, -jnp.inf)
    s_fin, i_fin = jax.lax.top_k(exact, k)
    pos_fin = jnp.take_along_axis(sl_pos, i_fin, axis=1)
    return s_fin, pos_fin


_rerank = jax.jit(_rerank_impl, static_argnums=(5,))


_sharded_adc_cache: dict = {}


def _sharded_adc_shortlist(mesh, centroids, codebooks, codes, lvalid, rowpos, q, nprobe: int, shortlist: int):
    """ADC shortlist with code blocks sharded over the mesh ``data`` axis by
    IVF list.  Each device scans the probed lists it owns (out-of-shard probes
    are masked), takes a local top-shortlist, and an all_gather + final top-k
    merges — the same local-merge reduction as ``_sharded_topk``, but over
    shortlist candidates instead of corpus rows.  The rerank tier stays
    replicated; the rerank kernel runs outside the shard_map.
    """
    from jax.sharding import PartitionSpec as P

    key = (id(mesh), nprobe, shortlist, codes.shape, q.shape)
    fn = _sharded_adc_cache.get(key)
    if fn is None:
        n_shards = mesh.shape["data"]
        nlist, list_cap, m = codes.shape
        nl_loc = nlist // n_shards
        sl_loc = min(shortlist, nprobe * list_cap)

        def local_scan(codes_l, lvalid_l, rowpos_l, centroids_r, codebooks_r, q_r):
            q_n = q_r.shape[0]
            sub = codebooks_r.shape[2]
            csim = q_r @ centroids_r.T
            top_c, top_ci = jax.lax.top_k(csim, nprobe)
            off = jax.lax.axis_index("data") * nl_loc
            li = top_ci - off
            in_shard = (li >= 0) & (li < nl_loc)
            li_c = jnp.clip(li, 0, nl_loc - 1)
            lut = jnp.einsum("qms,mcs->qmc", q_r.reshape(q_n, m, sub), codebooks_r)
            pc = jnp.take(codes_l, li_c, axis=0)
            pv = jnp.take(lvalid_l, li_c, axis=0) & in_shard[..., None]
            pp = jnp.take(rowpos_l, li_c, axis=0)
            flat_codes = pc.reshape(q_n, nprobe * list_cap, m).astype(jnp.int32)
            adc = _adc_body(lut, flat_codes, m)
            scores = jnp.repeat(top_c, list_cap, axis=1) + adc
            scores = jnp.where(pv.reshape(q_n, -1), scores, -jnp.inf)
            s_loc, s_i = jax.lax.top_k(scores, sl_loc)
            p_loc = jnp.take_along_axis(pp.reshape(q_n, -1), s_i, axis=1)
            s_all = jax.lax.all_gather(s_loc, "data", axis=1, tiled=True)
            p_all = jax.lax.all_gather(p_loc, "data", axis=1, tiled=True)
            s_fin, sel = jax.lax.top_k(s_all, shortlist)
            p_fin = jnp.take_along_axis(p_all, sel, axis=1)
            return s_fin, p_fin

        fn = jax.jit(
            jax.shard_map(
                local_scan,
                mesh=mesh,
                in_specs=(P("data"), P("data"), P("data"), P(), P(), P()),
                out_specs=(P(), P()),
                # outputs are replicated by the all_gather + identical final
                # top_k, which the static VMA check can't prove
                check_vma=False,
            )
        )
        _sharded_adc_cache[key] = fn
    return fn(codes, lvalid, rowpos, centroids, codebooks, q)


def _spill_assign(lists2: np.ndarray, fill: np.ndarray, cap: int) -> np.ndarray:
    """Capacity-respecting list assignment (host-side, vectorized).

    Rows go to their nearest list until it reaches the soft cap; overflow rows
    go to their runner-up if it has room, else stay (the cap is soft — the
    block capacity just grows).  Bounds the dense-block scan cost at
    ``nprobe * O(avg fill)`` instead of ``nprobe * max fill``: unbalanced
    k-means lists otherwise make every probe pay for the biggest list.
    Mutates ``fill`` to the resulting per-list occupancy.
    """
    n = lists2.shape[0]
    l1 = lists2[:, 0].astype(np.int64)
    l2 = lists2[:, 1].astype(np.int64)
    out = l1.astype(np.int32).copy()
    counts = np.bincount(l1, minlength=fill.shape[0])
    cum = np.concatenate([[0], np.cumsum(counts)])
    order = np.argsort(l1, kind="stable")
    rank = np.empty((n,), np.int64)
    rank[order] = np.arange(n) - cum[l1[order]]
    overflow = rank + fill[l1] >= cap
    ov = np.nonzero(overflow)[0]
    np.add.at(fill, out[~overflow], 1)
    # exact greedy over the overflow tail only (a small fraction of n): rows
    # whose runner-up is ALSO full stay in their nearest list past the cap —
    # the cap is soft and the block capacity grows to cover them
    for j in ov:
        t = int(l2[j])
        if fill[t] >= cap:
            t = int(l1[j])
        out[j] = t
        fill[t] += 1
    return out


def _auto_m(dim: int) -> int:
    """Largest reasonable subquantizer count: prefer ~8-d subspaces, fall back
    to any divisor giving sub_dim >= 2."""
    for sub in (8, 12, 16, 4, 6, 24, 32, 2, 3):
        if dim % sub == 0 and dim // sub >= 1:
            return dim // sub
    return 1


def _auto_nlist(n: int, shards: int = 1) -> int:
    """~2*sqrt(n) lists, power-of-two-ish, multiple of the mesh shard count."""
    base = max(8, shards)
    return min(4096 * max(1, shards), _next_cap(base, max(8, int(2.0 * math.sqrt(max(1, n))))))


class ANNIndex:
    """IVF-PQ approximate index with the ``VectorIndex`` search surface.

    Thread-safe under the same single-leaf-lock discipline as the exact index:
    mutators build new device arrays and swap them under ``_lock``; searches
    snapshot the handles under the lock and compute outside it, so in-flight
    queries always see an internally consistent (codes, rerank, ids) triple
    even while ingestion appends concurrently.

    ``mesh`` shards the code blocks over the ``data`` axis by IVF list; the
    centroids, codebooks, and rerank tier stay replicated.
    """

    def __init__(
        self,
        dim: int,
        mesh=None,
        nlist: int = 0,
        m: int = 0,
        nprobe: int = 0,
        rerank_depth: int = _DEF_RERANK,
        seed: int = 0,
        mat_alloc=None,
    ):
        self.dim = dim
        self.mesh = mesh
        self.nlist = int(nlist)
        self.m = int(m) if m else _auto_m(dim)
        if dim % self.m:
            raise ValueError(f"m={self.m} must divide dim={dim}")
        self.sub_dim = dim // self.m
        self.nprobe = int(nprobe)
        self.rerank_depth = int(rerank_depth)
        self.seed = int(seed)
        self.drift_threshold = _DRIFT_ADVISE_FRAC
        self._lock = threading.Lock()
        # host f32 row tier allocator — the durability plane injects an
        # mmap-backed allocator here so corpora past host RAM page from disk;
        # the device rerank tier is unaffected (bf16 copies still live in HBM)
        self._mat_alloc = mat_alloc or (lambda shape: np.empty(shape, np.float32))
        # host row tier (raw f32, positions append-only between restages)
        self._ids: list[int] = []
        self._id_pos: dict[int, int] = {}
        self._mat = self._mat_alloc((0, dim))
        self._n = 0
        self._dead: set[int] = set()
        # device rerank tier (bf16 normalized rows + validity)
        self._rerank: Optional[jnp.ndarray] = None
        self._rvalid: Optional[jnp.ndarray] = None
        self._rerank_count = 0
        self._snapshot_ids: list[int] = []
        self._rerank_dirty = True
        # trained state
        self._trained = False
        self._centroids: Optional[jnp.ndarray] = None
        self._codebooks: Optional[jnp.ndarray] = None
        self._codes: Optional[jnp.ndarray] = None
        self._lvalid: Optional[jnp.ndarray] = None
        self._rowpos: Optional[jnp.ndarray] = None
        self._list_counts = np.zeros((0,), np.int64)
        self._row_list = np.empty((0,), np.int32)  # position -> IVF list (-1 = none)
        self._row_slot = np.empty((0,), np.int32)
        # drift gauge state: running sums of appended/encoded normalized rows
        self._list_sums = np.zeros((0, dim), np.float32)
        self._list_nums = np.zeros((0,), np.int64)
        self._drift_frac = 0.0
        self._drift_stale = 0
        # counters
        self.searches = 0
        self.compactions = 0
        self.retrains = 0
        self.appended_since_train = 0
        self.last_recall: Optional[dict] = None

    def __len__(self) -> int:
        # live rows — tombstoned entries are gone from the caller's view even
        # before compaction reclaims their slots
        return self._n - len(self._dead)

    # ------------------------------------------------------------------ config
    def _shards(self) -> int:
        return self.mesh.shape.get("data", 1) if self.mesh is not None else 1

    def _put(self, arr: jnp.ndarray, sharded: bool) -> jnp.ndarray:
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P("data") if sharded else P()
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _nprobe_eff(self, nprobe: Optional[int] = None) -> int:
        # with balanced lists + a deep exact rerank, recall saturates at a
        # small probe fraction (measured: flat from nprobe=16 at nlist=1024)
        p = int(nprobe) if nprobe else (self.nprobe or max(8, self.nlist // 64))
        return max(1, min(p, self.nlist))

    # ---------------------------------------------------------------- mutation
    def _grow_host(self, need: int) -> None:
        cap = _next_cap(max(1024, self._mat.shape[0]), need)
        if cap != self._mat.shape[0]:
            new_mat = self._mat_alloc((cap, self.dim))
            new_mat[: self._n] = self._mat[: self._n]
            self._mat = new_mat
            for name in ("_row_list", "_row_slot"):
                old = getattr(self, name)
                new_arr = np.full((cap,), -1, np.int32)
                new_arr[: old.shape[0]] = old
                setattr(self, name, new_arr)

    def add(self, ids: Sequence[int], vectors: np.ndarray) -> None:
        vectors = np.asarray(vectors, np.float32).reshape(-1, self.dim)
        ids = [int(i) for i in ids]
        with self._lock:
            self._add_locked(ids, vectors)

    def _add_locked(self, ids: list[int], vectors: np.ndarray) -> None:
        # overwrite semantics: tombstone the old slot, append the new row —
        # positions are append-only so in-flight searches stay consistent
        old_positions = [self._id_pos[i] for i in ids if i in self._id_pos]
        if old_positions:
            self._tombstone_locked(old_positions)
        m_rows = len(ids)
        start = self._n
        self._grow_host(start + m_rows)
        self._mat[start : start + m_rows] = vectors
        last = {}
        for j, i in enumerate(ids):
            last[i] = start + j  # duplicate ids in one batch: last write wins
        dup_dead = [start + j for j, i in enumerate(ids) if last[i] != start + j]
        self._ids.extend(ids)
        self._id_pos.update(last)
        self._n = start + m_rows
        if self._trained:
            self._append_trained_locked(start, m_rows, dup_dead)
        else:
            self._dead.update(dup_dead)
            self._rerank_dirty = True

    def add_device(self, ids: Sequence[int], rows) -> None:
        """API-compat with ``VectorIndex``: encode needs host rows anyway (list
        slot allocation is host logic), so fetch and take the host path."""
        self.add(ids, np.asarray(jax.device_get(jnp.asarray(rows)), np.float32))

    def reserve(self, n: int) -> None:
        with self._lock:
            self._grow_host(n)

    def remove(self, ids: Sequence[int]) -> None:
        with self._lock:
            drop = [self._id_pos[int(i)] for i in ids if int(i) in self._id_pos]
            if not drop:
                return
            for i in ids:
                self._id_pos.pop(int(i), None)
            self._tombstone_locked(drop)
            if not self._trained:
                self._rerank_dirty = True
        self._maybe_compact()

    def clear(self) -> None:
        with self._lock:
            self._ids, self._id_pos = [], {}
            self._mat = self._mat_alloc((0, self.dim))
            self._n = 0
            self._dead = set()
            self._rerank = self._rvalid = None
            self._rerank_count = 0
            self._snapshot_ids = []
            self._rerank_dirty = True
            self._trained = False
            self._centroids = self._codebooks = None
            self._codes = self._lvalid = self._rowpos = None
            self._list_counts = np.zeros((0,), np.int64)
            self._row_list = np.empty((0,), np.int32)
            self._row_slot = np.empty((0,), np.int32)
            self._list_sums = np.zeros((0, self.dim), np.float32)
            self._list_nums = np.zeros((0,), np.int64)
            self._drift_frac = 0.0
            self._drift_stale = 0
            self.appended_since_train = 0

    def _tombstone_locked(self, positions: list[int]) -> None:
        """Mark positions dead: host set + list-validity and rerank-validity
        scatters (bucketed, padded with out-of-range indices -> dropped)."""
        fresh = [p for p in positions if p not in self._dead]
        if not fresh:
            return
        self._dead.update(fresh)
        if self._trained and self._codes is not None:
            assigned = [p for p in fresh if self._row_list[p] >= 0]
            if assigned:
                bkt = _bucket(len(assigned), _APPEND_BUCKETS)
                li = np.full((bkt,), self.nlist, np.int32)  # pad -> dropped
                si = np.zeros((bkt,), np.int32)
                li[: len(assigned)] = self._row_list[assigned]
                si[: len(assigned)] = self._row_slot[assigned]
                self._lvalid = self._put(
                    _tombstone(self._lvalid, jnp.asarray(li), jnp.asarray(si)),
                    sharded=True,
                )
        if self._rvalid is not None and self._rerank_count:
            in_tier = [p for p in fresh if p < self._rerank_count]
            if in_tier:
                bkt = _bucket(len(in_tier), _APPEND_BUCKETS)
                pos = np.full((bkt,), self._rvalid.shape[0], np.int32)  # pad -> dropped
                pos[: len(in_tier)] = in_tier
                self._rvalid = self._put(
                    _mask_positions(self._rvalid, jnp.asarray(pos)), sharded=False
                )

    def _append_rerank_locked(self, start: int, rows_f32: np.ndarray) -> None:
        """Bucketed device append into the rerank tier (bf16-then-normalize,
        same bit discipline as the exact index).  Caller holds ``_lock``."""
        m_rows = rows_f32.shape[0]
        bkt = _bucket(m_rows, _APPEND_BUCKETS)
        cap = 0 if self._rerank is None else self._rerank.shape[0]
        if self._rerank is None:
            new_cap = _next_cap(1024, start + bkt)
            self._rerank = self._put(
                jnp.zeros((new_cap, self.dim), jnp.bfloat16), sharded=False
            )
            self._rvalid = self._put(jnp.zeros((new_cap,), bool), sharded=False)
        elif start + bkt > cap:
            grown = _grow_dev(self._rerank, self._rvalid, _next_cap(cap, start + bkt))
            self._rerank = self._put(grown[0], sharded=False)
            self._rvalid = self._put(grown[1], sharded=False)
        fresh = rows_f32.astype(np.dtype(jnp.bfloat16))
        if bkt != m_rows:
            fresh = np.concatenate(
                [fresh, np.zeros((bkt - m_rows, self.dim), fresh.dtype)]
            )
        fresh_valid = np.zeros((bkt,), bool)
        fresh_valid[:m_rows] = True
        out = _append_rows(
            self._rerank, self._rvalid, jnp.asarray(fresh), jnp.asarray(fresh_valid), start
        )
        self._rerank = self._put(out[0], sharded=False)
        self._rvalid = self._put(out[1], sharded=False)
        self._rerank_count = max(self._rerank_count, start + m_rows)
        self._snapshot_ids = self._ids

    @staticmethod
    def _pad_rows(rows: np.ndarray, bkt: int) -> np.ndarray:
        if bkt == rows.shape[0]:
            return rows
        pad_shape = (bkt - rows.shape[0],) + rows.shape[1:]
        return np.concatenate([rows, np.zeros(pad_shape, rows.dtype)])

    def _assign_batch(self, centroids, rows_norm: np.ndarray) -> np.ndarray:
        """Top-2 list candidates per row, padded to an append bucket so the
        kernel compiles once per bucket."""
        m_rows = rows_norm.shape[0]
        bkt = _bucket(m_rows, _APPEND_BUCKETS)
        lists2 = jax.device_get(
            _assign(centroids, jnp.asarray(self._pad_rows(rows_norm, bkt)))
        )
        return np.asarray(lists2[:m_rows])

    def _encode_assigned_batch(
        self, centroids, codebooks, rows_norm: np.ndarray, lists: np.ndarray
    ) -> np.ndarray:
        m_rows = rows_norm.shape[0]
        bkt = _bucket(m_rows, _APPEND_BUCKETS)
        codes = jax.device_get(
            _encode_assigned(
                centroids,
                codebooks,
                jnp.asarray(self._pad_rows(rows_norm, bkt)),
                jnp.asarray(self._pad_rows(lists.astype(np.int32), bkt)),
            )
        )
        return np.asarray(codes[:m_rows])

    def _scatter_batch_locked(
        self, positions: np.ndarray, lists: np.ndarray, codes: np.ndarray
    ) -> None:
        """Allocate list slots host-side and flush ONE bucketed scatter."""
        m_rows = positions.shape[0]
        if not m_rows:
            return
        slots = np.empty((m_rows,), np.int32)
        for j in range(m_rows):
            li = int(lists[j])
            slots[j] = self._list_counts[li]
            self._list_counts[li] += 1
        need = int(self._list_counts.max())
        list_cap = self._codes.shape[1]
        if need > list_cap:
            new_cap = _next_cap(list_cap, need)
            grown = _grow_lists(self._codes, self._lvalid, self._rowpos, new_cap)
            self._codes = self._put(grown[0], sharded=True)
            self._lvalid = self._put(grown[1], sharded=True)
            self._rowpos = self._put(grown[2], sharded=True)
        bkt = _bucket(m_rows, _APPEND_BUCKETS)
        li = np.full((bkt,), self.nlist, np.int32)  # pad -> out of range -> dropped
        si = np.zeros((bkt,), np.int32)
        cc = np.zeros((bkt, self.m), np.uint8)
        pp = np.zeros((bkt,), np.int32)
        li[:m_rows] = lists
        si[:m_rows] = slots
        cc[:m_rows] = codes
        pp[:m_rows] = positions
        out = _scatter_codes(
            self._codes,
            self._lvalid,
            self._rowpos,
            jnp.asarray(li),
            jnp.asarray(si),
            jnp.asarray(cc),
            jnp.asarray(pp),
        )
        self._codes = self._put(out[0], sharded=True)
        self._lvalid = self._put(out[1], sharded=True)
        self._rowpos = self._put(out[2], sharded=True)
        self._row_list[positions] = lists
        self._row_slot[positions] = slots

    def _append_trained_locked(self, start: int, m_rows: int, dup_dead: list[int]) -> None:
        """Incremental append on a trained index: encode with the CURRENT
        quantizers (no retrain), pack, extend the rerank tier, feed the drift
        gauge.  Caller holds ``_lock``."""
        rows_norm = _normalize(self._mat[start : start + m_rows])
        lists2 = self._assign_batch(self._centroids, rows_norm)
        # spill against a copy: _scatter_batch_locked owns the real counters
        cap_soft = max(32, self._codes.shape[1]) if self._codes is not None else 1 << 30
        lists = _spill_assign(lists2, self._list_counts.copy(), cap_soft)
        codes = self._encode_assigned_batch(
            self._centroids, self._codebooks, rows_norm, lists
        )
        keep = np.ones((m_rows,), bool)
        for p in dup_dead:
            keep[p - start] = False
        positions = start + np.nonzero(keep)[0].astype(np.int32)
        self._scatter_batch_locked(positions, lists[keep], codes[keep])
        # all rows append to the rerank tier (positions are contiguous);
        # duplicate-in-batch losers never reach the code blocks and their
        # rerank rows are masked dead right after
        self._append_rerank_locked(start, self._mat[start : start + m_rows])
        if dup_dead:
            self._tombstone_locked(list(dup_dead))
        np.add.at(self._list_sums, lists[keep], rows_norm[keep])
        np.add.at(self._list_nums, lists[keep], 1)
        self.appended_since_train += int(keep.sum())
        self._drift_stale += int(keep.sum())
        if self._drift_stale >= max(1024, self._n // 50):
            self._refresh_drift_locked()

    # ---------------------------------------------------------------- training
    def train(
        self,
        nlist: int = 0,
        iters: int = 4,
        sample: int = _TRAIN_SAMPLE,
        seed: Optional[int] = None,
    ) -> "ANNIndex":
        """(Re)learn the coarse quantizer + PQ codebooks from a seeded sample
        of the live rows, then re-encode and re-stage everything.  Host-driven
        and off the query hot path — searches keep running against the old
        arrays until the swap at the end."""
        self._restage(retrain=True, nlist=nlist, iters=iters, sample=sample, seed=seed)
        return self

    def compact(self) -> None:
        """Reclaim tombstoned slots: rebuild positions from live rows and
        re-encode with the existing quantizers (no re-learning)."""
        self._restage(retrain=False)

    def _maybe_compact(self) -> None:
        with self._lock:
            n, dead = self._n, len(self._dead)
        if n and dead / n > _DEAD_COMPACT_FRAC:
            self.compact()

    def _learn(self, rows_norm: np.ndarray, nlist: int, iters: int, rng):
        """Mini-batch k-means for centroids, then PQ codebooks over residuals.
        Returns device (centroids, codebooks) — the caller swaps them in under
        the lock so a concurrent search never sees new centroids with old
        codes."""
        n = rows_norm.shape[0]
        init = rng.choice(n, size=min(nlist, n), replace=False)
        cent = np.zeros((nlist, self.dim), np.float32)
        cent[: init.shape[0]] = rows_norm[init]
        if init.shape[0] < nlist:  # fewer rows than lists: pad with jittered repeats
            extra = rows_norm[rng.integers(0, n, nlist - init.shape[0])]
            cent[init.shape[0] :] = extra + 1e-3 * rng.standard_normal(extra.shape).astype(
                np.float32
            )
        cent = _normalize(cent)
        centroids = jnp.asarray(cent)
        counts = jnp.zeros((nlist,), jnp.float32)
        for _ in range(max(1, iters)):
            order = rng.permutation(n)
            for s in range(0, n, _TRAIN_BATCH):
                batch = jnp.asarray(rows_norm[order[s : s + _TRAIN_BATCH]])
                centroids, counts = _kmeans_step(centroids, counts, batch)
        # PQ over residuals of the sample under the final centroids
        lists = np.asarray(jax.device_get(_assign(centroids, jnp.asarray(rows_norm))))[:, 0]
        resid = rows_norm - jax.device_get(centroids)[lists]
        resid = resid.reshape(n, self.m, self.sub_dim)
        cinit = rng.choice(n, size=min(_CODES, n), replace=False)
        cb = np.zeros((self.m, _CODES, self.sub_dim), np.float32)
        cb[:, : cinit.shape[0]] = resid[cinit].transpose(1, 0, 2)
        codebooks = jnp.asarray(cb)
        ccounts = jnp.zeros((self.m, _CODES), jnp.float32)
        for _ in range(max(1, iters)):
            order = rng.permutation(n)
            for s in range(0, n, _TRAIN_BATCH):
                batch = jnp.asarray(resid[order[s : s + _TRAIN_BATCH]])
                codebooks, ccounts = _pq_step(codebooks, ccounts, batch)
        return self._put(centroids, sharded=False), self._put(codebooks, sharded=False)

    def _encode_pack(self, live_rows: np.ndarray, all_lists: np.ndarray,
                     centroids, codebooks, nlist_eff: int):
        """Encode every row against its ASSIGNED list and pack the device code
        blocks — shared by ``_restage`` (fresh spill assignment) and
        ``restore_state`` (assignment read back from a snapshot, so restored
        placement — and therefore every ADC score — matches pre-crash bits).

        Returns ``(codes_d, lvalid_d, rowpos_d, counts, row_slot, sums)``.
        """
        n = live_rows.shape[0]
        all_codes = np.empty((n, self.m), np.uint8)
        for s in range(0, n, _ENCODE_BATCH):
            e = min(n, s + _ENCODE_BATCH)
            all_codes[s:e] = jax.device_get(
                _encode_assigned(
                    centroids,
                    codebooks,
                    jnp.asarray(_normalize(live_rows[s:e])),
                    jnp.asarray(all_lists[s:e]),
                )
            )
        counts = np.bincount(all_lists, minlength=nlist_eff).astype(np.int64)
        # tight rounding (multiple of 128, not power of two): list_cap directly
        # multiplies every probe's scan cost; append-time growth stays geometric
        list_cap = max(32, -(-int(counts.max(initial=0)) // 128) * 128)
        # vectorized host-side packing (stable argsort gives each row its slot
        # within its list), then one sharded device_put per array
        order = np.argsort(all_lists, kind="stable")
        cum = np.concatenate([[0], np.cumsum(counts)])
        row_slot = np.empty((n,), np.int32)
        row_slot[order] = (np.arange(n) - cum[all_lists[order]]).astype(np.int32)
        codes_h = np.zeros((nlist_eff, list_cap, self.m), np.uint8)
        lvalid_h = np.zeros((nlist_eff, list_cap), bool)
        rowpos_h = np.zeros((nlist_eff, list_cap), np.int32)
        codes_h[all_lists, row_slot] = all_codes
        lvalid_h[all_lists, row_slot] = True
        rowpos_h[all_lists, row_slot] = np.arange(n, dtype=np.int32)
        codes_d = self._put(jnp.asarray(codes_h), sharded=True)
        lvalid_d = self._put(jnp.asarray(lvalid_h), sharded=True)
        rowpos_d = self._put(jnp.asarray(rowpos_h), sharded=True)
        # drift gauge restarts from the fresh assignment
        sums = np.zeros((nlist_eff, self.dim), np.float32)
        np.add.at(sums, all_lists, _normalize(live_rows))
        return codes_d, lvalid_d, rowpos_d, counts, row_slot, sums

    def _restage(
        self,
        retrain: bool,
        nlist: int = 0,
        iters: int = 4,
        sample: int = _TRAIN_SAMPLE,
        seed: Optional[int] = None,
    ) -> None:
        """Rebuild the whole device state from live host rows.  Compaction =
        restage with the existing quantizers; (re)train = learn first.

        Everything is computed into fresh arrays and swapped in under the lock
        at the end, so concurrent searches never see a half-built index.
        Mutations that land DURING the rebuild (the task plane keeps ingesting)
        are captured as a delta at swap time and replayed through the normal
        append/tombstone paths."""
        with self._lock:
            n0 = self._n
            dead0 = set(self._dead)
            live_mask = np.ones((n0,), bool)
            for p in dead0:
                live_mask[p] = False
            live_rows = self._mat[:n0][live_mask].copy()
            live_ids = [i for p, i in enumerate(self._ids[:n0]) if live_mask[p]]
        n = live_rows.shape[0]
        if n == 0:
            with self._lock:
                self._swap_empty_locked()
            return
        rng = np.random.default_rng(self.seed if seed is None else seed)
        centroids, codebooks = self._centroids, self._codebooks
        nlist_eff = self.nlist
        if retrain or centroids is None:
            nlist_eff = int(nlist) or self.nlist or _auto_nlist(n, self._shards())
            nlist_eff = _next_cap(self._shards(), nlist_eff)  # mesh: even split
            take = rng.choice(n, size=min(n, sample), replace=False)
            centroids, codebooks = self._learn(
                _normalize(live_rows[take]), nlist_eff, iters, rng
            )
        # assign every live row (top-2 candidates), balance with spill, then
        # re-encode against the FINAL placement
        all_lists2 = np.empty((n, 2), np.int32)
        for s in range(0, n, _ENCODE_BATCH):
            e = min(n, s + _ENCODE_BATCH)
            all_lists2[s:e] = jax.device_get(
                _assign(centroids, jnp.asarray(_normalize(live_rows[s:e])))
            )
        cap_soft = max(32, _next_cap(32, 2 * max(1, -(-n // nlist_eff))))
        fill = np.zeros((nlist_eff,), np.int64)
        all_lists = _spill_assign(all_lists2, fill, cap_soft)
        (codes_d, lvalid_d, rowpos_d, counts, row_slot, sums) = self._encode_pack(
            live_rows, all_lists, centroids, codebooks, nlist_eff
        )
        with self._lock:
            was_trained = self._trained
            # capture mutations that raced the rebuild, replayed after the swap
            removed_ids = [self._ids[p] for p in self._dead - dead0 if p < n0]
            delta = [
                (self._ids[p], self._mat[p].copy())
                for p in range(n0, self._n)
                if p not in self._dead
            ]
            self._ids = live_ids
            self._id_pos = {i: p for p, i in enumerate(live_ids)}
            cap = _next_cap(1024, n)
            mat = self._mat_alloc((cap, self.dim))
            mat[:n] = live_rows
            self._mat = mat
            self._n = n
            self._dead = set()
            self.nlist = nlist_eff
            self._centroids, self._codebooks = centroids, codebooks
            self._codes, self._lvalid, self._rowpos = codes_d, lvalid_d, rowpos_d
            self._list_counts = counts
            rl = np.full((cap,), -1, np.int32)
            rs = np.full((cap,), -1, np.int32)
            rl[:n] = all_lists
            rs[:n] = row_slot
            self._row_list, self._row_slot = rl, rs
            self._list_sums = sums
            self._list_nums = counts.copy()
            self._drift_frac = 0.0
            self._drift_stale = 0
            if was_trained and retrain:
                self.retrains += 1
            self._trained = True
            self.appended_since_train = 0
            # rebuild the rerank tier from scratch at the new positions
            self._rerank = None
            self._rvalid = None
            self._rerank_count = 0
            for s in range(0, n, _ENCODE_BATCH):
                e = min(n, s + _ENCODE_BATCH)
                self._append_rerank_locked(s, live_rows[s:e])
            self._snapshot_ids = self._ids
            self._rerank_dirty = False
            if was_trained and not retrain:
                self.compactions += 1
            for rid in removed_ids:
                pos = self._id_pos.pop(rid, None)
                if pos is not None:
                    self._tombstone_locked([pos])
            if delta:
                self._add_locked(
                    [i for i, _ in delta], np.stack([r for _, r in delta])
                )

    def _swap_empty_locked(self) -> None:
        """Everything was removed while (re)staging: reset to untrained empty."""
        self._ids, self._id_pos = [], {}
        self._mat = self._mat_alloc((0, self.dim))
        self._n = 0
        self._dead = set()
        self._rerank = self._rvalid = None
        self._rerank_count = 0
        self._snapshot_ids = []
        self._rerank_dirty = True
        self._trained = False
        self._centroids = self._codebooks = None
        self._codes = self._lvalid = self._rowpos = None
        self._list_counts = np.zeros((0,), np.int64)
        self._row_list = np.empty((0,), np.int32)
        self._row_slot = np.empty((0,), np.int32)
        self._list_sums = np.zeros((0, self.dim), np.float32)
        self._list_nums = np.zeros((0,), np.int64)
        self.appended_since_train = 0

    # ------------------------------------------------------------------- drift
    def _refresh_drift_locked(self, sample: int = 512) -> None:
        """Fraction of sampled assigned rows whose nearest *running-mean* list
        differs from their assigned list.  The running means track what the
        centroids WOULD look like if retrained on everything seen so far, so
        the gauge rises as ingestion shifts the distribution."""
        self._drift_stale = 0
        assigned = np.nonzero(self._row_list[: self._n] >= 0)[0]
        if self._dead:
            assigned = assigned[~np.isin(assigned, list(self._dead))]
        if assigned.shape[0] == 0 or self._list_nums.sum() == 0:
            self._drift_frac = 0.0
            return
        rng = np.random.default_rng(self.seed + 1)
        take = rng.choice(assigned, size=min(sample, assigned.shape[0]), replace=False)
        means = self._list_sums / np.maximum(self._list_nums, 1)[:, None]
        means = _normalize(means)
        rows = _normalize(self._mat[take])
        nearest = np.argmax(rows @ means.T, axis=1)
        self._drift_frac = float(np.mean(nearest != self._row_list[take]))

    # ------------------------------------------------------------------ search
    def _ensure_exact_locked(self):
        """Stage/refresh the rerank tier for the exact fallback paths."""
        if self._rerank_dirty or self._rerank is None:
            self._rerank = None
            self._rvalid = None
            self._rerank_count = 0
            if self._n:
                self._append_rerank_locked(0, self._mat[: self._n])
                if self._dead:
                    self._tombstone_dead_rerank_locked()
            self._snapshot_ids = self._ids
            self._rerank_dirty = False

    def _tombstone_dead_rerank_locked(self) -> None:
        dead = sorted(self._dead)
        for s in range(0, len(dead), _APPEND_BUCKETS[-1]):
            chunk = dead[s : s + _APPEND_BUCKETS[-1]]
            bkt = _bucket(len(chunk), _APPEND_BUCKETS)
            pos = np.full((bkt,), self._rvalid.shape[0], np.int32)
            pos[: len(chunk)] = chunk
            self._rvalid = self._put(
                _mask_positions(self._rvalid, jnp.asarray(pos)), sharded=False
            )

    def _snapshot(self, allowed_ids: Optional[set]):
        """Take a consistent view of everything a search needs, under the lock.

        jax arrays are immutable, so computing on the snapshot outside the
        lock is safe even while mutators swap in successors."""
        with self._lock:
            if not self._trained or allowed_ids is not None:
                self._ensure_exact_locked()
            allowed_mask = None
            if allowed_ids is not None and self._rvalid is not None:
                allowed_mask = np.zeros((self._rvalid.shape[0],), bool)
                for i in allowed_ids:
                    pos = self._id_pos.get(int(i))
                    if pos is not None and pos < allowed_mask.shape[0]:
                        allowed_mask[pos] = True
            return (
                self._trained,
                self._centroids,
                self._codebooks,
                self._codes,
                self._lvalid,
                self._rowpos,
                self._rerank,
                self._rvalid,
                self._snapshot_ids,
                len(self),
                allowed_mask,
            )

    def search(
        self,
        query: np.ndarray,
        k: int = 10,
        allowed_ids: Optional[set] = None,
        nprobe: Optional[int] = None,
    ) -> list[tuple[int, float]]:
        return self.search_batch(
            np.asarray(query, np.float32)[None, :], k, allowed_ids=allowed_ids, nprobe=nprobe
        )[0]

    def search_batch(
        self,
        queries: np.ndarray,
        k: int = 10,
        allowed_ids: Optional[set] = None,
        nprobe: Optional[int] = None,
    ) -> list[list[tuple[int, float]]]:
        """Batched approximate top-k: ADC shortlist -> exact rerank.

        Allow-listed and untrained searches run the EXACT kernel over the
        rerank tier — identical results to ``VectorIndex`` (an allowlist is a
        small candidate set; IVF pruning there costs recall and saves nothing).
        """
        (trained, centroids, codebooks, codes, lvalid, rowpos,
         rerank, rvalid, ids, n_live, allowed_mask) = self._snapshot(allowed_ids)
        n_q = len(queries)
        if not ids or n_live == 0 or rerank is None:
            return [[] for _ in range(n_q)]
        self.searches += n_q
        q = _normalize(np.asarray(queries, np.float32).reshape(-1, self.dim))
        q_pad = _bucket(q.shape[0], _QUERY_BUCKETS)
        if q_pad != q.shape[0]:
            q = np.concatenate([q, np.zeros((q_pad - q.shape[0], self.dim), np.float32)])
        qd = jnp.asarray(q)
        use_exact = (not trained) or allowed_mask is not None
        if use_exact:
            valid = rvalid
            if allowed_mask is not None:
                if not allowed_mask.any():
                    return [[] for _ in range(n_q)]
                valid = jnp.asarray(allowed_mask)
                n_live = int(allowed_mask.sum())
            k_eff = min(k, n_live)
            kb = min(_bucket(k_eff, _K_BUCKETS), rerank.shape[0])
            scores, idx = jax.device_get(_topk_scores(rerank, qd, valid, kb))
        else:
            k_eff = min(k, n_live)
            kb = min(_bucket(k_eff, _K_BUCKETS), rerank.shape[0])
            p_eff = self._nprobe_eff(nprobe)
            list_cap = codes.shape[1]
            sl = min(max(self.rerank_depth, kb), p_eff * list_cap)
            if self.mesh is not None:
                sl_scores, sl_pos = _sharded_adc_shortlist(
                    self.mesh, centroids, codebooks, codes, lvalid, rowpos, qd, p_eff, sl
                )
            else:
                sl_scores, sl_pos = _adc_shortlist(
                    centroids, codebooks, codes, lvalid, rowpos, qd, p_eff, sl
                )
            kb = min(kb, sl)
            scores, idx = jax.device_get(
                _rerank(rerank, rvalid, qd, sl_scores, sl_pos, kb)
            )
        out_rows = []
        for qi in range(n_q):
            row = []
            seen: set = set()
            for j in range(min(k_eff, scores.shape[1])):
                p = int(idx[qi, j])
                if p < len(ids) and np.isfinite(scores[qi, j]) and p not in seen:
                    seen.add(p)
                    row.append((ids[p], float(scores[qi, j])))
            out_rows.append(row)
        return out_rows

    def warmup(self, ks: Sequence[int] = (16,), q_rows: Sequence[int] = (8, 32)):
        """Pre-execute the scan + rerank kernels for the common buckets and
        BLOCK until the code blocks and rerank tier are resident — same
        rationale as ``VectorIndex.warmup`` (async dispatch would hide the
        transfer + compile inside the first live query)."""
        if not len(self):
            return self
        q = np.zeros((1, self.dim), np.float32)
        q[0, 0] = 1.0
        for qr in q_rows:
            qb = _bucket(qr, _QUERY_BUCKETS)
            for k in ks:
                # search_batch fetches synchronously — that IS the barrier
                self.search_batch(np.repeat(q, qb, axis=0), k=k)
        return self

    # ------------------------------------------------------------------ stats
    def probe_recall(
        self,
        n_queries: int = 64,
        k: int = 10,
        nprobe: Optional[int] = None,
        seed: int = 0,
        noise: float = 0.05,
    ) -> dict:
        """Recall@k of the ANN path against this index's own exact tier.

        Queries are seeded perturbations of stored rows — near-duplicate
        lookups, the RAG-retrieval shape.  Result is cached for stats()/obs.
        """
        with self._lock:
            n = self._n
            live = [p for p in range(n) if p not in self._dead]
            trained = self._trained
            if trained and live:
                rng = np.random.default_rng(seed)
                take = rng.choice(
                    np.asarray(live), size=min(n_queries, len(live)), replace=False
                )
                base = self._mat[take].copy()  # under the lock: _mat can be swapped
        if not trained or not live:
            rec = {"recall_at_k": 1.0, "k": k, "nprobe": 0, "queries": 0, "exact": True}
            self.last_recall = rec
            return rec
        qs = base + noise * rng.standard_normal((take.shape[0], self.dim)).astype(np.float32)
        exact = self._exact_batch(qs, k)
        approx = self.search_batch(qs, k=k, nprobe=nprobe)
        hits = total = 0
        for e_row, a_row in zip(exact, approx):
            truth = {i for i, _ in e_row}
            got = {i for i, _ in a_row}
            hits += len(truth & got)
            total += len(truth)
        rec = {
            "recall_at_k": (hits / total) if total else 1.0,
            "k": k,
            "nprobe": self._nprobe_eff(nprobe),
            "queries": int(take.shape[0]),
            "exact": False,
        }
        self.last_recall = rec
        return rec

    def _exact_batch(self, queries: np.ndarray, k: int) -> list[list[tuple[int, float]]]:
        """Exact top-k over the rerank tier (ground truth for recall probes)."""
        (_, _, _, _, _, _, rerank, rvalid, ids, n_live, _) = self._snapshot(None)
        if rerank is None or not ids:
            return [[] for _ in range(len(queries))]
        q = _normalize(np.asarray(queries, np.float32).reshape(-1, self.dim))
        q_pad = _bucket(q.shape[0], _QUERY_BUCKETS)
        if q_pad != q.shape[0]:
            q = np.concatenate([q, np.zeros((q_pad - q.shape[0], self.dim), np.float32)])
        k_eff = min(k, n_live)
        kb = min(_bucket(k_eff, _K_BUCKETS), rerank.shape[0])
        scores, idx = jax.device_get(_topk_scores(rerank, jnp.asarray(q), rvalid, kb))
        out = []
        for qi in range(len(queries)):
            row = []
            for j in range(k_eff):
                p = int(idx[qi, j])
                if p < len(ids) and np.isfinite(scores[qi, j]):
                    row.append((ids[p], float(scores[qi, j])))
            out.append(row)
        return out

    def stats(self) -> dict:
        """Operator/observability snapshot — everything /metrics and /healthz
        surface, computed without touching the device."""
        with self._lock:
            n_live = len(self)
            codes_bytes = 0 if self._codes is None else int(np.prod(self._codes.shape))
            list_cap = 0 if self._codes is None else int(self._codes.shape[1])
            list_fill_max = int(self._list_counts.max()) if self._list_counts.size else 0
            if self._trained and self._drift_stale and self._n < 50_000:
                self._refresh_drift_locked()
            drift = self._drift_frac
            return {
                "kind": "ivfpq",
                "trained": self._trained,
                "exact_fallback": not self._trained,
                "rows": n_live,
                "tombstones": len(self._dead),
                "nlist": self.nlist,
                "nprobe": self._nprobe_eff() if self._trained else 0,
                "m": self.m,
                "sub_dim": self.sub_dim,
                "codes_bytes": codes_bytes,
                "codes_bytes_per_vector": (codes_bytes / n_live) if n_live else 0.0,
                "rerank_depth": self.rerank_depth,
                "pending_appends": self.appended_since_train,
                "drift_frac": drift,
                "retrain_advised": bool(self._trained and drift > self.drift_threshold),
                "last_recall": self.last_recall,
                "searches": self.searches,
                "compactions": self.compactions,
                "retrains": self.retrains,
                "list_cap": list_cap,
                "list_fill_max": list_fill_max,
            }

    # -------------------------------------------------------------- durability
    def snapshot_state(self) -> dict:
        """Host-side state for an atomic snapshot (storage/durable.py).

        Live rows only, in position order — a snapshot is semantically a
        compaction point: tombstoned rows are simply absent, so pre-snapshot
        tombstones can never resurrect on WAL-tail replay.  ``row_list``
        stores each live row's ASSIGNED IVF list verbatim; restore re-encodes
        against that stored assignment rather than re-running spill balancing,
        because the pre-crash spill decisions depended on occupancy counters
        that included since-tombstoned slots — recomputing would move rows
        between lists and shift their ADC scores off the pre-crash bits.
        """
        with self._lock:
            n0 = self._n
            live_mask = np.ones((n0,), bool)
            for p in self._dead:
                if p < n0:
                    live_mask[p] = False
            state = {
                "ids": np.asarray(
                    [i for p, i in enumerate(self._ids[:n0]) if live_mask[p]], np.int64
                ),
                "vectors": np.ascontiguousarray(
                    self._mat[:n0][live_mask], dtype=np.float32
                ),
                "trained": bool(self._trained),
                "nlist": int(self.nlist),
                "m": int(self.m),
                "dim": int(self.dim),
                "seed": int(self.seed),
            }
            if self._trained and self._centroids is not None:
                state["centroids"] = np.asarray(
                    jax.device_get(self._centroids), np.float32
                )
                state["codebooks"] = np.asarray(
                    jax.device_get(self._codebooks), np.float32
                )
                state["row_list"] = np.ascontiguousarray(
                    self._row_list[:n0][live_mask], np.int32
                )
            return state

    def restore_state(self, state) -> None:
        """Rebuild the whole index from a ``snapshot_state`` dict.

        The stored per-row list assignment is adopted verbatim (no re-spill;
        see ``snapshot_state``), the rerank tier is restaged at the restored
        positions, and the drift gauge + advisory-retrain state restart from
        the restored assignment — a just-restored index must not immediately
        advise the retrain it just persisted.
        """
        ids = [int(i) for i in np.asarray(state["ids"]).reshape(-1).tolist()]
        vectors = np.asarray(state["vectors"], np.float32).reshape(-1, self.dim)
        if len(ids) != vectors.shape[0]:
            raise ValueError("snapshot ids/vectors length mismatch")
        n = len(ids)
        with self._lock:
            self._swap_empty_locked()
            if n == 0:
                return
            cap = _next_cap(1024, n)
            mat = self._mat_alloc((cap, self.dim))
            mat[:n] = vectors
            self._mat = mat
            self._n = n
            self._ids = ids
            self._id_pos = {i: p for p, i in enumerate(ids)}
            rl = np.full((cap,), -1, np.int32)
            rs = np.full((cap,), -1, np.int32)
            if not bool(state.get("trained")):
                self._row_list, self._row_slot = rl, rs
                self._rerank_dirty = True
                return
            nlist_eff = int(state["nlist"])
            centroids = self._put(
                jnp.asarray(np.asarray(state["centroids"], np.float32)), sharded=False
            )
            codebooks = self._put(
                jnp.asarray(np.asarray(state["codebooks"], np.float32)), sharded=False
            )
            all_lists = np.asarray(state["row_list"], np.int32).reshape(-1)
            (codes_d, lvalid_d, rowpos_d, counts, row_slot, sums) = self._encode_pack(
                vectors, all_lists, centroids, codebooks, nlist_eff
            )
            self.nlist = nlist_eff
            self._centroids, self._codebooks = centroids, codebooks
            self._codes, self._lvalid, self._rowpos = codes_d, lvalid_d, rowpos_d
            self._list_counts = counts
            rl[:n] = all_lists
            rs[:n] = row_slot
            self._row_list, self._row_slot = rl, rs
            self._list_sums = sums
            self._list_nums = counts.copy()
            self._drift_frac = 0.0
            self._drift_stale = 0
            self._trained = True
            self.appended_since_train = 0
            self._rerank = self._rvalid = None
            self._rerank_count = 0
            for s in range(0, n, _ENCODE_BATCH):
                e = min(n, s + _ENCODE_BATCH)
                self._append_rerank_locked(s, vectors[s:e])
            self._snapshot_ids = self._ids
            self._rerank_dirty = False

    def install_trained(self, centroids, codebooks, nlist: int) -> "ANNIndex":
        """Adopt quantizers learned elsewhere and restage against them — the
        WAL-replay twin of ``train()``.  Recovery must not re-LEARN (mini-batch
        k-means over the recovered corpus would not reproduce the pre-crash
        centroids bit-for-bit); it re-INSTALLS the exact arrays the crashed
        process logged in its retrain-install record, then the deterministic
        assign+spill+encode restage reproduces the pre-crash placement."""
        with self._lock:
            self.nlist = int(nlist)
            self._centroids = self._put(
                jnp.asarray(np.asarray(centroids, np.float32)), sharded=False
            )
            self._codebooks = self._put(
                jnp.asarray(np.asarray(codebooks, np.float32)), sharded=False
            )
            # _trained flips inside _restage's locked swap — flipping it here
            # would let a concurrent search snapshot trained=True with no codes
        self._restage(retrain=False)
        return self

    def live_ids(self) -> list[int]:
        """Ids currently serving (tombstoned ones excluded) — the registry's
        durable-recovery reconcile diffs this against the DB."""
        with self._lock:
            return list(self._id_pos.keys())

    def trained_arrays(self):
        """Host copies of the learned quantizers ``(centroids, codebooks,
        nlist)`` for a WAL retrain-install record; None while untrained."""
        with self._lock:
            if not self._trained or self._centroids is None:
                return None
            return (
                np.asarray(jax.device_get(self._centroids), np.float32),
                np.asarray(jax.device_get(self._codebooks), np.float32),
                int(self.nlist),
            )

    # ----------------------------------------------------------------- loading
    @classmethod
    def from_model(
        cls,
        model_cls,
        field: str = "embedding",
        mesh=None,
        nlist: int = 0,
        m: int = 0,
        nprobe: int = 0,
        rerank_depth: int = _DEF_RERANK,
        **filter_kw,
    ) -> "ANNIndex":
        """Build + train from every non-null vector of an ORM model."""
        dim = model_cls._fields[field].dim
        index = cls(
            dim, mesh=mesh, nlist=nlist, m=m, nprobe=nprobe, rerank_depth=rerank_depth
        )
        qs = model_cls.objects.filter(**filter_kw).exclude(**{f"{field}__isnull": True})
        ids, rows = [], []
        for obj in qs:
            vec = getattr(obj, field)
            if vec is not None:
                ids.append(obj.id)
                rows.append(vec)
        if ids:
            index.add(ids, np.stack(rows))
            index.train()
        return index
