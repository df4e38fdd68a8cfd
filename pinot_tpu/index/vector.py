"""Vector similarity index: dense matrix + IVF coarse quantizer, both
searched fully on device.

Reference parity: pinot-segment-local/.../segment/index/vector/
VectorIndexType.java (Lucene HNSW graph) consumed by
operator/filter/VectorSimilarityFilterOperator (VECTOR_SIMILARITY(col,
query, topK)). TPU-native difference: approximate graph traversal is a
pointer-chasing workload the TPU hates; brute-force similarity IS a dense
matmul — exactly what the MXU is built for — so the flat index stores the
raw float32 matrix and the search runs fully on device (one jit'd matmul
+ lax.top_k, only the k winners cross the host link). l2 ranks by the
expanded form 2*m.q - |m|^2 (row norms resident) so no (n_docs, dim)
difference materializes.

Round 19 grows the IVF layer (*Ragged Paged Attention* is the kernel
blueprint — page-resident data, ragged per-query lengths, one fused
device pass): a seeded k-means coarse quantizer at build time writes
centroids plus a CSR-style page layout beside the flat matrix — each
list's doc ids land in fixed-size PAGES (padded with the ``n_docs``
sentinel), lists own contiguous page runs indexed by a (n_lists+1)
``pageptr``. A query scores the centroids on device, picks ``nprobe``
lists with ``lax.top_k``, expands their RAGGED page runs into a
pow2-padded page-index vector (cumsum + searchsorted, all on device),
gathers the page-resident doc vectors and top-ks the masked scores —
exact brute force stays as ``nprobe >= n_lists`` and as the recall
oracle. Concurrent queries of one shape stack on a leading batch axis
and execute as ONE device launch through ``lax.map`` — the per-query
computation graph is the scan body, IDENTICAL at every batch size, so
batched results are exactly equal to solo by construction
(engine/vector_exec.py owns the admission window).

Device residency is accounted: every upload registers in the
``vector`` pool of utils/devmem (``/debug/memory``), counts toward the
shared ``PINOT_HBM_BUDGET_BYTES`` tier budget (engine/tier sums every
pool), and a tier demotion of the owning segment drops the arrays
(``evict_device``). The build path is lock-disciplined: the round-13
seed's unlocked check-then-act (two broker threads could double-upload
the matrix — analysis/concur CC205) is now a ``_build_lock`` held
across the whole build+upload with a re-check inside, publish under
``_res_lock``.

No benchmark cell measures the flat or the IVF path on the chip yet
(PERF.md section 7, ``vector_10m_1chip``).
"""
from __future__ import annotations

import functools
import math
import os
import threading
import weakref
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..utils.devmem import global_device_memory
from ..utils.metrics import global_metrics

SUFFIX = ".vec.bin"
IVF_CENT_SUFFIX = ".vec.cent.bin"
IVF_PAGES_SUFFIX = ".vec.pages.bin"
IVF_PAGEPTR_SUFFIX = ".vec.pageptr.bin"

POOL = "vector"                 # utils/devmem pool name
PAGE_SIZE = 64                  # doc ids per IVF page (RPA page analog)
KMEANS_ITERS = 8
KMEANS_SAMPLE = 1 << 16         # centroid fit sample cap (assignment is full)
_DEVICE_MIN_ROWS = 4096  # below this, numpy beats the dispatch overhead

# live readers (reconcile_devmem sums their actual device bytes against
# the tracked pool); WeakSet so an unloaded segment's reader never pins
_LIVE_LOCK = threading.Lock()
_LIVE_READERS: "weakref.WeakSet[VectorIndexReader]" = weakref.WeakSet()
# process-unique reader identity for memo/batch keys: NEVER id() — a
# GC'd reader's address can be reused and would alias cache entries
_READER_SEQ = __import__("itertools").count(1)

# devmem entries whose reader was GC'd while resident: the weakref
# finalizer appends here LOCK-FREE (GC can fire on a thread already
# holding the devmem lock — the engine/tier dead-list lesson) and the
# next ensure_device/live_readers drains it on a normal thread
_DEAD_ENTRIES: list = []


def _reap_dead_entries() -> None:
    while _DEAD_ENTRIES:
        pool_key, names = _DEAD_ENTRIES.pop()
        for name in names:
            global_device_memory.remove(POOL, (pool_key, name),
                                        evicted=False)


def live_readers():
    _reap_dead_entries()
    with _LIVE_LOCK:
        return list(_LIVE_READERS)


def default_n_lists(n_docs: int) -> int:
    """sqrt(n) clamped — the standard IVF list-count heuristic."""
    return max(8, min(1024, int(round(math.sqrt(max(n_docs, 1))))))


def default_nprobe(n_lists: int) -> int:
    """Probe ~1/32 of the lists by default — the recall/QPS knee the
    round 19's CPU nprobe sweep found (recall ~0.98 at ~5x the exact
    scan's QPS on the CPU smoke with balanced lists; raise per query
    via the 4th VECTOR_SIMILARITY argument when recall matters more)."""
    return max(1, (n_lists + 31) // 32)


# ---------------------------------------------------------------------------
# build: seeded k-means + CSR page layout
# ---------------------------------------------------------------------------

def _fit_centroids(x: np.ndarray, n_lists: int, seed: int,
                   iters: int = KMEANS_ITERS) -> np.ndarray:
    """Seeded Lloyd k-means on a bounded sample; deterministic in
    (data, n_lists, seed). Empty clusters re-seed to random rows."""
    rng = np.random.default_rng(seed)
    n = len(x)
    fit = x if n <= KMEANS_SAMPLE else \
        x[rng.choice(n, size=KMEANS_SAMPLE, replace=False)]
    c = fit[rng.choice(len(fit), size=n_lists, replace=False)].astype(
        np.float64)
    for _ in range(iters):
        a = _assign(fit, c)
        sums = np.zeros_like(c)
        np.add.at(sums, a, fit.astype(np.float64))
        cnt = np.bincount(a, minlength=n_lists)
        nz = cnt > 0
        c[nz] = sums[nz] / cnt[nz, None]
        if not nz.all():
            c[~nz] = fit[rng.choice(len(fit), size=int((~nz).sum()))]
    return c.astype(np.float32)


def _assign(x: np.ndarray, c: np.ndarray, chunk: int = 1 << 16
            ) -> np.ndarray:
    """argmin-L2 list assignment, chunked so the (rows, n_lists)
    distance block stays bounded at any matrix size."""
    out = np.empty(len(x), dtype=np.int32)
    c64 = c.astype(np.float64)
    csq = (c64 * c64).sum(axis=1)
    for i in range(0, len(x), chunk):
        xb = x[i: i + chunk].astype(np.float64)
        d = csq[None, :] - 2.0 * (xb @ c64.T)
        out[i: i + chunk] = np.argmin(d, axis=1)
    return out


# balanced-assignment slack: every list is capped at slack * (n / L)
# docs, overflow spills to the doc's next-nearest centroid — the probe
# bound becomes TIGHT (nprobe * cap pages, no worst-list blowup), which
# is what makes the ragged scan actually cheaper than the flat matmul
# (1.1 measured better than 1.25 on the CPU smoke: ~13% less padded
# probe work for a ~0.5pt recall cost at the default nprobe)
BALANCE_SLACK = 1.1
_BALANCE_CHOICES = 8


def _balanced_assign(x: np.ndarray, c: np.ndarray,
                     cap: int, chunk: int = 1 << 16) -> np.ndarray:
    """Capacity-bounded list assignment: closest-first seat claiming
    over each doc's ranked centroid choices (deterministic in the
    inputs). Guarantees every list holds <= cap docs, every doc lands
    somewhere (cap * n_lists >= n by construction)."""
    n, n_lists = len(x), len(c)
    r_max = min(n_lists, _BALANCE_CHOICES)
    choice = np.empty((n, r_max), dtype=np.int32)
    choice_d = np.empty((n, r_max), dtype=np.float64)
    c64 = c.astype(np.float64)
    csq = (c64 * c64).sum(axis=1)
    for i in range(0, n, chunk):
        xb = x[i: i + chunk].astype(np.float64)
        d = csq[None, :] - 2.0 * (xb @ c64.T)
        top = np.argpartition(d, r_max - 1, axis=1)[:, :r_max]
        td = np.take_along_axis(d, top, axis=1)
        order = np.argsort(td, axis=1, kind="stable")
        choice[i: i + chunk] = np.take_along_axis(top, order, axis=1)
        choice_d[i: i + chunk] = np.take_along_axis(td, order, axis=1)
    assign = np.full(n, -1, dtype=np.int32)
    counts = np.zeros(n_lists, dtype=np.int64)
    for r in range(r_max):
        idx = np.nonzero(assign < 0)[0]
        if not len(idx):
            break
        lists = choice[idx, r]
        d = choice_d[idx, r]
        # group by target list, closest docs claim the free seats
        order = np.lexsort((d, lists))
        sl = lists[order]
        starts = np.searchsorted(sl, np.arange(n_lists))
        rank = np.arange(len(order)) - starts[sl]
        take = rank < (cap - counts)[sl]
        won = order[take]
        assign[idx[won]] = sl[take]
        counts += np.bincount(sl[take], minlength=n_lists)
    left = np.nonzero(assign < 0)[0]
    if len(left):
        # pathological spill (every ranked choice full): deterministic
        # round-robin over the remaining free seats
        free = np.repeat(np.arange(n_lists),
                         np.maximum(cap - counts, 0))
        assign[left] = free[: len(left)].astype(np.int32)
    return assign


def _page_layout(assign: np.ndarray, n_docs: int, n_lists: int,
                 page: int) -> Tuple[np.ndarray, np.ndarray]:
    """-> (pages (n_pages, page) int32 doc ids padded with the n_docs
    sentinel, pageptr (n_lists+1) int32): list l owns pages
    [pageptr[l], pageptr[l+1]) — contiguous, CSR-style."""
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=n_lists)
    pages_per = (counts + page - 1) // page
    pageptr = np.zeros(n_lists + 1, dtype=np.int32)
    np.cumsum(pages_per, out=pageptr[1:])
    pages = np.full((int(pageptr[-1]), page), n_docs, dtype=np.int32)
    flat = pages.reshape(-1)
    off = np.cumsum(counts) - counts
    for li in range(n_lists):
        c = int(counts[li])
        if c:
            p0 = int(pageptr[li]) * page
            flat[p0: p0 + c] = order[off[li]: off[li] + c]
    return pages, pageptr


def build(col: str, seg_dir: str, *, values: np.ndarray,
          metric: str = "cosine", nLists: Optional[int] = None,
          seed: int = 7, pageSize: int = PAGE_SIZE,
          **_: Any) -> Dict[str, Any]:
    rows = [np.asarray(v, dtype=np.float32) for v in values]
    if not rows:
        raise ValueError(f"vector index on empty column {col}")
    dim = len(rows[0])
    for r in rows:
        if r.shape != (dim,):
            raise ValueError(f"ragged vector column {col}: "
                             f"{r.shape} != ({dim},)")
    mat = np.stack(rows)
    mat.tofile(os.path.join(seg_dir, col + SUFFIX))
    meta: Dict[str, Any] = {"dim": int(dim), "metric": str(metric)}
    if nLists:
        # clamp an oversized config instead of crashing the build: the
        # k-means fit samples at most KMEANS_SAMPLE rows, so that also
        # bounds how many distinct centroids can be seeded
        n_lists = max(1, min(int(nLists), len(mat), KMEANS_SAMPLE))
        space = _ivf_space(mat, metric)
        cents = _fit_centroids(space, n_lists, int(seed))
        cap = _list_cap(len(mat), n_lists)
        pages, pageptr = _page_layout(
            _balanced_assign(space, cents, cap), len(mat), n_lists,
            int(pageSize))
        cents.tofile(os.path.join(seg_dir, col + IVF_CENT_SUFFIX))
        pages.tofile(os.path.join(seg_dir, col + IVF_PAGES_SUFFIX))
        pageptr.tofile(os.path.join(seg_dir, col + IVF_PAGEPTR_SUFFIX))
        meta["ivf"] = {"nLists": int(n_lists), "pageSize": int(pageSize),
                       "nPages": int(pages.shape[0]), "seed": int(seed),
                       "nprobe": default_nprobe(n_lists)}
    return meta


def _list_cap(n_docs: int, n_lists: int) -> int:
    """Per-list doc capacity (balanced assignment): slack * mean,
    rounded up so cap * n_lists always covers n."""
    return max(int(math.ceil(n_docs / n_lists * BALANCE_SLACK)), 1)


def _ivf_space(mat: np.ndarray, metric: str) -> np.ndarray:
    """The space k-means partitions: normalized rows for cosine
    (spherical k-means — centroid dot ranks like row dot), raw for l2."""
    if metric == "cosine":
        norms = np.linalg.norm(mat, axis=1, keepdims=True)
        return (mat / np.maximum(norms, 1e-30)).astype(np.float32)
    return mat.astype(np.float32)


# ---------------------------------------------------------------------------
# device kernels: one jit per static shape, lax.map over the batch axis
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _batched_flat_kernel(metric: str, k_pad: int, n_docs: int,
                         dim: int, b_pad: int):
    """Exact scan over the (n+1, dim)-padded matrix (last row is the
    gather sentinel, forced to -inf). ``lax.map`` makes the per-query
    body identical at every batch size — batched == solo by
    construction. ``dim``/``b_pad`` are cache-key-only (the jit
    re-specializes per input shape anyway): every XLA compile lands on
    a cold cache slot, so ``vector_kernel_compiles`` counts real
    compiles and a zero-post-warmup-retrace check can pin
    it."""
    import jax
    import jax.numpy as jnp

    global_metrics.count("vector_kernel_compiles")

    def body(q, m_pad, row_sq_pad):
        if metric == "cosine":
            sims = m_pad @ q
        else:
            sims = 2.0 * (m_pad @ q) - row_sq_pad - jnp.sum(q * q)
        sims = sims.at[n_docs].set(-jnp.inf)
        return jax.lax.top_k(sims, k_pad)

    def run(qs, m_pad, row_sq_pad):
        return jax.lax.map(lambda q: body(q, m_pad, row_sq_pad), qs)

    from ..utils.compileplane import staged
    return staged(jax.jit(run), "vector",
                  ("vec_flat", metric, k_pad, n_docs, dim, b_pad))


@functools.lru_cache(maxsize=256)
def _batched_ivf_kernel(metric: str, k_pad: int, nprobe: int,
                        max_pages: int, n_docs: int, n_pages: int,
                        dim: int, b_pad: int):
    """IVF probe: centroid top-nprobe, ragged page-run expansion
    (cumsum + searchsorted over the per-list page counts), page gather,
    masked top-k — ONE fused pass, no host round trip. Same
    ``lax.map`` batching contract as the flat kernel."""
    import jax
    import jax.numpy as jnp

    global_metrics.count("vector_kernel_compiles")

    def body(q, paged, paged_sq, cents, cent_sq, pages_pad, pageptr):
        if metric == "cosine":
            cscore = cents @ q
        else:
            cscore = 2.0 * (cents @ q) - cent_sq
        _, lists = jax.lax.top_k(cscore, nprobe)
        starts = pageptr[lists]
        counts = pageptr[lists + 1] - starts
        cum = jnp.cumsum(counts)
        total = cum[-1]
        j = jnp.arange(max_pages, dtype=jnp.int32)
        li = jnp.minimum(
            jnp.searchsorted(cum, j, side="right"), nprobe - 1)
        pos = j - (cum[li] - counts[li])
        # slots past the ragged total point at the all-sentinel pad page
        page_idx = jnp.where(j < total, starts[li] + pos, n_pages)
        # page-RESIDENT gather (the RPA trick): each index pulls one
        # contiguous (page, dim) block of the pre-paged matrix — never
        # a per-row scatter over the flat layout
        docs = pages_pad[page_idx]              # (max_pages, page)
        vecs = paged[page_idx]                  # (max_pages, page, dim)
        if metric == "cosine":
            sims = vecs @ q
        else:
            sims = 2.0 * (vecs @ q) - paged_sq[page_idx] - jnp.sum(q * q)
        sims = jnp.where(docs == n_docs, -jnp.inf, sims)
        scores, idx = jax.lax.top_k(sims.reshape(-1), k_pad)
        return scores, docs.reshape(-1)[idx]

    def run(qs, paged, paged_sq, cents, cent_sq, pages_pad, pageptr):
        return jax.lax.map(
            lambda q: body(q, paged, paged_sq, cents, cent_sq,
                           pages_pad, pageptr), qs)

    from ..utils.compileplane import staged
    return staged(jax.jit(run), "vector",
                  ("vec_ivf", metric, k_pad, nprobe, max_pages, n_docs,
                   n_pages, dim, b_pad))


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------

class VectorIndexReader:
    def __init__(self, seg_dir: str, col: str, meta: Dict[str, Any]):
        from ..segment import segdir
        raw = segdir.read_array(seg_dir, col + SUFFIX, np.float32)
        ivf = None
        im = meta.get("ivf")
        if im:
            cents = np.asarray(segdir.read_array(
                seg_dir, col + IVF_CENT_SUFFIX, np.float32,
                mmap=False)).reshape(int(im["nLists"]), -1)
            pages = np.asarray(segdir.read_array(
                seg_dir, col + IVF_PAGES_SUFFIX, np.int32,
                mmap=False)).reshape(int(im["nPages"]),
                                     int(im["pageSize"]))
            pageptr = np.asarray(segdir.read_array(
                seg_dir, col + IVF_PAGEPTR_SUFFIX, np.int32, mmap=False))
            ivf = {"centroids": cents, "pages": pages,
                   "pageptr": pageptr,
                   "nprobe": int(im.get("nprobe")
                                 or default_nprobe(int(im["nLists"])))}
        self._init(raw.reshape(-1, int(meta["dim"])),
                   meta.get("metric", "cosine"), ivf)

    def _init(self, matrix: np.ndarray, metric: str,
              ivf: Optional[Dict[str, Any]] = None) -> None:
        self.dim = matrix.shape[1]
        self.metric = metric
        self.matrix = matrix
        self.ivf = ivf
        # process-unique identity for memo/batch keys (id() could be
        # reused after GC and alias another reader's cache entries)
        self.token: int = next(_READER_SEQ)
        # devmem identity: (owner uid, col) once attached to a segment,
        # the token fallback for in-memory readers (benches)
        self._pool_key: Any = f"reader_{self.token}"
        self._owner: Optional[Any] = None       # weakref to the segment
        self._finalizer: Optional[Any] = None   # devmem-entry reaper
        # device residents, published under _res_lock; _build_lock is
        # held across the whole host-prep + upload so two threads can
        # never double-upload the matrix (the CC205 check-then-act fix)
        self._res_lock = threading.Lock()
        self._build_lock = threading.Lock()
        self._dev: Dict[str, Any] = {}
        self._max_pages: Dict[int, int] = {}
        with _LIVE_LOCK:
            _LIVE_READERS.add(self)

    @classmethod
    def from_matrix(cls, matrix: np.ndarray,
                    metric: str = "cosine") -> "VectorIndexReader":
        """Reader over an in-memory matrix (benches, mutable segments)."""
        r = cls.__new__(cls)
        r._init(np.asarray(matrix, dtype=np.float32), metric)
        return r

    def build_ivf(self, n_lists: Optional[int] = None, seed: int = 7,
                  page: int = PAGE_SIZE,
                  nprobe: Optional[int] = None) -> "VectorIndexReader":
        """In-memory IVF layer (benches / tests; the file path builds it
        at segment-build time)."""
        n_lists = min(n_lists or default_n_lists(len(self.matrix)),
                      len(self.matrix), KMEANS_SAMPLE)
        space = _ivf_space(self.matrix, self.metric)
        cents = _fit_centroids(space, n_lists, seed)
        cap = _list_cap(len(self.matrix), n_lists)
        pages, pageptr = _page_layout(
            _balanced_assign(space, cents, cap), len(self.matrix),
            n_lists, page)
        self.evict_device()
        self.ivf = {"centroids": cents, "pages": pages,
                    "pageptr": pageptr,
                    "nprobe": nprobe or default_nprobe(n_lists)}
        return self

    # -- ownership / tier --------------------------------------------------
    def attach_owner(self, segment, col: str) -> None:
        """Bind to the owning segment: devmem keys become (uid, col) and
        the tier sees every upload as an admission of that segment."""
        self._pool_key = (segment.uid, col)
        self._owner = weakref.ref(segment)

    def owner(self):
        return self._owner() if self._owner is not None else None

    @property
    def n_lists(self) -> int:
        return len(self.ivf["centroids"]) if self.ivf else 0

    @property
    def nprobe_default(self) -> int:
        return int(self.ivf["nprobe"]) if self.ivf else 0

    # -- device residency --------------------------------------------------
    def _host_arrays(self) -> Dict[str, np.ndarray]:
        """The upload set: sentinel-padded matrix (+ squared norms for
        l2) and, with an IVF layer, the centroids plus the PAGE-MAJOR
        matrix copy (``paged[p, i] = matrix[pages[p, i]]``) — the probe
        kernel gathers whole contiguous (page, dim) blocks from it, the
        RPA page-residency trick that makes the ragged scan beat the
        flat matmul instead of paying a per-row scatter."""
        m = self.matrix
        if self.metric == "cosine":
            norms = np.linalg.norm(m, axis=1, keepdims=True)
            m = m / np.maximum(norms, 1e-30)
        m = np.ascontiguousarray(m, dtype=np.float32)
        m_pad = np.concatenate(
            [m, np.zeros((1, self.dim), dtype=np.float32)])
        out = {"matrix": m_pad}
        # the squared-norm companions are zeros for cosine (the kernel
        # never reads them — XLA DCE's the dead arg) so call sites pass
        # resident arrays unconditionally instead of slicing a dummy
        # off the matrix per search (an eager device gather per query)
        if self.metric != "cosine":
            row_sq = np.concatenate(
                [np.sum(m.astype(np.float64) * m, axis=1),
                 [0.0]]).astype(np.float32)
        else:
            row_sq = np.zeros(len(m) + 1, dtype=np.float32)
        out["row_sq"] = row_sq
        if self.ivf:
            cents = self.ivf["centroids"]
            out["centroids"] = cents
            if self.metric != "cosine":
                out["cent_sq"] = np.sum(
                    cents.astype(np.float64) * cents, axis=1).astype(
                    np.float32)
            else:
                out["cent_sq"] = np.zeros(len(cents), dtype=np.float32)
            pages_pad = np.concatenate(
                [self.ivf["pages"],
                 np.full((1, self.ivf["pages"].shape[1]),
                         len(self.matrix), dtype=np.int32)])
            out["pages"] = pages_pad
            out["pageptr"] = self.ivf["pageptr"].astype(np.int32)
            out["paged"] = m_pad[pages_pad]      # (n_pages+1, page, dim)
            out["paged_sq"] = row_sq[pages_pad]
        return out

    def ensure_device(self) -> Dict[str, Any]:
        """Upload-once device residency. Serialized by ``_build_lock``
        (held across prep + upload: the second thread re-checks inside
        and returns the first upload — never a double upload); inserts
        publish + account under ``_res_lock`` so a concurrent
        ``evict_device`` can't strand devmem bytes."""
        dev = self._dev
        if dev:
            return dev
        import jax
        _reap_dead_entries()
        with self._build_lock:
            if self._dev:
                return self._dev
            hosts = self._host_arrays()
            arrs = {k: jax.device_put(v) for k, v in hosts.items()}
            with self._res_lock:
                self._dev = arrs
                for k, v in arrs.items():
                    global_device_memory.add(
                        POOL, (self._pool_key, k), int(v.nbytes))
                # pair the accounting with the reader's lifetime: a
                # resident reader GC'd without evict_device must not
                # leave phantom pool bytes charging the tier budget
                # (callback is lock-free — see _DEAD_ENTRIES)
                self._finalizer = weakref.finalize(
                    self, _DEAD_ENTRIES.append,
                    (self._pool_key, tuple(arrs)))
        owner = self.owner()
        if owner is not None:
            from ..engine.tier import global_tier
            global_tier.admitted(owner)
        return self._dev

    def evict_device(self) -> None:
        """Drop the device residents (tier demotion of the owning
        segment / budget eviction); the next search re-uploads."""
        with self._res_lock:
            for k in self._dev:
                global_device_memory.remove(POOL, (self._pool_key, k))
            self._dev = {}
            if self._finalizer is not None:
                self._finalizer.detach()
                self._finalizer = None

    def device_bytes(self) -> int:
        with self._res_lock:
            return sum(int(v.nbytes) for v in self._dev.values())

    # -- search ------------------------------------------------------------
    def _query_vec(self, query: np.ndarray) -> np.ndarray:
        q = np.asarray(query, dtype=np.float32)
        if q.shape != (self.dim,):
            raise ValueError(f"query dim {q.shape} != ({self.dim},)")
        if self.metric == "cosine":
            q = q / max(float(np.linalg.norm(q)), 1e-30)
        return q

    def max_pages_for(self, nprobe: int) -> int:
        """Static per-(index, nprobe) bound on the ragged page-run
        total: the nprobe LARGEST lists' page counts (tight under the
        balanced build — every list is capped near the mean), rounded
        to a multiple of 8 pages so near sizes share a compile."""
        got = self._max_pages.get(nprobe)
        if got is None:
            ptr = self.ivf["pageptr"].astype(np.int64)
            counts = np.sort(ptr[1:] - ptr[:-1])[::-1]
            worst = int(counts[:nprobe].sum())
            got = min(-(-max(worst, 1) // 8) * 8, int(ptr[-1]))
            got = max(got, 1)
            self._max_pages[nprobe] = got
        return got

    def effective_nprobe(self, nprobe: Optional[int]) -> int:
        """Clamped probe count: None -> the index default; >= n_lists
        (or no IVF layer) -> exact flat scan (0 means flat)."""
        if not self.ivf:
            return 0
        np_ = int(nprobe) if nprobe else self.nprobe_default
        return 0 if np_ >= self.n_lists else max(np_, 1)

    def search_batch(self, queries: np.ndarray, k: int,
                     nprobe: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k for a [B, dim] stack of queries in ONE device launch ->
        (scores [B, k] float32, docs [B, k] int32, -1 where a probe
        found fewer than k). Batched results are exactly equal to solo
        (lax.map body — module docstring); B is pow2-padded, pad rows
        discarded."""
        qs = np.stack([self._query_vec(q) for q in queries])
        b = len(qs)
        n = len(self.matrix)
        k = min(max(int(k), 1), n)
        b_pad = _pow2(b)
        if b_pad > b:
            qs = np.concatenate(
                [qs, np.zeros((b_pad - b, self.dim), dtype=np.float32)])
        eff = self.effective_nprobe(nprobe)
        dev = self.ensure_device()
        if eff:
            k_pad = min(_pow2(k),
                        self.max_pages_for(eff)
                        * self.ivf["pages"].shape[1])
            fn = _batched_ivf_kernel(
                self.metric, k_pad, eff, self.max_pages_for(eff), n,
                int(self.ivf["pages"].shape[0]), self.dim, b_pad)
            scores, docs = fn(qs, dev["paged"], dev["paged_sq"],
                              dev["centroids"], dev["cent_sq"],
                              dev["pages"], dev["pageptr"])
        else:
            k_pad = min(_pow2(k), n)
            fn = _batched_flat_kernel(self.metric, k_pad, n, self.dim,
                                      b_pad)
            scores, docs = fn(qs, dev["matrix"], dev["row_sq"])
        scores = np.asarray(scores)[:b, :k]
        docs = np.asarray(docs)[:b, :k].astype(np.int32)
        docs = np.where(np.isneginf(scores), np.int32(-1), docs)
        if scores.shape[1] < k:
            # a tiny IVF layout can bound the probe below k: pad the
            # contract shape with explicit misses
            pad = k - scores.shape[1]
            scores = np.concatenate(
                [scores, np.full((b, pad), -np.inf, np.float32)], axis=1)
            docs = np.concatenate(
                [docs, np.full((b, pad), -1, np.int32)], axis=1)
        return scores, docs

    def host_scores(self, query: np.ndarray,
                    sel: Optional[np.ndarray] = None) -> np.ndarray:
        """Exact per-doc similarity scores, host-side (ORDER BY keys /
        oracles): cosine = normalized dot, l2 = negated squared
        distance. Deterministic regardless of batching/placement."""
        qn = self._query_vec(query)
        m = np.asarray(self.matrix if sel is None else self.matrix[sel],
                       dtype=np.float32)
        if self.metric == "cosine":
            norms = np.linalg.norm(m, axis=1, keepdims=True)
            return (m / np.maximum(norms, 1e-30)) @ qn
        d = m - qn
        return -np.sum(d * d, axis=1)

    def top_k_docs(self, query: np.ndarray, k: int) -> np.ndarray:
        """Solo top-k doc ids (legacy surface; engine/vector_exec routes
        searches through search_batch for the batching plane)."""
        n = len(self.matrix)
        k = min(max(int(k), 1), n)
        if n < _DEVICE_MIN_ROWS and not self.ivf:
            sims = self.host_scores(query)
            idx = np.argpartition(-sims, k - 1)[:k]
            return idx[np.argsort(-sims[idx])].astype(np.int32)
        _scores, docs = self.search_batch(
            np.asarray(query, dtype=np.float32)[None, :], k)
        return docs[0][docs[0] >= 0]

    def top_k_mask(self, query: np.ndarray, k: int,
                   n_docs: int) -> np.ndarray:
        mask = np.zeros(n_docs, dtype=bool)
        docs = self.top_k_docs(query, k)
        mask[docs[docs >= 0]] = True
        return mask
