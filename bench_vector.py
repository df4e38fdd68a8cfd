"""Vector similarity bench: flat 1M x 128d device matmul top-k, plus the
round-19 IVF acceptance mode (``--ivf``).

Default mode: VECTOR_SIMILARITY runs on device at >= 1M x 128d with a
capture-log entry. Prints ONE JSON line with the size-keyed metric
"vector_similarity_<rows>x<dim>d_qps"; vs_baseline is the speedup over the single-thread numpy brute-force scan
of the same data (the stand-in for Lucene HNSW, which trades recall for
speed — this path is exact, recall 1.0).

``--ivf`` (ISSUE 14 acceptance gate): clustered data through the IVF
page-resident index (index/vector.py) —

- recall@10 vs the exact numpy oracle across an nprobe sweep, gated
  >= 0.95 at the DEFAULT nprobe;
- solo IVF QPS gated >= 3x the exact full-matrix device scan of the
  same data (the CPU-smoke proxy of the TPU page-gather win);
- batched concurrent searches (one fused pow2-padded launch) gated
  EXACTLY equal to solo, with ZERO vector-kernel compiles observed in
  the measured phase (post-warmup retrace gate);
- an eviction churn (evict_device + re-search x3) after which the
  ``vector`` devmem pool must reconcile to the byte — zero unaccounted
  bytes, /debug/memory's invariant.

Appends a validated ``vector_bench`` ledger record (recall/QPS/latency
contract, utils/ledger.py) beside the bench_capture line.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

N_ROWS = int(os.environ.get("PINOT_BENCH_VEC_ROWS", 1 << 20))
DIM = int(os.environ.get("PINOT_BENCH_VEC_DIM", 128))
K = 10
QUERIES = 20

IVF_ROWS = int(os.environ.get("PINOT_BENCH_IVF_ROWS", 1 << 19))
IVF_DIM = int(os.environ.get("PINOT_BENCH_IVF_DIM", 64))
IVF_LISTS = int(os.environ.get("PINOT_BENCH_IVF_LISTS", 128))
IVF_QUERIES = 32
IVF_BATCH = 8
IVF_SEED = 11
NPROBE_SWEEP = (1, 2, 4, 8, 16)

RECALL_BAR = 0.95
QPS_RATIO_BAR = 3.0

# size-keyed so ledger comparisons never mix differently-sized captures
METRIC = f"vector_similarity_{N_ROWS}x{DIM}d_qps"
METRIC_IVF = f"vector_ivf_{IVF_ROWS}x{IVF_DIM}d_qps"


def gen_clustered(rows: int, dim: int, n_clusters: int, seed: int):
    """Mixture-of-gaussians embeddings (the workload IVF exists for —
    real embedding spaces cluster; pure isotropic noise has no coarse
    structure to quantize) plus queries near stored rows."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32)
    a = rng.integers(0, n_clusters, rows)
    mat = (centers[a]
           + 0.2 * rng.standard_normal((rows, dim))).astype(np.float32)
    qidx = rng.integers(0, rows, IVF_QUERIES)
    queries = (mat[qidx] + 0.02 * rng.standard_normal(
        (IVF_QUERIES, dim))).astype(np.float32)
    return mat, queries


def main_ivf() -> None:
    from bench_common import finish, ledger_append_raw, require_backend

    backend = require_backend(METRIC_IVF)

    from pinot_tpu.index.vector import VectorIndexReader
    from pinot_tpu.utils import ledger as uledger
    from pinot_tpu.utils.devmem import global_device_memory
    from pinot_tpu.utils.metrics import global_metrics

    errors = []

    def gate(name, ok, detail=""):
        if not ok:
            errors.append(f"{name}: {detail}")
            print(f"  GATE FAIL {name}: {detail}", file=sys.stderr)

    # 64 natural clusters quantized by IVF_LISTS k-means lists (a finer
    # partition than the data's own structure adapts to cluster
    # boundaries — the nprobe sweep documents the recall/QPS knee)
    mat, queries = gen_clustered(IVF_ROWS, IVF_DIM, 64, IVF_SEED)
    t0 = time.perf_counter()
    reader = VectorIndexReader.from_matrix(mat).build_ivf(
        n_lists=IVF_LISTS, seed=7)
    build_s = time.perf_counter() - t0
    nprobe_def = reader.nprobe_default
    print(f"  built IVF: {IVF_ROWS}x{IVF_DIM}d, {IVF_LISTS} lists, "
          f"default nprobe {nprobe_def}, {build_s:.1f}s",
          file=sys.stderr)

    # exact oracle (numpy): top-10 per query
    mn = mat / np.maximum(
        np.linalg.norm(mat, axis=1, keepdims=True), 1e-30)
    oracle = []
    for q in queries:
        sims = mn @ (q / np.linalg.norm(q))
        oracle.append(set(np.argsort(-sims)[:K].tolist()))

    # warm every (nprobe, batch-rung) shape the measured phases touch
    sweep_probes = sorted({*NPROBE_SWEEP, nprobe_def})
    for npb in sweep_probes:
        reader.search_batch(queries[:1], K, nprobe=npb)
    reader.search_batch(queries[:1], K, nprobe=IVF_LISTS)  # exact scan
    b = 1
    while b < IVF_BATCH:
        b <<= 1
        reader.search_batch(queries[:b], K)

    # nprobe sweep: recall@10 vs the oracle
    sweep = {}
    for npb in sweep_probes:
        tot = 0.0
        for i, q in enumerate(queries):
            _s, d = reader.search_batch(q[None, :], K, nprobe=npb)
            tot += len(oracle[i] & set(d[0].tolist())) / K
        sweep[npb] = round(tot / len(queries), 4)
    recall = sweep[nprobe_def]
    gate("recall", recall >= RECALL_BAR,
         f"recall@10 {recall} < {RECALL_BAR} at default nprobe "
         f"{nprobe_def} (sweep {sweep})")

    compiles0 = global_metrics.snapshot()["counters"].get(
        "vector_kernel_compiles", 0)

    # solo IVF QPS + latency percentiles
    lat = []
    reps = 3
    for _ in range(reps):
        for q in queries:
            t1 = time.perf_counter()
            reader.search_batch(q[None, :], K)
            lat.append((time.perf_counter() - t1) * 1e3)
    qps_ivf = len(lat) / (sum(lat) / 1e3)
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))

    # exact full-matrix device scan of the same data
    t1 = time.perf_counter()
    for _ in range(reps):
        for q in queries:
            reader.search_batch(q[None, :], K, nprobe=IVF_LISTS)
    qps_exact = reps * len(queries) / (time.perf_counter() - t1)
    ratio = qps_ivf / qps_exact
    gate("qps_ratio", ratio >= QPS_RATIO_BAR,
         f"IVF {qps_ivf:.1f} q/s vs exact {qps_exact:.1f} q/s = "
         f"{ratio:.2f}x < {QPS_RATIO_BAR}x")

    # batched == solo, exactly (the lax.map contract), measured fused QPS
    solo = [reader.search_batch(q[None, :], K) for q in queries]
    batched_equal = True
    t1 = time.perf_counter()
    for lo in range(0, len(queries), IVF_BATCH):
        s, d = reader.search_batch(queries[lo: lo + IVF_BATCH], K)
        for j in range(len(s)):
            ss, ds = solo[lo + j]
            if not (np.array_equal(s[j], ss[0])
                    and np.array_equal(d[j], ds[0])):
                batched_equal = False
    qps_batched = len(queries) / (time.perf_counter() - t1)
    gate("batched_equal", batched_equal,
         "fused batched top-k != solo top-k")

    retraces = global_metrics.snapshot()["counters"].get(
        "vector_kernel_compiles", 0) - compiles0
    gate("zero_retraces", retraces == 0,
         f"{retraces} vector-kernel compiles during the measured phase")

    # eviction churn: device residents dropped + re-promoted x3, then
    # the vector pool must reconcile to the byte (and drain to zero)
    for _ in range(3):
        reader.evict_device()
        reader.search_batch(queries[:1], K)
    tracked = global_device_memory.pool_bytes("vector")
    actual = reader.device_bytes()
    unaccounted = tracked - actual
    gate("pool_reconciles", unaccounted == 0,
         f"vector pool tracked {tracked} != actual {actual}")
    reader.evict_device()
    drained = global_device_memory.pool_bytes("vector")
    gate("pool_drains", drained == 0,
         f"{drained} vector-pool bytes after final eviction")

    ok = not errors
    rec = uledger.make_record(
        "vector_bench", backend=backend, ok=ok, rows=IVF_ROWS,
        dim=IVF_DIM, metric=reader.metric, k=K, nprobe=nprobe_def,
        n_lists=IVF_LISTS, recall_at_10=recall,
        qps_ivf=round(qps_ivf, 2), qps_exact=round(qps_exact, 2),
        qps_ratio=round(ratio, 2), p50_ms=round(p50, 3),
        p99_ms=round(p99, 3), seed=IVF_SEED, queries=len(queries),
        page_size=int(reader.ivf["pages"].shape[1]), batch=IVF_BATCH,
        qps_batched=round(qps_batched, 2), batched_equal=batched_equal,
        retraces=int(retraces), unaccounted_bytes=int(unaccounted),
        nprobe_sweep={str(k_): v for k_, v in sweep.items()})
    ledger_append_raw(rec)

    out = {
        "metric": METRIC_IVF,
        "value": round(qps_ivf, 2),
        "unit": "queries/s",
        "vs_baseline": round(ratio, 2),
        "n_rows": IVF_ROWS,
        "queries": {
            "ivf": {"ok": ok, "dim": IVF_DIM, "k": K,
                    "n_lists": IVF_LISTS, "nprobe": nprobe_def,
                    "recall_at_10": recall, "nprobe_sweep": sweep,
                    "qps_exact": round(qps_exact, 2),
                    "qps_batched": round(qps_batched, 2),
                    "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
                    "batched_equal": batched_equal,
                    "retraces": int(retraces),
                    "unaccounted_bytes": int(unaccounted)},
        },
    }
    if errors:
        out["error"] = "; ".join(errors)[:400]
    finish(out, backend, ok)


def main() -> None:
    from bench_common import finish, require_backend

    backend = require_backend(METRIC)

    from pinot_tpu.index.vector import VectorIndexReader

    rng = np.random.default_rng(7)
    mat = rng.standard_normal((N_ROWS, DIM), dtype=np.float32)
    queries = rng.standard_normal((QUERIES, DIM), dtype=np.float32)

    reader = VectorIndexReader.from_matrix(mat)

    # warm: residency + compile
    got = reader.top_k_docs(queries[0], K)
    t0 = time.perf_counter()
    for q in queries:
        reader.top_k_docs(q, K)
    dev_t = (time.perf_counter() - t0) / QUERIES

    # numpy single-thread baseline (normalized matmul + argpartition)
    norms = np.linalg.norm(mat, axis=1, keepdims=True)
    mn = mat / np.maximum(norms, 1e-30)
    qn = queries[0] / np.linalg.norm(queries[0])
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        sims = mn @ qn
        idx = np.argpartition(-sims, K - 1)[:K]
        base = idx[np.argsort(-sims[idx])]
    cpu_t = (time.perf_counter() - t0) / reps

    del got
    # exactness check on the warm query (device and numpy agree on top-k)
    ok = set(reader.top_k_docs(queries[0], K).tolist()) == \
        set(base.tolist())

    out = {
        "metric": METRIC,
        "value": round(1.0 / dev_t, 2),
        "unit": "queries/s",
        "vs_baseline": round(cpu_t / dev_t, 2),
        "n_rows": N_ROWS,
        "queries": {
            "topk": {"ok": ok, "dim": DIM, "k": K,
                     "device_ms": round(dev_t * 1e3, 3),
                     "cpu_ms": round(cpu_t * 1e3, 3),
                     "rows_per_sec": round(N_ROWS / dev_t)},
        },
    }
    finish(out, backend, ok)


if __name__ == "__main__":
    if "--ivf" in sys.argv[1:]:
        main_ivf()
    else:
        main()
