"""Benchmark: the FULL SSB suite (Q1.1-Q4.3) on one real chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "queries": {qid: {...}}}

value: geometric-mean end-to-end scanned rows/sec/chip over the 13
queries (full query path: plan + kernel + reduce). vs_baseline:
geometric-mean speedup over a single-threaded vectorized numpy CPU
implementation of the same queries on the same data — the stand-in for
the reference's single-threaded pinot-perf JMH baseline (BASELINE.md:
the reference publishes no absolute numbers; the CPU baseline must be
measured, and a numpy dict-id scan is a *stronger* baseline than Pinot's
per-block Java loop). Per-query detail reports device-kernel time and
end-to-end time separately, plus effective HBM GB/s on the kernel and
the group-by strategy the planner picked.

One process: it checks the backend (bench_common.require_backend — a
TPU, or an explicit PINOT_BENCH_FORCE_CPU=1 rehearsal), builds the
segment, uploads once and runs the 13 queries. A query that raises or
misses its digest is listed by name and the process exits non-zero.

Queries: the 13 SSB queries (reference:
pinot-integration-tests/src/test/resources/ssb/ssb_query_set.yaml:22+)
with dimension-table predicates denormalized onto a flat lineorder table
(BASELINE.md configs 2-4) — the dimension attributes each query touches
(d_year, p_brand1, s_region, c_city, ...) are materialized as
dictionary-encoded columns, hierarchically consistent with the SSB spec
(brand -> category -> mfgr; city -> nation -> region).
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Optional

import numpy as np

N_ROWS = int(os.environ.get("PINOT_BENCH_ROWS", 1 << 27))  # 134M default
ITERS = int(os.environ.get("PINOT_BENCH_ITERS", 3))
CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache")
OPTION = " OPTION(timeoutMs=600000)"

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    # 5 per region, region r owns nations r*5..r*5+4 (SSB nation list)
    "ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE",
    "ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES",
    "INDIA", "INDONESIA", "JAPAN", "CHINA", "VIETNAM",
    "FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM",
    "EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA",
]
# SSB cities: nation name truncated to 9 chars + digit 0-9
CITIES = [n[:9] + str(d) for n in NATIONS for d in range(10)]
MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun",
          "Jul", "Aug", "Sep", "Oct", "Nov", "Dec"]
YEARS = list(range(1992, 1999))
YEARMONTHS = [f"{m}{y}" for y in YEARS for m in MONTHS]
# brands: MFGR#<m><c><b>, m 1-5, c 1-5, b 1-40; category MFGR#<m><c>
BRANDS = [f"MFGR#{m}{c}{b}" for m in range(1, 6) for c in range(1, 6)
          for b in range(1, 41)]
CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]


def gen_columns(n: int, seed=1992):
    """Generate the flat denormalized lineorder columns from ``seed``
    (an int, or a sequence of ints such as (seed, segment index))."""
    from pinot_tpu.segment.builder import Categorical

    rng = np.random.default_rng(seed)
    year = rng.integers(0, 7, n).astype(np.int16)          # 1992..1998
    month = rng.integers(0, 12, n).astype(np.int8)
    brand = rng.integers(0, 1000, n).astype(np.int16)
    s_nation = rng.integers(0, 25, n).astype(np.int8)
    c_nation = rng.integers(0, 25, n).astype(np.int8)
    s_city = (s_nation.astype(np.int16) * 10
              + rng.integers(0, 10, n).astype(np.int16))
    c_city = (c_nation.astype(np.int16) * 10
              + rng.integers(0, 10, n).astype(np.int16))
    return {
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_extendedprice": rng.integers(900, 55451, n).astype(np.int32),
        "lo_revenue": rng.integers(10000, 6000000, n).astype(np.int32),
        "lo_supplycost": rng.integers(10000, 120000, n).astype(np.int32),
        "d_year": (year.astype(np.int32) + 1992),
        "d_yearmonthnum": ((year.astype(np.int32) + 1992) * 100
                           + month + 1),
        "d_weeknuminyear": rng.integers(1, 54, n).astype(np.int32),
        "d_yearmonth": Categorical(year.astype(np.int16) * 12 + month,
                                   YEARMONTHS),
        "p_brand1": Categorical(brand, BRANDS),
        "p_category": Categorical((brand // 40).astype(np.int8), CATEGORIES),
        "p_mfgr": Categorical((brand // 200).astype(np.int8), MFGRS),
        "s_region": Categorical((s_nation // 5).astype(np.int8), REGIONS),
        "s_nation": Categorical(s_nation, NATIONS),
        "s_city": Categorical(s_city, CITIES),
        "c_region": Categorical((c_nation // 5).astype(np.int8), REGIONS),
        "c_nation": Categorical(c_nation, NATIONS),
        "c_city": Categorical(c_city, CITIES),
    }


def _ssb_fields(cols):
    from pinot_tpu.spi import DataType, FieldSpec, FieldType

    fields = []
    for name in cols:
        if name.startswith("lo_") and name not in ("lo_quantity",
                                                   "lo_discount"):
            fields.append(FieldSpec(name, DataType.INT, FieldType.METRIC))
        elif isinstance(cols[name], np.ndarray):
            fields.append(FieldSpec(name, DataType.INT, FieldType.DIMENSION))
        else:
            fields.append(FieldSpec(name, DataType.STRING,
                                    FieldType.DIMENSION))
    return fields


def build_segment(n: int, out_dir: str):
    """Build the flat SSB segment at n rows under out_dir; returns it."""
    from pinot_tpu.segment import ImmutableSegment, SegmentBuilder
    from pinot_tpu.spi import Schema, TableConfig

    cols = gen_columns(n)
    schema = Schema("lineorder", _ssb_fields(cols))
    builder = SegmentBuilder(schema, TableConfig("lineorder"))
    seg_dir = builder.build(cols, out_dir, "seg_0")
    return ImmutableSegment.load(seg_dir)


def build_or_load_segment(n_rows: Optional[int] = None):
    from pinot_tpu.segment import ImmutableSegment

    n_rows = N_ROWS if n_rows is None else n_rows
    seg_dir = os.path.join(CACHE, f"ssb_flat_{n_rows}", "seg_0")
    if os.path.exists(os.path.join(seg_dir, "metadata.json")):
        return ImmutableSegment.load(seg_dir)
    return build_segment(n_rows, os.path.join(CACHE,
                                              f"ssb_flat_{n_rows}"))


# ---------------------------------------------------------------------------
# Query specs: (qid, preds, value_expr, group_cols)
# preds: (col, op, value) with op in {eq, in, between, lt}
# value_expr: (col,) | (col, '*', col) | (col, '-', col)
# ---------------------------------------------------------------------------

QUERIES = [
    ("q1.1", [("d_year", "eq", 1993), ("lo_discount", "between", (1, 3)),
              ("lo_quantity", "lt", 25)],
     ("lo_extendedprice", "*", "lo_discount"), []),
    ("q1.2", [("d_yearmonthnum", "eq", 199401),
              ("lo_discount", "between", (4, 6)),
              ("lo_quantity", "between", (26, 35))],
     ("lo_extendedprice", "*", "lo_discount"), []),
    ("q1.3", [("d_weeknuminyear", "eq", 6), ("d_year", "eq", 1994),
              ("lo_discount", "between", (5, 7)),
              ("lo_quantity", "between", (26, 35))],
     ("lo_extendedprice", "*", "lo_discount"), []),
    ("q2.1", [("p_category", "eq", "MFGR#12"), ("s_region", "eq", "AMERICA")],
     ("lo_revenue",), ["d_year", "p_brand1"]),
    ("q2.2", [("p_brand1", "between", ("MFGR#2221", "MFGR#2228")),
              ("s_region", "eq", "ASIA")],
     ("lo_revenue",), ["d_year", "p_brand1"]),
    ("q2.3", [("p_brand1", "eq", "MFGR#2221"), ("s_region", "eq", "EUROPE")],
     ("lo_revenue",), ["d_year", "p_brand1"]),
    ("q3.1", [("c_region", "eq", "ASIA"), ("s_region", "eq", "ASIA"),
              ("d_year", "between", (1992, 1997))],
     ("lo_revenue",), ["c_nation", "s_nation", "d_year"]),
    ("q3.2", [("c_nation", "eq", "UNITED STATES"),
              ("s_nation", "eq", "UNITED STATES"),
              ("d_year", "between", (1992, 1997))],
     ("lo_revenue",), ["c_city", "s_city", "d_year"]),
    ("q3.3", [("c_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("s_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("d_year", "between", (1992, 1997))],
     ("lo_revenue",), ["c_city", "s_city", "d_year"]),
    ("q3.4", [("c_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("s_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("d_yearmonth", "eq", "Jul1995")],
     ("lo_revenue",), ["c_city", "s_city", "d_year"]),
    ("q4.1", [("c_region", "eq", "AMERICA"), ("s_region", "eq", "AMERICA"),
              ("p_mfgr", "in", ("MFGR#1", "MFGR#2"))],
     ("lo_revenue", "-", "lo_supplycost"), ["d_year", "c_nation"]),
    ("q4.2", [("c_region", "eq", "AMERICA"), ("s_region", "eq", "AMERICA"),
              ("d_year", "in", (1997, 1998)),
              ("p_mfgr", "in", ("MFGR#1", "MFGR#2"))],
     ("lo_revenue", "-", "lo_supplycost"),
     ["d_year", "s_nation", "p_category"]),
    ("q4.3", [("c_region", "eq", "AMERICA"),
              ("s_nation", "eq", "UNITED STATES"),
              ("d_year", "in", (1997, 1998)),
              ("p_category", "eq", "MFGR#14")],
     ("lo_revenue", "-", "lo_supplycost"),
     ["d_year", "s_city", "p_brand1"]),
]


def _sql_lit(v) -> str:
    return f"'{v}'" if isinstance(v, str) else str(v)


def spec_to_sql(preds, value_expr, group_cols) -> str:
    agg = "SUM(" + " ".join(value_expr) + ")"
    sel = ", ".join(group_cols + [agg]) if group_cols else agg
    conds = []
    for col, op, val in preds:
        if op == "eq":
            conds.append(f"{col} = {_sql_lit(val)}")
        elif op == "lt":
            conds.append(f"{col} < {_sql_lit(val)}")
        elif op == "between":
            conds.append(f"{col} BETWEEN {_sql_lit(val[0])} "
                         f"AND {_sql_lit(val[1])}")
        elif op == "in":
            # the reference queries write 2-value sets as OR-of-equals;
            # keep that form so the planner's Or folding is exercised
            conds.append("(" + " OR ".join(
                f"{col} = {_sql_lit(v)}" for v in val) + ")")
    sql = f"SELECT {sel} FROM lineorder WHERE {' AND '.join(conds)}"
    if group_cols:
        sql += (" GROUP BY " + ", ".join(group_cols)
                + " ORDER BY " + ", ".join(group_cols) + " LIMIT 100000")
    return sql


# ---------------------------------------------------------------------------
# numpy oracle (= single-threaded CPU baseline, on dict ids like Pinot)
# ---------------------------------------------------------------------------

def _pred_mask(seg, col, op, val):
    ids = np.asarray(seg.fwd(col))
    d = seg.dictionary(col)
    vals = None if d is None else np.asarray(d.values)
    if op == "eq":
        if d is None:
            return ids == val
        i = d.index_of(val)
        return (ids == i) if i >= 0 else np.zeros(len(ids), dtype=bool)
    if op == "in":
        if d is None:
            return np.isin(ids, list(val))
        tgt = [i for i in (d.index_of(v) for v in val) if i >= 0]
        return np.isin(ids, tgt)
    if op == "lt":
        if d is None:
            return ids < val
        return ids < int(np.searchsorted(vals, val, side="left"))
    assert op == "between"
    lo_v, hi_v = val
    if d is None:
        return (ids >= lo_v) & (ids <= hi_v)
    lo = int(np.searchsorted(vals, lo_v, side="left"))
    hi = int(np.searchsorted(vals, hi_v, side="right"))
    return (ids >= lo) & (ids < hi)


def _value(seg, value_expr, mask):
    def col_vals(c):
        ids = np.asarray(seg.fwd(c))[mask]
        d = seg.dictionary(c)
        if d is None:
            return ids.astype(np.int64)
        return np.asarray(d.values)[ids].astype(np.int64)

    if len(value_expr) == 1:
        return col_vals(value_expr[0])
    a, op, b = value_expr
    return col_vals(a) * col_vals(b) if op == "*" \
        else col_vals(a) - col_vals(b)


def oracle_run(seg, preds, value_expr, group_cols):
    """Evaluate one spec with numpy; returns (rows, elapsed_seconds)."""
    t0 = time.perf_counter()
    mask = None
    for p in preds:
        m = _pred_mask(seg, *p)
        mask = m if mask is None else (mask & m)
    vals = _value(seg, value_expr, mask)
    if not group_cols:
        rows = [(int(vals.sum()),)]
        return rows, time.perf_counter() - t0
    dims = [(c, seg.columns[c].cardinality) for c in group_cols]
    key = np.zeros(int(mask.sum()), dtype=np.int64)
    for c, card in dims:
        key = key * card + np.asarray(seg.fwd(c))[mask].astype(np.int64)
    space = math.prod(card for _, card in dims)
    sums = np.bincount(key, weights=vals.astype(np.float64),
                       minlength=space)
    cnts = np.bincount(key, minlength=space)
    idxs = np.nonzero(cnts)[0]
    elapsed = time.perf_counter() - t0
    keycols = []
    rem = idxs.copy()
    for c, card in reversed(dims):
        keycols.append(seg.dictionary(c).values_for(rem % card))
        rem = rem // card
    keycols.reverse()
    rows = [tuple(_py(kc[i]) for kc in keycols) + (int(sums[idxs[i]]),)
            for i in range(len(idxs))]
    return rows, elapsed


def _py(v):
    return v.item() if isinstance(v, np.generic) else v


def _digest(rows):
    out = []
    for r in rows:
        out.append(tuple(str(x) if isinstance(x, str) else int(x)
                         for x in r))
    return sorted(out)


# ---------------------------------------------------------------------------
# engine execution: end-to-end (broker) and device-kernel-only timings
# ---------------------------------------------------------------------------

def engine_e2e(broker, sql, iters):
    """Returns (result, best_seconds, retraces): retraces counts kernel
    plan-cache misses during the POST-warmup iterations — the round-6
    acceptance gate requires it to be 0 (the keyed plan cache plus the
    quantized cost-model capacity make every repeat iteration a pure
    cache hit). The in-engine RetraceDetector (round-7) must agree:
    any divergence means a compile escaped the detector's generation
    accounting."""
    from pinot_tpu.ops.plan_cache import global_plan_cache

    res = broker.query(sql + OPTION)  # warmup: upload + compile
    miss0 = global_plan_cache.snapshot_misses()
    det0 = global_plan_cache.detector.retraces
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        res = broker.query(sql + OPTION)
        best = min(best, time.perf_counter() - t0)
    misses = global_plan_cache.snapshot_misses() - miss0
    detected = global_plan_cache.detector.retraces - det0
    return res, best, max(misses, detected)


def kernel_time(seg, sql, iters):
    """Time just the jitted device kernel (no plan/reduce/host).

    Uses the SAME cost-model compaction capacity the executor runs with
    (CompiledPlan.slots_cap) so kernel_ms measures the production kernel,
    and mirrors the executor's overflow retry: if the tight capacity
    overflows, the full-capacity kernel is what production pays, so that
    is what gets timed."""
    import jax

    from pinot_tpu.engine.executor import resolve_params
    from pinot_tpu.ops.compact import full_slots_cap
    from pinot_tpu.ops.kernels import jitted_kernel
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    ctx = build_query_context(parse_sql(sql))
    plan = SegmentPlanner(ctx, seg).plan()
    if plan.kind != "kernel":
        return None, plan.kind, 0
    cols = seg.device_cols(plan.col_names)
    params = resolve_params(plan)
    fn = jitted_kernel(plan.kernel_plan, seg.bucket, plan.slots_cap)
    n = np.int32(seg.n_docs)
    out = jax.device_get(fn(cols, n, params))  # compile + warm
    if int(out.get("overflow", 0)):
        fn = jitted_kernel(plan.kernel_plan, seg.bucket,
                           full_slots_cap(seg.bucket))
        jax.block_until_ready(fn(cols, n, params))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(cols, n, params))
    t_one = time.perf_counter() - t0
    # pipelined launches: per-launch device time ~= (t_{k+1} - t_1) / k
    k = max(iters, 5)
    t0 = time.perf_counter()
    outs = [fn(cols, n, params) for _ in range(k + 1)]
    jax.block_until_ready(outs)
    t_k = time.perf_counter() - t0
    best = max((t_k - t_one) / k, 1e-9)
    nbytes = sum(c.nbytes for c in cols)
    return best, plan.kernel_plan.strategy, nbytes


METRIC = "ssb_q1.1-q4.3_geomean_rows_per_sec_per_chip"
QPS_METRIC = "ssb_concurrent_qps"

# ---------------------------------------------------------------------------
# concurrent-QPS mode (--concurrency N, PR 8): N simultaneous
# plan-shape-sharing SSB queries through the broker, cross-query
# micro-batching fused vs the serial per-query dispatch path
# ---------------------------------------------------------------------------

# literal-variant generators per SSB shape: each variant KEEPS the plan
# structure (eq stays eq, BETWEEN keeps both bounds, OR-of-equals keeps
# its width) and varies only literal values, so concurrent variants
# share the exact KernelPlan the plan cache / ragged batcher key on
QPS_SHAPES = [
    ("q1.1", lambda i:
        f"SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder "
        f"WHERE d_year = {1992 + i % 7} "
        f"AND lo_discount BETWEEN {i % 4} AND {i % 4 + 2} "
        f"AND lo_quantity < {20 + i % 15}"),
    ("q1.2", lambda i:
        f"SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder "
        f"WHERE d_yearmonthnum = {199201 + (i % 7) * 100 + i % 12} "
        f"AND lo_discount BETWEEN {1 + i % 4} AND {3 + i % 4} "
        f"AND lo_quantity BETWEEN {10 + i % 10} AND {30 + i % 10}"),
    ("q3.1", lambda i:
        f"SELECT c_nation, s_nation, d_year, SUM(lo_revenue) "
        f"FROM lineorder WHERE c_region = '{REGIONS[i % 5]}' "
        f"AND s_region = '{REGIONS[(i // 5) % 5]}' "
        f"AND d_year BETWEEN {1992 + i % 2} AND {1996 + i % 3} "
        f"GROUP BY c_nation, s_nation, d_year "
        f"ORDER BY c_nation, s_nation, d_year LIMIT 100000"),
    ("q4.1", lambda i:
        f"SELECT d_year, c_nation, "
        f"SUM(lo_revenue - lo_supplycost) FROM lineorder "
        f"WHERE c_region = '{REGIONS[i % 5]}' "
        f"AND s_region = '{REGIONS[(i // 5) % 5]}' "
        f"AND (p_mfgr = 'MFGR#{1 + i % 4}' OR p_mfgr = 'MFGR#{2 + i % 4}')"
        f" GROUP BY d_year, c_nation ORDER BY d_year, c_nation "
        f"LIMIT 100000"),
]

QPS_ROUNDS = int(os.environ.get("PINOT_BENCH_QPS_ROUNDS", 6))
QPS_WINDOW_MS = float(os.environ.get("PINOT_BENCH_QPS_WINDOW_MS", 8.0))


def _qps_broker(n_rows: int):
    from pinot_tpu.broker import Broker
    from pinot_tpu.server import TableDataManager

    dm = TableDataManager("lineorder")
    dm.add_segment(build_or_load_segment(n_rows))
    broker = Broker()
    broker.register_table(dm)
    return broker


def _drive_round(broker, sqls, out_rows, latencies, errors):
    """One synchronized wave: len(sqls) threads fire simultaneously."""
    import threading

    barrier = threading.Barrier(len(sqls))

    def worker(k):
        try:
            barrier.wait(30)
            t0 = time.perf_counter()
            res = broker.query(sqls[k])
            latencies.append((time.perf_counter() - t0) * 1e3)
            out_rows[k] = res.rows
        except Exception as e:  # noqa: BLE001 — collected, fails the run
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(len(sqls))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0


def _drive(broker, concurrency, rounds, latencies, errors):
    """-> (total wall s, digests {shape: [per-variant digest]}, n)."""
    digests: dict = {}
    wall = 0.0
    n = 0
    for shape, make in QPS_SHAPES:
        rows_out = [None] * concurrency
        sqls = [make(k) + OPTION for k in range(concurrency)]
        for _r in range(rounds):
            wall += _drive_round(broker, sqls, rows_out, latencies,
                                 errors)
            n += concurrency
        digests[shape] = [None if r is None else _digest(r)
                          for r in rows_out]
    return wall, digests, n


def run_concurrent_qps(concurrency: int) -> None:
    """The PR 8 acceptance benchmark: queries/sec through the broker at
    ``concurrency`` simultaneous plan-shape-sharing SSB queries, fused
    (cross-query micro-batching) vs the serial per-query dispatch path,
    with byte-identical digests and zero post-warmup retraces gated."""
    from bench_common import (attach_capture_context, finish,
                              install_capture_guard, require_backend)
    from pinot_tpu.engine.ragged import global_batcher
    from pinot_tpu.ops.plan_cache import global_plan_cache

    backend = require_backend(QPS_METRIC)
    n_rows = (N_ROWS if "PINOT_BENCH_ROWS" in os.environ
              else 1 << 20)
    out: dict = {"metric": QPS_METRIC, "value": 0, "unit": "queries/s",
                 "concurrency": concurrency, "n_rows": n_rows}
    install_capture_guard(lambda: attach_capture_context(dict(out),
                                                         backend))
    broker = _qps_broker(n_rows)
    errors: list = []

    # warmup both paths: compiles (solo kernels, cube builders, the
    # ragged pow2 ladder) happen here, outside every measured window.
    # Every pow2 rung <= concurrency is visited explicitly: measured
    # waves can split on arrival timing (e.g. 23+9), and a rung first
    # compiled mid-measurement would stall that wave — warmup, not a
    # retrace, by the detector's first-visit rule, but wall time the
    # measured rounds must not pay
    global_batcher.configure(enabled=False)
    _drive(broker, concurrency, 1, [], errors)
    global_batcher.configure(enabled=True, window_ms=QPS_WINDOW_MS,
                             max_batch=concurrency)
    _drive(broker, concurrency, 2, [], errors)
    rung = 2
    while rung < concurrency:
        _drive(broker, rung, 1, [], errors)
        rung *= 2
    if errors:
        out["error"] = f"warmup failed: {errors[0]}"
        print(json.dumps(attach_capture_context(out, backend)))
        sys.exit(1)

    # measured: fused first (zero-retrace gate brackets it), then serial
    miss0 = global_plan_cache.snapshot_misses()
    det0 = global_plan_cache.detector.retraces
    fused_lat: list = []
    snap0 = _batching_counters()
    fused_wall, fused_digests, n_fused = _drive(
        broker, concurrency, QPS_ROUNDS, fused_lat, errors)
    snap1 = _batching_counters()
    retraces = max(global_plan_cache.snapshot_misses() - miss0,
                   global_plan_cache.detector.retraces - det0)

    global_batcher.configure(enabled=False)
    serial_lat: list = []
    serial_wall, serial_digests, n_serial = _drive(
        broker, concurrency, QPS_ROUNDS, serial_lat, errors)

    # solo-dispatch latency for a lone query: batching on must not
    # regress the no-peers path (<5% gate)
    solo_sql = QPS_SHAPES[0][1](0) + OPTION
    def solo_median(enabled: bool) -> float:
        global_batcher.configure(enabled=enabled)
        ts = []
        for _ in range(9):
            t0 = time.perf_counter()
            broker.query(solo_sql)
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2] * 1e3
    solo_off = solo_median(False)
    solo_on = solo_median(True)
    global_batcher.configure(enabled=False)

    digests_ok = fused_digests == serial_digests and not errors
    qps = n_fused / fused_wall if fused_wall else 0.0
    qps_serial = n_serial / serial_wall if serial_wall else 0.0
    fused_q = snap1["batched_queries"] - snap0["batched_queries"]
    sl = sorted(fused_lat) or [0.0]
    out.update({
        "value": round(qps, 1),
        "qps": round(qps, 1),
        "qps_serial": round(qps_serial, 1),
        "qps_ratio": round(qps / qps_serial, 2) if qps_serial else 0.0,
        "p50_ms": round(sl[len(sl) // 2], 2),
        "p99_ms": round(sl[min(len(sl) - 1, int(len(sl) * 0.99))], 2),
        "fused_ratio": round(fused_q / max(n_fused, 1), 3),
        "solo_latency_ratio": round(solo_on / solo_off, 3)
        if solo_off else 0.0,
        "extra": {
            "retraces_post_warmup": retraces,
            "digests_byte_identical": digests_ok,
            "batched_dispatches": snap1["batched_dispatches"]
            - snap0["batched_dispatches"],
            "queries_per_mode": n_fused,
            "rounds": QPS_ROUNDS,
            "window_ms": QPS_WINDOW_MS,
        },
    })
    if errors:
        out["error"] = errors[0]
    all_ok = (digests_ok and retraces == 0
              and out["qps_ratio"] >= 2.0
              and out["solo_latency_ratio"] <= 1.05)
    if not all_ok and "error" not in out:
        out["error"] = ("concurrent-QPS acceptance gate failed "
                        f"(ratio {out['qps_ratio']}, retraces "
                        f"{retraces}, digests_ok {digests_ok}, solo "
                        f"{out['solo_latency_ratio']})")
    finish(out, backend, all_ok)


def _batching_counters() -> dict:
    from pinot_tpu.utils.metrics import global_metrics
    c = global_metrics.snapshot()["counters"]
    return {"batched_queries": c.get("batched_queries", 0),
            "batched_dispatches": c.get("batched_dispatches", 0)}


# ---------------------------------------------------------------------------
# multistage mode (--multistage, PR 16): the join+window+set-op SSB mix
# through whole-plan mesh compilation vs the mailbox exchange plane
# ---------------------------------------------------------------------------

MS_METRIC = "ssb_multistage_fused_qps"
MS_ROUNDS = int(os.environ.get("PINOT_BENCH_MS_ROUNDS", 5))
MS_FACT_ROWS = int(os.environ.get("PINOT_BENCH_MS_ROWS", 1 << 18))
MS_CUST_ROWS = 60_000     # > BROADCAST_THRESHOLD -> hash/all_to_all stage
MS_PART_ROWS = 2_000      # broadcast stage

# literal variants vary ONLY select-expression constants: every variant
# scans/joins identical row counts, so leaf shapes stay stable and the
# fused program compiles once per shape (the zero-retrace gate needs it)
MS_SHAPES = [
    ("join_gb", lambda i:
        f"SELECT c.c_nation, SUM(o.o_price + {i % 7}), COUNT(*) "
        f"FROM orders o JOIN customers c ON o.o_cust = c.c_id "
        f"GROUP BY c.c_nation ORDER BY c.c_nation LIMIT 10"),
    ("join3_gb", lambda i:
        f"SELECT c.c_nation, p.p_brand, SUM(o.o_price * 2 + {i % 5}) "
        f"FROM orders o JOIN customers c ON o.o_cust = c.c_id "
        f"JOIN parts p ON o.o_part = p.p_id "
        f"GROUP BY c.c_nation, p.p_brand "
        f"ORDER BY c.c_nation, p.p_brand LIMIT 40"),
    ("join_window", lambda i:
        f"SELECT c.c_nation, o.o_key + {i % 3}, "
        f"ROW_NUMBER() OVER (PARTITION BY c.c_nation ORDER BY o.o_key) "
        f"FROM orders o JOIN customers c ON o.o_cust = c.c_id "
        f"WHERE o.o_price > 3750 "
        f"ORDER BY c.c_nation, o.o_key LIMIT 50"),
    ("join_union", lambda i:
        f"SELECT c.c_nation, SUM(o.o_price + {i % 4}) FROM orders o "
        f"JOIN customers c ON o.o_cust = c.c_id "
        f"WHERE o.o_price > 2500 GROUP BY c.c_nation "
        f"UNION ALL "
        f"SELECT p.p_brand, SUM(o.o_price + {i % 4}) FROM orders o "
        f"JOIN parts p ON o.o_part = p.p_id "
        f"WHERE o.o_price <= 2500 GROUP BY p.p_brand"),
]


def _ms_broker():
    """Star schema sized to exercise BOTH collective lowerings: the
    customers build side exceeds BROADCAST_THRESHOLD (hash exchange ->
    lax.all_to_all), parts stays under it (broadcast)."""
    from pinot_tpu.broker import Broker
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.server import TableDataManager
    from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                               TableConfig)

    rng = np.random.default_rng(16)
    out = os.path.join(CACHE, f"multistage_{MS_FACT_ROWS}")
    cust = {"c_id": np.arange(MS_CUST_ROWS).astype(np.int32),
            "c_nation": rng.choice(["us", "de", "jp", "br", "cn"],
                                   MS_CUST_ROWS)}
    part = {"p_id": np.arange(MS_PART_ROWS).astype(np.int32),
            "p_brand": rng.choice(["acme", "blitz", "corex"],
                                  MS_PART_ROWS)}
    orders = {
        "o_key": np.arange(MS_FACT_ROWS).astype(np.int64),
        "o_cust": rng.choice(MS_CUST_ROWS, MS_FACT_ROWS).astype(np.int32),
        "o_part": rng.choice(MS_PART_ROWS, MS_FACT_ROWS).astype(np.int32),
        "o_price": rng.integers(10, 5000, MS_FACT_ROWS).astype(np.int64),
    }

    def build(name, cols, fields, n_segments=1):
        b = SegmentBuilder(Schema(name, fields), TableConfig(name))
        dm = TableDataManager(name)
        n = len(next(iter(cols.values())))
        bounds = np.linspace(0, n, n_segments + 1).astype(int)
        for i in range(n_segments):
            chunk = {k: v[bounds[i]:bounds[i + 1]]
                     for k, v in cols.items()}
            dm.add_segment_dir(b.build(chunk, os.path.join(out, name),
                                       f"s{i}"))
        return dm

    broker = Broker()
    broker.register_table(build("customers", cust, [
        FieldSpec("c_id", DataType.INT),
        FieldSpec("c_nation", DataType.STRING)]))
    broker.register_table(build("parts", part, [
        FieldSpec("p_id", DataType.INT),
        FieldSpec("p_brand", DataType.STRING)]))
    broker.register_table(build("orders", orders, [
        FieldSpec("o_key", DataType.LONG),
        FieldSpec("o_cust", DataType.INT),
        FieldSpec("o_part", DataType.INT),
        FieldSpec("o_price", DataType.LONG, FieldType.METRIC)],
        n_segments=4))
    return broker


def _ms_drive(broker, plane_opt: str, rounds: int, n_variants: int,
              latencies: list, errors: list):
    """-> (wall s, digests {shape: [variant digests]}, queries run)."""
    digests: dict = {}
    wall = 0.0
    n = 0
    for shape, make in MS_SHAPES:
        digests[shape] = [None] * n_variants
        for _r in range(rounds):
            for k in range(n_variants):
                sql = make(k) + plane_opt
                try:
                    t0 = time.perf_counter()
                    res = broker.query(sql)
                    dt = time.perf_counter() - t0
                    wall += dt
                    latencies.append(dt * 1e3)
                    digests[shape][k] = _digest(res.rows)
                    n += 1
                except Exception as e:  # noqa: BLE001 — fails the run
                    errors.append(f"{shape}[{k}]: "
                                  f"{type(e).__name__}: {e}")
    return wall, digests, n


def run_multistage() -> None:
    """PR 16 acceptance: the multistage mix through ONE fused shard_map
    program per plan vs the mailbox exchange plane (device joins
    disabled so every stage boundary pays the host round-trip the
    mailbox data plane actually costs), digests byte-identical, zero
    post-warmup retraces, >= 1.5x QPS."""
    from bench_common import (attach_capture_context, finish,
                              install_capture_guard, require_backend)
    from pinot_tpu.multistage import fused
    from pinot_tpu.ops.plan_cache import global_plan_cache

    backend = require_backend(MS_METRIC)
    n_variants = 3
    # NB "queries" stays out of the live capture dict: finish() treats
    # that key as the per-query detail MAP of the SSB suite record
    out: dict = {"metric": MS_METRIC, "value": 0, "unit": "queries/s",
                 "rows": MS_FACT_ROWS,
                 "query_count": len(MS_SHAPES) * n_variants}
    install_capture_guard(lambda: attach_capture_context(dict(out),
                                                         backend))
    broker = _ms_broker()
    errors: list = []
    fused0 = dict(fused.STATS)

    # warmup both planes: fused whole-plan compiles (one per shape) and
    # the mailbox plane's window/groupby kernels happen here, outside
    # every measured window
    _ms_drive(broker, " OPTION(multistageFused=true)", 1, n_variants,
              [], errors)
    mailbox_env = {"PINOT_DEVICE_JOIN_MIN_ROWS": str(1 << 62)}
    saved = {k: os.environ.get(k) for k in mailbox_env}
    os.environ.update(mailbox_env)
    _ms_drive(broker, " OPTION(multistageFused=false)", 1, n_variants,
              [], errors)
    for k, v in saved.items():
        os.environ.pop(k, None) if v is None else \
            os.environ.__setitem__(k, v)
    if errors:
        out["error"] = f"warmup failed: {errors[0]}"
        print(json.dumps(attach_capture_context(out, backend)))
        sys.exit(1)

    # measured: fused first, bracketed by the zero-retrace gate
    miss0 = global_plan_cache.snapshot_misses()
    det0 = global_plan_cache.detector.retraces
    lat_f: list = []
    wall_f, dig_f, n_f = _ms_drive(
        broker, " OPTION(multistageFused=true)", MS_ROUNDS, n_variants,
        lat_f, errors)
    retraces = max(global_plan_cache.snapshot_misses() - miss0,
                   global_plan_cache.detector.retraces - det0)

    os.environ.update(mailbox_env)
    lat_m: list = []
    wall_m, dig_m, n_m = _ms_drive(
        broker, " OPTION(multistageFused=false)", MS_ROUNDS, n_variants,
        lat_m, errors)
    for k, v in saved.items():
        os.environ.pop(k, None) if v is None else \
            os.environ.__setitem__(k, v)

    digests_ok = dig_f == dig_m and not errors
    qps_f = n_f / wall_f if wall_f else 0.0
    qps_m = n_m / wall_m if wall_m else 0.0
    speedup = qps_f / qps_m if qps_m else 0.0
    sl = sorted(lat_f) or [0.0]
    fused_delta = {k: fused.STATS[k] - fused0[k] for k in fused.STATS}
    out.update({
        "value": round(qps_f, 1),
        "qps_fused": round(qps_f, 1),
        "qps_mailbox": round(qps_m, 1),
        "speedup": round(speedup, 2),
        "p50_ms": round(sl[len(sl) // 2], 2),
        "p99_ms": round(sl[min(len(sl) - 1, int(len(sl) * 0.99))], 2),
        "digests_ok": digests_ok,
        "retraces": retraces,
        "extra": {
            "rounds": MS_ROUNDS,
            "fused_plans": fused_delta["fused_plans"],
            "fused_fallbacks": fused_delta["fused_fallbacks"],
            "queries_per_plane": n_f,
        },
    })
    if errors:
        out["error"] = errors[0]
    all_ok = (digests_ok and retraces == 0 and speedup >= 1.5
              and fused_delta["fused_fallbacks"] == 0)
    if not all_ok and "error" not in out:
        out["error"] = ("multistage acceptance gate failed "
                        f"(speedup {out['speedup']}, retraces "
                        f"{retraces}, digests_ok {digests_ok}, "
                        f"fallbacks {fused_delta['fused_fallbacks']})")

    # the validated multistage_bench v2 ledger record (writer contract
    # in pinot_tpu/utils/ledger.py; check_ledger reports the kind)
    from bench_common import ledger_append_raw
    from pinot_tpu.utils.ledger import make_record
    try:
        ledger_append_raw(make_record(
            "multistage_bench", backend=backend, ok=bool(all_ok),
            queries=out["query_count"], qps_fused=out["qps_fused"],
            qps_mailbox=out["qps_mailbox"], speedup=out["speedup"],
            p50_ms=out["p50_ms"], p99_ms=out["p99_ms"],
            digests_ok=bool(digests_ok), retraces=int(retraces),
            rows=MS_FACT_ROWS, rounds=MS_ROUNDS,
            fused_plans=fused_delta["fused_plans"],
            fused_fallbacks=fused_delta["fused_fallbacks"]))
    except ValueError as e:
        out["error"] = f"ledger contract violation: {e}"
        all_ok = False
    finish(out, backend, all_ok)


# ---------------------------------------------------------------------------
# constrained-budget HBM-tier mode (--tier, ISSUE 13): the full SSB mix
# under PINOT_HBM_BUDGET_BYTES below the working set, vs the no-tier
# strawman that evicts everything between queries (re-upload per query)
# ---------------------------------------------------------------------------

TIER_METRIC = "ssb_tier_constrained_qps_ratio"
TIER_SEGMENTS = 4


def _build_or_load_tier_segments(n_rows: int, table: str,
                                 seg_prefix: str,
                                 n_segments: int = TIER_SEGMENTS):
    """N-segment split of the flat SSB table (cached like
    build_or_load_segment — the tier bench needs multiple segments so
    demotion has per-segment granularity, and TWO tables so demotion
    has victims outside the querying table's pinned working set)."""
    from pinot_tpu.segment import ImmutableSegment, SegmentBuilder
    from pinot_tpu.segment.builder import Categorical
    from pinot_tpu.spi import Schema, TableConfig

    base = os.path.join(CACHE, f"ssb_tier_{table}_{n_rows}_{n_segments}")
    if not all(os.path.exists(os.path.join(base, f"{seg_prefix}{k}",
                                           "metadata.json"))
               for k in range(n_segments)):
        cols = gen_columns(n_rows)
        schema = Schema(table, _ssb_fields(cols))
        builder = SegmentBuilder(schema, TableConfig(table))
        step = n_rows // n_segments
        for k in range(n_segments):
            lo, hi = k * step, n_rows if k == n_segments - 1 \
                else (k + 1) * step
            part = {n: (Categorical(v.codes[lo:hi], v.values)
                        if isinstance(v, Categorical) else v[lo:hi])
                    for n, v in cols.items()}
            builder.build(part, base, f"{seg_prefix}{k}")
    return [ImmutableSegment.load(os.path.join(base, f"{seg_prefix}{k}"))
            for k in range(n_segments)]


def run_tier_bench() -> None:
    """The ISSUE-13 acceptance bench: the full SSB mix, alternated
    over two tables (working-set shifts — the realistic node whose
    total table-bytes exceed HBM), with the budget set below the
    working set must (a) answer byte-identical to the unbounded run,
    (b) leave zero unaccounted devmem bytes across the demotion churn,
    (c) beat the no-tier evict-all-between-queries strawman by >= 1.5x
    QPS, and (d) keep demotion churn bounded."""
    from bench_common import (attach_capture_context, finish,
                              install_capture_guard, require_backend)
    from pinot_tpu.broker import Broker
    from pinot_tpu.engine.tier import global_tier, reconcile_devmem
    from pinot_tpu.server import TableDataManager
    from pinot_tpu.utils.devmem import global_device_memory
    from pinot_tpu.utils.heat import global_segment_heat

    backend = require_backend(TIER_METRIC)
    n_rows = (N_ROWS if "PINOT_BENCH_ROWS" in os.environ else 1 << 20)
    iters = max(ITERS, 2)
    # the env budget applies to the TIER PHASE ONLY: pop it now so the
    # unbounded baseline and the strawman run genuinely unconstrained
    # (a budget left armed would clamp `peak` and flip the
    # engine/pipeline group router during the comparison phases too)
    env_budget = os.environ.pop("PINOT_HBM_BUDGET_BYTES", None)
    out: dict = {"metric": TIER_METRIC, "value": 0, "unit": "x",
                 "n_rows": n_rows}
    install_capture_guard(lambda: attach_capture_context(dict(out),
                                                         backend))
    dms = []
    all_segs = []
    for table, prefix in (("lineorder", "seg_"),
                          ("lineorder2", "t2seg_")):
        segs = _build_or_load_tier_segments(n_rows, table, prefix)
        dm = TableDataManager(table)
        for s in segs:
            dm.add_segment(s)
        dms.append(dm)
        all_segs.extend(segs)
    broker = Broker()
    for dm in dms:
        broker.register_table(dm)
    sqls = []
    for qid, p, v, g in QUERIES:
        sql = spec_to_sql(p, v, g) + OPTION
        sqls.append((qid, "a", sql))
        sqls.append((qid, "b", sql.replace("FROM lineorder ",
                                           "FROM lineorder2 ")))
    # table-phase order: the A mix, then the B mix — each phase reuses
    # its own residency, the phase switch shifts the working set
    sqls.sort(key=lambda t: t[1])

    def run_mix() -> dict:
        return {(qid, t): _digest(broker.query(sql).rows)
                for qid, t, sql in sqls}

    def evict_all() -> None:
        for s in all_segs:
            s.evict_device()

    def uploads() -> int:
        return sum(e["device_misses"]
                   for e in global_segment_heat.snapshot())

    base = run_mix()                    # warmup: compiles + uploads
    wall_unb = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        unb_digests = run_mix()
        wall_unb = min(wall_unb, time.perf_counter() - t0)
    peak = global_device_memory.snapshot()["total"]["bytes"]

    # strawman: a no-tier node whose working set exceeds HBM has to
    # drop everything between queries — re-pad, re-upload, re-stack
    evict_all()
    run_mix()                           # cold-path shapes warm too
    u0 = uploads()
    straw_digests: dict = {}
    wall_straw = float("inf")
    for it in range(iters):
        t0 = time.perf_counter()
        for qid, t, sql in sqls:
            evict_all()
            res = broker.query(sql)
            if it == iters - 1:
                straw_digests[qid, t] = _digest(res.rows)
        wall_straw = min(wall_straw, time.perf_counter() - t0)
    straw_uploads = (uploads() - u0) / iters

    # the tier: same constrained HBM, but heat-ranked residency —
    # budget below the working set (env override wins; default 60% of
    # the measured unbounded two-table peak — low enough to force
    # demotion churn at the table-phase switches, high enough that a
    # phase's own working set stays resident). The env var is restored
    # FOR THIS PHASE so engine/pipeline's group routing sees the same
    # budget a production node would.
    budget = int(env_budget) if env_budget else int(peak * 0.6)
    os.environ["PINOT_HBM_BUDGET_BYTES"] = str(budget)
    evict_all()
    global_tier.configure(budget_bytes=budget)
    d_settle0 = global_tier.demotions
    run_mix()                           # settle residency under budget
    d_timed0 = global_tier.demotions
    u1 = uploads()
    wall_tier = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        tier_digests = run_mix()
        wall_tier = min(wall_tier, time.perf_counter() - t0)
    tier_uploads = (uploads() - u1) / iters
    demotions_timed = global_tier.demotions - d_timed0
    demotions_total = global_tier.demotions - d_settle0
    rec = reconcile_devmem(all_segs)
    unaccounted = sum(abs(r["tracked"] - r["actual"])
                      for r in rec.values())
    global_tier.configure(budget_bytes=None)
    if env_budget is None:
        os.environ.pop("PINOT_HBM_BUDGET_BYTES", None)
    else:
        os.environ["PINOT_HBM_BUDGET_BYTES"] = env_budget

    n_q = len(sqls)
    ratio = wall_straw / wall_tier if wall_tier else 0.0
    upload_ratio = straw_uploads / max(tier_uploads, 1.0)
    digests_ok = base == unb_digests == straw_digests == tier_digests
    constrained = demotions_total > 0 and budget < peak
    churn_ok = demotions_timed <= 2 * n_q * iters
    # the >=1.5x QPS bar prices H2D transfer — on a real chip (PCIe vs
    # HBM) it binds directly; the CPU smoke's "device" is host memory
    # (device_put ~ memcpy, kernels ~7x slower per byte), so there the
    # gate is the deterministic avoided-upload proxy at the same bar
    # plus QPS non-regression vs the strawman. Same discipline as the
    # ROADMAP's CPU-smoke-vs-TPU-harvest split everywhere else.
    if backend == "tpu":
        perf_ok = ratio >= 1.5
        perf_detail = f"qps ratio {round(ratio, 2)} (need >=1.5)"
    else:
        perf_ok = upload_ratio >= 1.5 and ratio >= 1.0
        perf_detail = (f"cpu smoke: upload ratio "
                       f"{round(upload_ratio, 2)} (need >=1.5), qps "
                       f"ratio {round(ratio, 2)} (need >=1.0)")
    out.update({
        "value": round(ratio, 2),
        "vs_baseline": round(ratio, 2),
        "qps": round(n_q / wall_tier, 1) if wall_tier else 0.0,
        "extra": {
            "budget_bytes": budget,
            "working_set_bytes": peak,
            "qps_tier": round(n_q / wall_tier, 1) if wall_tier else 0,
            "qps_evict_all": round(n_q / wall_straw, 1)
            if wall_straw else 0,
            "qps_unbounded": round(n_q / wall_unb, 1)
            if wall_unb else 0,
            "digests_byte_identical": digests_ok,
            "uploads_per_pass_evict_all": round(straw_uploads, 1),
            "uploads_per_pass_tier": round(tier_uploads, 1),
            "upload_ratio": round(upload_ratio, 2),
            "tier_demotions": demotions_total,
            "tier_demotions_timed": demotions_timed,
            "tier_promotions": global_tier.promotions,
            "unaccounted_devmem_bytes": unaccounted,
        },
    })
    all_ok = (digests_ok and unaccounted == 0 and constrained
              and churn_ok and perf_ok)
    if not all_ok:
        out["error"] = ("tier acceptance gate failed: "
                        f"{perf_detail}, digests_ok {digests_ok}, "
                        f"unaccounted {unaccounted}, demotions "
                        f"{demotions_total} (timed {demotions_timed}, "
                        f"churn_ok {churn_ok})")
    finish(out, backend, all_ok)

def run_queries(detail: dict, errors: dict) -> bool:
    """Capture the 13 queries in THIS process, filling ``detail`` (and
    ``errors`` for a query that raised) as it goes so the capture guard
    can ship the completed prefix; -> all_ok."""
    seg = build_or_load_segment()
    from pinot_tpu.broker import Broker
    from pinot_tpu.server import TableDataManager

    dm = TableDataManager("lineorder")
    dm.add_segment(seg)
    broker = Broker()
    broker.register_table(dm)

    all_ok = True
    for qid, preds, vexpr, gcols in QUERIES:
        sql = spec_to_sql(preds, vexpr, gcols)
        try:
            expected, cpu_t = oracle_run(seg, preds, vexpr, gcols)
            res, e2e_t, retraces = engine_e2e(broker, sql, ITERS)
            k_t, strategy, nbytes = kernel_time(seg, sql, max(ITERS, 5))
        except Exception as e:  # noqa: BLE001 — listed by name, fails the run
            errors[qid] = f"{type(e).__name__}: {e}"[:500]
            all_ok = False
            print(f"  {qid}: FAILED {errors[qid]}", file=sys.stderr)
            continue
        ok = _digest(res.rows) == _digest(expected)
        all_ok = all_ok and ok
        detail[qid] = {
            "ok": ok,
            "strategy": strategy,
            "retrace_iter2": retraces,
            "groups": len(expected) if gcols else 0,
            # raw seconds: the geomeans must never run through 2-decimal
            # rounding (a 0.00 speedup would log(0) -> crash)
            "e2e_s": e2e_t,
            "cpu_s": cpu_t,
            "kernel_ms": round(k_t * 1e3, 3) if k_t else None,
            "e2e_ms": round(e2e_t * 1e3, 2),
            "cpu_ms": round(cpu_t * 1e3, 1),
            "rows_per_sec_e2e": round(N_ROWS / e2e_t),
            "rows_per_sec_kernel": round(N_ROWS / k_t) if k_t else None,
            "kernel_gbps": round(nbytes / k_t / 1e9, 1) if k_t else None,
            "speedup_e2e": round(cpu_t / e2e_t, 2),
            "speedup_kernel": round(cpu_t / k_t, 1) if k_t else None,
        }
        print(f"  {qid}: ok={ok} strat={strategy} "
              f"kernel={detail[qid]['kernel_ms']}ms "
              f"e2e={detail[qid]['e2e_ms']}ms cpu={detail[qid]['cpu_ms']}ms "
              f"x{detail[qid]['speedup_e2e']}", file=sys.stderr)
    return all_ok


def build_summary(detail: dict, errors: dict, partial: bool = False
                  ) -> dict:
    """The COMPLETE summary payload from whatever queries have finished —
    geomeans over captured queries only. Called by the capture guard
    (SIGTERM mid-run) and for the final line."""
    rates = []
    spds = []
    clean: dict = {}
    for qid, d in detail.items():
        d = dict(d)
        e2e_s = d.pop("e2e_s", None)
        cpu_s = d.pop("cpu_s", None)
        if e2e_s:
            rates.append(max(N_ROWS / e2e_s, 1e-12))
            spds.append(max((cpu_s or 0.0) / e2e_s, 1e-12))
        clean[qid] = d
    geo_rate = math.exp(sum(math.log(r) for r in rates)
                        / len(rates)) if rates else 0.0
    geo_speedup = math.exp(sum(math.log(s) for s in spds)
                           / len(spds)) if spds else 0.0
    out = {
        "metric": METRIC,
        "value": round(geo_rate),
        "unit": "rows/s",
        "vs_baseline": round(geo_speedup, 2),
        "n_rows": N_ROWS,
        "queries": clean,
    }
    if partial:
        out["partial"] = True
    if errors:
        out["errors"] = dict(errors)
        out["error"] = (f"{len(errors)} of {len(QUERIES)} queries failed "
                        "to capture (see errors); geomeans cover the "
                        "captured queries only")
    return out


def main() -> None:
    from bench_common import (attach_capture_context, finish,
                              install_capture_guard, require_backend)

    if "--concurrency" in sys.argv:
        n = int(sys.argv[sys.argv.index("--concurrency") + 1])
        run_concurrent_qps(n)
        return

    if "--multistage" in sys.argv:
        run_multistage()
        return

    if "--tier" in sys.argv:
        run_tier_bench()
        return

    backend = require_backend(METRIC)   # exits before building data
    detail: dict = {}
    errors: dict = {}
    # the guard prints a COMPLETE summary — geomeans over the captured
    # queries — even when a time limit SIGTERMs the capture mid-query
    install_capture_guard(lambda: attach_capture_context(
        build_summary(detail, errors, partial=True), backend))
    all_ok = run_queries(detail, errors)
    finish(build_summary(detail, errors), backend, all_ok)


if __name__ == "__main__":
    main()
