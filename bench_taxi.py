"""Benchmark: NYC-taxi-shaped high-cardinality GROUP BY on one real chip
(BASELINE.md config 4; round-3 item 3).

Prints ONE JSON line like bench.py: geomean end-to-end rows/s over the
query set + geomean speedup vs the single-threaded numpy CPU baseline,
with per-query detail (device-kernel vs end-to-end time, strategy,
groups). The two group keys match the config's shape:

- PULocationID: ~265 distinct zones (low card, high rows/group);
- a ~100k-card key (pickup minute-of-month x zone bucket): the
  high-cardinality case that must run the compact sort path on device
  and beat host numpy.

Usage: python bench_taxi.py   (env: PINOT_BENCH_ROWS, PINOT_BENCH_ITERS)
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

from bench import OPTION, engine_e2e, kernel_time  # shared harness

N_ROWS = int(os.environ.get("PINOT_BENCH_ROWS", 1 << 27))  # 134M default
ITERS = int(os.environ.get("PINOT_BENCH_ITERS", 3))
CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache")

N_ZONES = 265
HC_CARD = 100_000


def gen_columns(n: int):
    rng = np.random.default_rng(2016)
    return {
        "pu_loc": rng.integers(0, N_ZONES, n).astype(np.int32),
        "hc_key": rng.integers(0, HC_CARD, n).astype(np.int32),
        "fare": rng.integers(250, 20_000, n).astype(np.int32),  # cents
        "distance": rng.integers(1, 3_000, n).astype(np.int32),
        "passengers": rng.integers(1, 7, n).astype(np.int32),
    }


def build_segment(n: int, out_dir: str):
    from pinot_tpu.segment import ImmutableSegment, SegmentBuilder
    from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                               TableConfig)

    schema = Schema("trips", [
        FieldSpec("pu_loc", DataType.INT, FieldType.DIMENSION),
        FieldSpec("hc_key", DataType.INT, FieldType.DIMENSION),
        FieldSpec("fare", DataType.INT, FieldType.METRIC),
        FieldSpec("distance", DataType.INT, FieldType.METRIC),
        FieldSpec("passengers", DataType.INT, FieldType.DIMENSION),
    ])
    cfg = TableConfig("trips")
    cfg.indexing.dictionary_columns.append("hc_key")  # keep dict past 2^17
    builder = SegmentBuilder(schema, cfg)
    d = builder.build(gen_columns(n), out_dir, "seg_0")
    return ImmutableSegment.load(d)


def build_or_load_segment():
    from pinot_tpu.segment import ImmutableSegment

    seg_dir = os.path.join(CACHE, f"taxi_{N_ROWS}", "seg_0")
    if os.path.exists(os.path.join(seg_dir, "metadata.json")):
        return ImmutableSegment.load(seg_dir)
    return build_segment(N_ROWS, os.path.join(CACHE, f"taxi_{N_ROWS}"))


QUERIES = [
    ("zones_265", "pu_loc", None),
    ("zones_filtered", "pu_loc", "passengers >= 2"),
    ("hc_100k", "hc_key", None),
    ("hc_100k_filtered", "hc_key", "distance < 1500"),
]


def _sql(key, where):
    w = f" WHERE {where}" if where else ""
    return (f"SELECT {key}, COUNT(*), AVG(fare) FROM trips{w} "
            f"GROUP BY {key} LIMIT 200000")


def oracle_run(seg, key, where):
    """numpy single-thread oracle (CPU baseline, dict-id space)."""
    t0 = time.perf_counter()
    ids = np.asarray(seg.fwd(key)).astype(np.int64)
    card = seg.columns[key].cardinality
    fare = np.asarray(seg.dictionary("fare").values_for(
        np.asarray(seg.fwd("fare")))) if seg.columns["fare"].has_dict \
        else np.asarray(seg.fwd("fare"))
    if where is None:
        sel_ids, sel_fare = ids, fare.astype(np.float64)
    elif where.startswith("passengers"):
        p = np.asarray(seg.raw_values("passengers"))
        m = p >= 2
        sel_ids, sel_fare = ids[m], fare[m].astype(np.float64)
    else:
        dist = np.asarray(seg.raw_values("distance"))
        m = dist < 1500
        sel_ids, sel_fare = ids[m], fare[m].astype(np.float64)
    cnt = np.bincount(sel_ids, minlength=card)
    s = np.bincount(sel_ids, weights=sel_fare, minlength=card)
    elapsed = time.perf_counter() - t0
    live = np.nonzero(cnt)[0]
    d = seg.dictionary(key)
    keys = d.values_for(live)
    rows = {int(keys[i]): (int(cnt[live[i]]), s[live[i]] / cnt[live[i]])
            for i in range(len(live))}
    return rows, elapsed


METRIC = "nyc_taxi_groupby_geomean_rows_per_sec_per_chip"


def main() -> None:
    from bench_common import finish, require_backend

    backend = require_backend(METRIC)
    seg = build_or_load_segment()
    from pinot_tpu.broker import Broker
    from pinot_tpu.server import TableDataManager

    dm = TableDataManager("trips")
    dm.add_segment(seg)
    broker = Broker()
    broker.register_table(dm)

    detail = {}
    speedups = []
    rates = []
    all_ok = True
    for qid, key, where in QUERIES:
        sql = _sql(key, where)
        oracle, cpu_t = oracle_run(seg, key, where)
        res, e2e_t = engine_e2e(broker, sql, ITERS)
        k_t, strategy, nbytes = kernel_time(seg, sql, max(ITERS, 5))
        got = {int(r[0]): (int(r[1]), float(r[2])) for r in res.rows}
        ok = set(got) == set(oracle) and all(
            got[k][0] == oracle[k][0]
            and abs(got[k][1] - oracle[k][1]) <= 1e-6 * max(
                1.0, abs(oracle[k][1]))
            for k in oracle)
        all_ok = all_ok and ok
        speedups.append(cpu_t / e2e_t)
        rates.append(N_ROWS / e2e_t)
        detail[qid] = {
            "ok": ok, "strategy": strategy, "groups": len(oracle),
            "kernel_ms": round(k_t * 1e3, 3) if k_t else None,
            "e2e_ms": round(e2e_t * 1e3, 2),
            "cpu_ms": round(cpu_t * 1e3, 1),
            "rows_per_sec_e2e": round(N_ROWS / e2e_t),
            "speedup_e2e": round(cpu_t / e2e_t, 2),
        }
        print(f"  {qid}: ok={ok} strat={strategy} "
              f"kernel={detail[qid]['kernel_ms']}ms "
              f"e2e={detail[qid]['e2e_ms']}ms cpu={detail[qid]['cpu_ms']}ms"
              f" x{detail[qid]['speedup_e2e']}", file=sys.stderr)

    geo = lambda xs: math.exp(sum(math.log(x) for x in xs) / len(xs))  # noqa
    out = {
        "metric": METRIC,
        "value": round(geo(rates)),
        "unit": "rows/s",
        "vs_baseline": round(geo(speedups), 2),
        "n_rows": N_ROWS,
        "queries": detail,
    }
    finish(out, backend, all_ok)


if __name__ == "__main__":
    main()
