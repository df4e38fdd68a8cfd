"""Benchmark: production-rate streaming ingest under chaos, while
querying (ISSUE 11 tentpole — ROADMAP direction 4).

Prints ONE JSON line:
    {"metric": "ingest_bench", "value": N, "unit": "rows/s",
     "freshness_p50_ms": ..., "freshness_p99_ms": ...,
     "commit_p50_ms": ..., "query_p50_ms": ..., "query_p99_ms": ...,
     "oracle_ok": true, "faults_fired": N, "restarts": N, ...}

value: delivered rows/sec across all partitions, sustained by the
closed-loop harness (pinot_tpu/engine/loadgen.py): seeded multi-
partition producers push through a real wire-protocol stream transport
(--backend mem|wire|kafka|kinesis|pulsar) into RealtimeTableDataManager
consumers WHILE a concurrent query mix runs through the Broker — with
the round-9/11 fault plan armed by default (every ingest point: stream
error/rebalance, commit crash + HTTP error, handoff stall, upsert
compact-crash), injected process deaths answered by checkpoint
restarts. The run only reports ok when the final queryable state is
byte-identical to the fault-free oracle — the freshness numbers are
meaningless if chaos lost or duplicated rows.

Freshness (fetch->queryable EWMA sampled through the run, p50/p99),
commit latency (seal->durable checkpoint), per-partition throughput and
query p50/p99 under ingest pressure land in a validated
``ingest_bench`` ledger record plus one ``ingest_stats`` record per
table (the rows the fleet rollup trends); bench_common.finish() then
runs the span-diff AND freshness-gate ratchets
(tools/freshness_gate.py vs tools/freshness_baseline.json).

    python bench_ingest.py                      # drain mode, chaos on
    python bench_ingest.py --rate 5000          # paced rows/s/partition
    python bench_ingest.py --backend kafka --no-chaos
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)



def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=2000,
                    help="rows per partition (default %(default)s)")
    ap.add_argument("--rate", type=float, default=None,
                    help="target produce rate rows/s per partition "
                         "(default: drain mode — flat out)")
    ap.add_argument("--partitions", type=int, default=2,
                    help="partitions per table (default %(default)s)")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="concurrent query workers (default %(default)s)")
    ap.add_argument("--backend", default="mem",
                    choices=("mem", "wire", "kafka", "kinesis", "pulsar"))
    ap.add_argument("--seed", type=int, default=20260805)
    ap.add_argument("--no-chaos", action="store_true",
                    help="fault-free run (chaos armed by default)")
    ap.add_argument("--no-batch", action="store_true",
                    help="disable cross-query micro-batching (on by "
                         "default since round 16)")
    ap.add_argument("--max-wall", type=float, default=180.0)
    ap.add_argument("--no-ledger", action="store_true",
                    help="skip the capture-log append (smoke runs)")
    args = ap.parse_args(argv)

    from bench_common import (attach_capture_context, finish,
                              install_capture_guard, require_backend)
    backend = require_backend("ingest_bench")

    from pinot_tpu.engine.loadgen import (LoadgenConfig, TableLoadSpec,
                                          run_load)
    from pinot_tpu.engine.ragged import global_batcher
    # the ONE all-points chaos plan (tools/ingest_fuzz.ingest_plan —
    # hand-copying it here would let the bench's chaos coverage drift
    # from the gate's when the fault family grows)
    from pinot_tpu.tools.ingest_fuzz import ingest_plan
    if args.no_batch:
        global_batcher.configure(enabled=False)

    out: dict = {"metric": "ingest_bench", "value": 0, "unit": "rows/s",
                 "n_rows": 2 * args.partitions * args.rows}
    install_capture_guard(
        lambda: attach_capture_context(dict(out), backend))

    import bench_common
    cfg = LoadgenConfig(
        tables=[
            TableLoadSpec("bi_append", partitions=args.partitions,
                          backend=args.backend),
            TableLoadSpec("bi_upsert", partitions=args.partitions,
                          upsert=True, protocol=True,
                          backend=args.backend),
        ],
        seed=args.seed,
        rows_per_partition=args.rows,
        rate_rows_s=args.rate,
        query_concurrency=args.concurrency,
        scenario="bench_ingest",
        fault_plan=None if args.no_chaos
        else ingest_plan(args.seed, protocol=True),
        ledger_path=None if args.no_ledger else bench_common.LEDGER,
        max_wall_s=args.max_wall)

    tmp = tempfile.mkdtemp(prefix="ptpu_bench_ingest_")
    try:
        summary = run_load(tmp, cfg)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    out.update({k: v for k, v in summary.items() if k != "per_table"})
    out["metric"] = "ingest_bench"
    out["value"] = summary["rows_per_s"]
    out["unit"] = "rows/s"
    out["n_rows"] = summary["rows"]
    out["per_table"] = {
        t: {k: st.get(k) for k in ("rows", "commits", "restarts",
                                   "freshness_p50_ms",
                                   "freshness_p99_ms", "oracle_ok")}
        for t, st in summary["per_table"].items()}

    all_ok = bool(summary["ok"])
    if not args.no_chaos and summary.get("faults_fired", 0) < 1:
        # an armed plan that never fired would make the chaos claim
        # vacuous — fail the capture loudly
        all_ok = False
        out.setdefault("error", "chaos plan armed but no fault fired")
    finish(out, backend, all_ok)


if __name__ == "__main__":
    main()
