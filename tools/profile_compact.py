"""Phase-level profile of the fused compact-strategy SSB kernels.

Decomposes kernel time into the round-6 pipeline's phases —
mask / fuse (key + payload materialization) / compact / sort /
aggregate / transfer — for the slow compact-path queries, so
strategy-ladder regressions are visible between captures (round-6
satellite). The decomposition itself lives in
pinot_tpu/ops/phase_profile.py (EXPLAIN ANALYZE's
OPTION(profilePhases=true) shares it); this CLI appends one validated
v2 ``phase_profile`` record per query to the capture log
(pinot_tpu/utils/ledger.py), so the ledger keeps a phase-attribution
history alongside the headline captures.

Run standalone (CPU or chip; bounded by the caller):

    python tools/profile_compact.py q2.1 q3.2 q4.3

Prints one JSON line per query with phase times, compaction stats, and
the planner's cost-model trace (estimated vs measured selectivity).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    qids = set(sys.argv[1:]) or {"q2.1", "q3.2", "q4.3"}
    from bench import QUERIES, build_or_load_segment, spec_to_sql
    from bench_common import LEDGER
    from pinot_tpu.ops.phase_profile import profile_plan
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql
    from pinot_tpu.utils import ledger as uledger

    seg = build_or_load_segment()
    backend = jax.default_backend()

    for qid, preds, vexpr, gcols in QUERIES:
        if qid not in qids:
            continue
        sql = spec_to_sql(preds, vexpr, gcols)
        ctx = build_query_context(parse_sql(sql))
        plan = SegmentPlanner(ctx, seg).plan()
        rec = uledger.make_record(
            "phase_profile",
            metric="compact_phase_profile", backend=backend, qid=qid,
            n_rows=int(seg.n_docs), **profile_plan(plan))
        print(json.dumps(rec), flush=True)
        uledger.append_record(rec, LEDGER)


if __name__ == "__main__":
    main()
