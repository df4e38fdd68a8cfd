"""Warmup-debt report + post-warmup compile gate over ``compile_event``
ledger records (ISSUE 15).

The compile plane (pinot_tpu/utils/compileplane.py) lands one validated
``compile_event`` per XLA compile: site, trigger taxonomy {cold, warmup,
overflow_retry, drift_requantize, lru_evict_rebuild, retrace}, explicit
``lower_ms``/``compile_ms`` split, normalized plan-shape hash (shared
with span_diff via utils/shapehash) and executable memory/FLOPs. This
tool renders the cold-start debt report from any ledger and gates it:

    python tools/warmup_report.py report [ledger ...]
    python tools/warmup_report.py gate   [ledger ...] \
        [--max-post-warmup N] [--min-events N]

``report`` prints per-plan-shape rows (compiles, median/total compile
ms, trigger breakdown, warmup cost = compiles x median — the same
ranking cluster/rollup.py ships as ``fleet_rollup.plan_shapes``) plus
the per-trigger and per-site totals, one summary JSON line last.

``gate`` is the ratchet beside the span / freshness / overload gates
(tests/test_compile_forensics.py runs it): post-warmup compiles (trigger retrace or
lru_evict_rebuild) above ``--max-post-warmup`` (default 0) fail with
exit 1 — a warmed engine paying unexplained compiles is the compile
storm's leading indicator, caught at test time instead of as a silent
QPS cliff. ``--min-events`` (default 1) guards against a structurally
vacuous green: a gate corpus that emitted NO compile events means the
instrumentation is broken, not that warmup debt is zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pinot_tpu.utils.compileplane import (  # noqa: E402
    POST_WARMUP_TRIGGERS, TRIGGERS)
from pinot_tpu.utils.ledger import default_capture_log  # noqa: E402

POST_WARMUP = set(POST_WARMUP_TRIGGERS)


def load_compile_events(paths: List[str]) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    for path in paths:
        if not os.path.exists(path):
            continue
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and \
                        rec.get("kind") == "compile_event":
                    out.append(rec)
    return out


def summarize(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Pure events -> report dict (the oracle tests pin this).

    Events dedupe by (proc, seq) first — a FLEET ledger ships the same
    event once per node that served it (cluster/rollup puller), and a
    duplicate-counted retrace would spuriously trip the gate. The
    per-shape aggregation IS cluster/rollup.rank_plan_shapes, so this
    report and the webapp plan_shapes panel can never disagree over
    one corpus."""
    from pinot_tpu.cluster.rollup import rank_plan_shapes

    seen: set = set()
    deduped: List[Dict[str, Any]] = []
    for e in events:
        uid = (e.get("proc"), e.get("seq"))
        if uid in seen:
            continue
        seen.add(uid)
        deduped.append(e)
    by_trigger: Dict[str, int] = {}
    by_site: Dict[str, int] = {}
    total_ms = 0.0
    for e in deduped:
        total_ms += float(e.get("lower_ms", 0.0)) \
            + float(e.get("compile_ms", 0.0))
        t = e.get("trigger") or "?"
        by_trigger[t] = by_trigger.get(t, 0) + 1
        site = e.get("site") or "?"
        by_site[site] = by_site.get(site, 0) + 1
    return {
        "events": len(deduped),
        "compile_ms_total": round(total_ms, 3),
        "by_trigger": {t: by_trigger[t] for t in sorted(by_trigger)},
        "by_site": {s: by_site[s] for s in sorted(by_site)},
        "post_warmup": sum(n for t, n in by_trigger.items()
                           if t in POST_WARMUP),
        "shapes": rank_plan_shapes(deduped, top=len(deduped) or 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=["report", "gate"])
    ap.add_argument("ledgers", nargs="*",
                    help="ledger path(s); default: the program's "
                         "capture log")
    ap.add_argument("--max-post-warmup", type=int, default=0,
                    help="gate: allowed retrace + lru_evict_rebuild "
                         "compiles (default %(default)s)")
    ap.add_argument("--min-events", type=int, default=1,
                    help="gate: minimum compile events for a "
                         "non-vacuous pass (default %(default)s)")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_intermixed_args(argv)

    ledgers = args.ledgers or [default_capture_log()]
    events = load_compile_events(ledgers)
    rep = summarize(events)

    if args.mode == "report":
        print(f"warmup debt: {rep['events']} compiles, "
              f"{rep['compile_ms_total']} ms total")
        for t in TRIGGERS:
            if rep["by_trigger"].get(t):
                print(f"  {t:>20}: {rep['by_trigger'][t]}")
        for s in rep["shapes"][: args.top]:
            print(f"  shape {s['plan_shape']}: x{s['compiles']} "
                  f"median {s['median_compile_ms']}ms "
                  f"cost {s['warmup_cost']} {s['triggers']} "
                  f"[{(s['sql'] or '')[:60]}]")
        print(json.dumps({"mode": "report", "ok": True,
                          **{k: rep[k] for k in
                             ("events", "compile_ms_total",
                              "by_trigger", "by_site",
                              "post_warmup")},
                          "shapes": len(rep["shapes"])}))
        return 0

    failures: List[str] = []
    if rep["events"] < args.min_events:
        failures.append(
            f"vacuous: only {rep['events']} compile_event record(s) "
            f"(< {args.min_events}) — instrumentation or corpus broken")
    if rep["post_warmup"] > args.max_post_warmup:
        offenders = [s for s in rep["shapes"]
                     if any(t in POST_WARMUP for t in s["triggers"])]
        failures.append(
            f"{rep['post_warmup']} post-warmup compile(s) > allowed "
            f"{args.max_post_warmup}: "
            + "; ".join(f"{s['plan_shape']} {s['triggers']}"
                        for s in offenders[:5]))
    for f in failures:
        print(f"GATE FAIL: {f}", file=sys.stderr)
    print(json.dumps({"mode": "gate", "ok": not failures,
                      "events": rep["events"],
                      "post_warmup": rep["post_warmup"],
                      "max_post_warmup": args.max_post_warmup,
                      "by_trigger": rep["by_trigger"],
                      "failures": failures}))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
