"""Round-7 observability: span tracer, retrace detector, EXPLAIN
ANALYZE, and the unified v2 perf ledger.

Coverage per the issue checklist: span-tree shape + phase completeness
across group-by strategies (dense / compact / sorted-post / scatter
core), retrace detector firing on a forced shape change and staying
silent across warm iterations, an EXPLAIN ANALYZE golden test on SSB
q2.1, and schema validation of every ledger writer (bench captures,
phase profiles, query traces, metrics snapshots) plus the
tools/check_ledger.py gate over a fixture capture log.
"""
import json
import os
import sys

import numpy as np
import pytest

from pinot_tpu.broker import Broker
from pinot_tpu.ops.plan_cache import RetraceDetector, global_plan_cache
from pinot_tpu.query.explain import ANALYZE_COLUMNS
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)
from pinot_tpu.tools import corpus
from pinot_tpu.utils import ledger as uledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_seg_dir(tmp, name, n=4000, seed=0):
    rng = np.random.default_rng(seed)
    cols = {
        "k": rng.choice(["a", "b", "c"], n),
        "g": rng.choice([f"g{i}" for i in range(40)], n),
        "v": rng.integers(0, 100, n).astype(np.int32),
    }
    schema = Schema("obs", [
        FieldSpec("k", DataType.STRING),
        FieldSpec("g", DataType.STRING),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    return SegmentBuilder(schema, TableConfig("obs")).build(
        cols, str(tmp), name)


@pytest.fixture(scope="module")
def broker(tmp_path_factory):
    dm = TableDataManager("obs")
    dm.add_segment_dir(_build_seg_dir(
        tmp_path_factory.mktemp("spans"), "s0"))
    b = Broker()
    b.register_table(dm)
    return b


def _rows_by_name(res):
    return {r[0]: r for r in res.rows}


def _tree_ok(rows):
    ids = {r[1] for r in rows}
    assert all(r[2] == -1 or r[2] in ids for r in rows)


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE: span tree shape, timings, est vs measured selectivity
# ---------------------------------------------------------------------------

def test_explain_analyze_tree_and_timing(broker):
    # timeoutMs: the warm run may pay the compact kernel's cold compile
    # when no earlier test warmed this shape (the fleet-smoke idiom)
    sql = ("EXPLAIN ANALYZE SELECT k, g, SUM(v) FROM obs WHERE v > 10 "
           "GROUP BY k, g OPTION(groupByStrategy=compact, "
           "timeoutMs=60000)")
    broker.query(sql)                       # warm: compile outside timing
    res = broker.query(sql)
    assert res.columns == ANALYZE_COLUMNS
    _tree_ok(res.rows)
    names = [r[0] for r in res.rows]
    for expect in ("query", "planning", "execution", "segment_kernel",
                   "device_execute", "device_transfer", "reduce"):
        assert expect in names, f"missing span {expect!r} in {names}"
    by = _rows_by_name(res)
    root = by["query"]
    children = [r for r in res.rows if r[2] == root[1]]
    total = sum(r[3] for r in children)
    # acceptance gate: phase timings sum to within 10% of wall time
    assert abs(total - root[3]) <= 0.10 * root[3]
    # cost-model decision trace on the planning span
    assert "cost_trace=" in by["planning"][4]
    assert "strategy=compact" in by["planning"][4]
    # cache hit/miss + est vs measured selectivity on the kernel span
    assert "cache=hit" in by["segment_kernel"][4]
    assert "est_sel=" in by["segment_kernel"][4]
    assert "meas_sel=" in by["segment_kernel"][4]
    # warm repeat: the detector asserts zero retraces
    assert "retraces=0" in root[4]
    # the raw tree rides the trace envelope for programmatic consumers
    assert res.trace["spans"]["name"] == "query"


@pytest.mark.parametrize("strategy", ["dense", "compact"])
def test_span_strategy_completeness(broker, strategy):
    sql = (f"EXPLAIN ANALYZE SELECT k, SUM(v), MIN(v) FROM obs "
           f"GROUP BY k OPTION(groupByStrategy={strategy})")
    res = broker.query(sql)
    by = _rows_by_name(res)
    assert f"strategy={strategy}" in by["segment_kernel"][4]
    assert "device_execute" in by and "device_transfer" in by
    _tree_ok(res.rows)






def test_span_scatter_core(broker, monkeypatch):
    # flip the CPU scatter aggregation core: the span tree must stay
    # complete and record the fresh compile (different cache key)
    monkeypatch.setenv("PINOT_CPU_FAST_GROUPBY", "1")
    sql = ("EXPLAIN ANALYZE SELECT k, SUM(v) FROM obs GROUP BY k "
           "OPTION(groupByStrategy=compact)")
    res = broker.query(sql)
    by = _rows_by_name(res)
    assert "segment_kernel" in by and "device_execute" in by
    _tree_ok(res.rows)


def test_span_host_and_kselect(broker):
    res = broker.query("EXPLAIN ANALYZE SELECT k, COUNT(*) FROM obs "
                       "GROUP BY k OPTION(forceHostExecution=true)")
    assert "segment_host" in [r[0] for r in res.rows]
    res = broker.query("EXPLAIN ANALYZE SELECT k, v FROM obs "
                       "ORDER BY v DESC LIMIT 5")
    assert "segment_kselect" in [r[0] for r in res.rows]


def test_phase_vocabulary_shared(broker):
    """utils/phases.py is the ONE phase-name vocabulary: the flat trace
    envelope (utils/trace.py Tracing.phase) and the EXPLAIN ANALYZE span
    tree (utils/spans.py) must agree — no drifted strings."""
    from pinot_tpu.utils import phases as ph
    res = broker.query("SELECT k, SUM(v) FROM obs GROUP BY k "
                       "OPTION(trace=true)")
    assert res.trace is not None
    envelope_phases = set(res.trace["phases"])
    assert envelope_phases <= ph.TRACED_PHASES, envelope_phases
    res2 = broker.query("EXPLAIN ANALYZE SELECT k, SUM(v) FROM obs "
                        "GROUP BY k")
    names = {r[0] for r in res2.rows}
    assert res2.rows[0][0] == ph.QUERY
    # every envelope phase appears as a span of the SAME name
    assert envelope_phases <= names
    for const in (ph.PLANNING, ph.EXECUTION, ph.REDUCE):
        assert const in names


def test_plain_queries_untouched(broker):
    res = broker.query("SELECT COUNT(*) FROM obs")
    assert res.trace is None
    res = broker.query("EXPLAIN PLAN FOR SELECT COUNT(*) FROM obs")
    assert res.columns == ["Operator", "Operator_Id", "Parent_Id"]


# ---------------------------------------------------------------------------
# Retrace detector
# ---------------------------------------------------------------------------

def test_retrace_detector_unit():
    det = RetraceDetector()
    det.begin_query()
    assert det.observe_compile(("plan", 1)) is False   # warmup compile
    assert det.observe_compile(("plan", 1)) is False   # same generation
    det.begin_query()
    assert det.observe_compile(("plan", 2)) is False   # new plan: warmup
    assert det.observe_compile(("plan", 1)) is True    # warm plan retraced
    det.begin_query()
    with det.expected():
        assert det.observe_compile(("plan", 2)) is False  # overflow ladder
    assert det.snapshot() == {"retraces": 1, "expected_recompiles": 1}


def test_retrace_detector_token_dedup():
    """A hybrid query plans two segment lists under ONE query id; the
    second begin_query with the same token must NOT open a new
    generation (its cold compiles are warmup, not retraces)."""
    det = RetraceDetector()
    det.begin_query("q1")
    assert det.observe_compile(("plan", 1)) is False   # offline half
    det.begin_query("q1")                              # realtime half
    assert det.observe_compile(("plan", 1)) is False   # same query: warmup
    det.begin_query("q2")                              # next query
    assert det.observe_compile(("plan", 1)) is True    # now a retrace
    det.begin_query(None)                              # tokenless bumps
    det.begin_query(None)
    assert det.observe_compile(("plan", 2)) is False




def test_retrace_detector_integration(tmp_path):
    dm = TableDataManager("obs")
    dm.add_segment_dir(_build_seg_dir(tmp_path / "a", "s0", n=3000))
    b = Broker()
    b.register_table(dm)
    sql = "SELECT g, SUM(v) FROM obs GROUP BY g"
    b.query(sql)                                   # warmup compile
    r0 = global_plan_cache.detector.retraces
    for _ in range(3):
        b.query(sql)                               # warm iterations
    assert global_plan_cache.detector.retraces == r0
    # forced shape change: same plan structure at a different bucket
    dm.add_segment_dir(_build_seg_dir(tmp_path / "b", "s1", n=20000))
    b.query(sql)
    assert global_plan_cache.detector.retraces > r0
    from pinot_tpu.utils.metrics import global_metrics
    assert global_metrics.snapshot()["counters"].get(
        "plan_cache_retraces", 0) >= 1


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE golden on SSB q2.1
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ssb_broker(tmp_path_factory):
    seg = corpus.build_ssb_segment(1 << 14,
                                   str(tmp_path_factory.mktemp("ssb")))
    dm = TableDataManager("lineorder")
    dm.add_segment(seg)
    b = Broker()
    b.register_table(dm)
    return b


GOLDEN_Q21_SPINE = ["query", "planning", "execution", "segment_kernel",
                    "device_execute", "device_transfer",
                    "extract_partial", "reduce"]


def test_explain_analyze_golden_q21(ssb_broker):
    q21 = next(q for q in corpus.SSB_QUERIES if q[0] == "q2.1")
    sql = ("EXPLAIN ANALYZE "
           + corpus.spec_to_sql(q21[1], q21[2], q21[3])
           + " OPTION(groupByStrategy=compact)")
    ssb_broker.query(sql)                  # warm
    res = ssb_broker.query(sql)
    names = [r[0] for r in res.rows]
    # golden spine: these spans, in this pre-order
    spine = [n for n in names if n in GOLDEN_Q21_SPINE]
    assert spine == GOLDEN_Q21_SPINE
    by = _rows_by_name(res)
    assert "strategy=compact" in by["planning"][4]
    assert "slots_cap=" in by["planning"][4]
    assert "cache=hit" in by["segment_kernel"][4]
    assert "est_sel=" in by["segment_kernel"][4]
    assert "meas_sel=" in by["segment_kernel"][4]
    root = by["query"]
    assert "retraces=0" in root[4]
    children = [r for r in res.rows if r[2] == root[1]]
    assert abs(sum(r[3] for r in children) - root[3]) <= 0.10 * root[3]


# ---------------------------------------------------------------------------
# Unified v2 ledger: schema, writers, check tool
# ---------------------------------------------------------------------------

def test_ledger_make_and_validate():
    rec = uledger.make_record("bench_capture", metric="m", backend="cpu",
                              ok=True, value=1.0, n_rows=10)
    assert rec["v"] == uledger.SCHEMA_VERSION and not \
        uledger.validate_record(rec)
    # unknown field rejected
    with pytest.raises(ValueError, match="unknown fields"):
        uledger.make_record("bench_capture", metric="m", backend="cpu",
                            ok=True, value=1.0, typo_field=1)
    # missing required rejected
    with pytest.raises(ValueError, match="missing required"):
        uledger.make_record("bench_capture", metric="m")
    # unknown kind rejected
    with pytest.raises(ValueError, match="unknown kind"):
        uledger.make_record("nope", metric="m")
    # legacy (pre-v2) lines are grandfathered
    assert uledger.validate_record({"metric": "old", "value": 1}) == []


def test_ledger_reserved_key_kind_rejected():
    """The round-22 collision, generalized: a payload field named
    ``kind`` would rename the record mid-write (hence slo_status's
    ``slo_kind``). Expanded dicts route into **fields thanks to the
    positional-only signature — a clear ValueError, never a
    TypeError."""
    fields = {"kind": "latency", "scope": "t", "objective": 0.99,
              "burn_fast": 0.0, "burn_slow": 0.0,
              "budget_remaining": 1.0, "window_s": 3600, "proc": "p"}
    with pytest.raises(ValueError, match="shadow reserved"):
        uledger.make_record("slo_status", **fields)


def test_ledger_reserved_key_node_rejected():
    """``node`` is fleet provenance, stamped envelope-level by the
    rollup puller — a writer-side payload field must not forge it."""
    with pytest.raises(ValueError, match="shadow reserved"):
        uledger.make_record("metrics_snapshot", counters={},
                            node="forged")


def test_ledger_reserved_key_proc_rejected():
    """``proc`` is admitted only where the kind's contract declares it
    (alert/compile_event/slo_status/incident) — on any other kind it
    shadows the fleet-dedup identity."""
    with pytest.raises(ValueError, match="shadow reserved"):
        uledger.make_record("metrics_snapshot", counters={},
                            proc="1234-abc")
    # a declaring kind still takes it (the AlertManager.fire path)
    rec = uledger.make_record(
        "alert", alert="a", severity="warning", rate_per_min=1.0,
        watermark=1.0, window_s=60, proc="1234-abc")
    assert rec["proc"] == "1234-abc"


def test_ledger_reserved_key_seq_rejected():
    with pytest.raises(ValueError, match="shadow reserved"):
        uledger.make_record("metrics_snapshot", counters={}, seq=7)
    # declared on compile_event: the per-process event counter
    rec = uledger.make_record(
        "compile_event", site="engine", trigger="miss", plan_shape="s",
        key_fp="fp", backend="cpu", lower_ms=1.0, compile_ms=2.0,
        donated=True, proc="1234-abc", seq=7)
    assert rec["seq"] == 7


def test_ledger_reserved_key_ts_string_enforced():
    """``ts`` stays injectable (deterministic emitters pin it) but must
    already be a formatted string — a float would corrupt the
    envelope's ISO-8601 contract."""
    rec = uledger.make_record("metrics_snapshot", counters={},
                              ts="t+1.000s")
    assert rec["ts"] == "t+1.000s"
    with pytest.raises(ValueError, match="ts must be a formatted"):
        uledger.make_record("metrics_snapshot", counters={}, ts=123.4)


def test_ledger_file_validation(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    uledger.append_record(
        uledger.make_record("phase_profile", metric="compact_phase_profile",
                            backend="cpu", qid="q2.1", strategy="compact",
                            t_mask_ms=0.1, t_kernel_ms=1.0), path)
    with open(path, "a") as fh:
        fh.write(json.dumps({"metric": "legacy_line", "value": 3}) + "\n")
    res = uledger.validate_file(path)
    assert res == {"lines": 2, "v2": 1, "legacy": 1,
                   "kinds": {"phase_profile": 1}, "errors": []}
    with open(path, "a") as fh:
        fh.write(json.dumps({"v": 2, "ts": "t", "kind": "phase_profile",
                             "metric": "m", "backend": "cpu",
                             "qid": "q", "strategy": "dense",
                             "bogus": 1}) + "\n")
        fh.write("not json\n")
    res = uledger.validate_file(path)
    assert len(res["errors"]) == 2
    # writer-side enforcement
    with pytest.raises(ValueError):
        uledger.append_record({"v": 2, "ts": "t", "kind": "phase_profile"},
                              path)


def test_explain_analyze_ledger_trace(broker, tmp_path):
    path = str(tmp_path / "trace.jsonl")
    broker.query("EXPLAIN ANALYZE SELECT COUNT(*) FROM obs "
                 f"OPTION(ledgerTrace=true, ledgerPath='{path}')")
    res = uledger.validate_file(path)
    assert res["v2"] == 1 and not res["errors"]
    rec = json.loads(open(path).read())
    assert rec["kind"] == "query_trace"
    assert rec["root"]["name"] == "query"
    assert "EXPLAIN ANALYZE" in rec["sql"]


def test_ledger_metrics_sink(tmp_path):
    from pinot_tpu.utils.metrics import MetricsRegistry
    from pinot_tpu.utils.metrics_sinks import LedgerSink
    reg = MetricsRegistry()
    reg.count("served", 3)
    path = str(tmp_path / "m.jsonl")
    LedgerSink(path).emit(reg.snapshot())
    res = uledger.validate_file(path)
    assert res["v2"] == 1 and not res["errors"]


def test_check_ledger_tool_fixture_file():
    """Tier-1 gate: a fixture capture log (one line per record kind the
    benches write, plus a grandfathered pre-v2 line) validates. The
    checkout-root PERF_LEDGER.jsonl is the driver's record — no test or
    tool of this program opens it."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_ledger
    assert check_ledger.main([os.path.join(
        REPO, "tests", "resources", "capture_log_fixture.jsonl")]) == 0


def test_check_ledger_tool_rejects_bad(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_ledger
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 2, "ts": "t", "kind": "query_trace"}\n')
    assert check_ledger.main([str(bad)]) == 1
    assert "missing required" in capsys.readouterr().out
    strict = tmp_path / "legacy.jsonl"
    strict.write_text('{"metric": "old"}\n')
    assert check_ledger.main([str(strict)]) == 0
    assert check_ledger.main([str(strict), "--strict"]) == 1


# ---------------------------------------------------------------------------
# Engine-wide metrics export
# ---------------------------------------------------------------------------

def test_plan_cache_counters_in_global_metrics(broker):
    from pinot_tpu.utils.metrics import global_metrics
    before = global_metrics.snapshot()["counters"]
    broker.query("SELECT g, SUM(v) FROM obs GROUP BY g")
    broker.query("SELECT g, SUM(v) FROM obs GROUP BY g")
    snap = global_metrics.snapshot()["counters"]
    assert snap.get("plan_cache_hits", 0) > before.get("plan_cache_hits", 0)
    assert "pinot_tpu_plan_cache_hits_total" in global_metrics.prometheus()


def test_kill_counters_in_global_metrics():
    from pinot_tpu.engine.accounting import ResourceAccountant
    from pinot_tpu.utils.metrics import global_metrics
    before = global_metrics.snapshot()["counters"].get("queries_killed", 0)
    acc = ResourceAccountant()
    acc.register("qk1")
    acc.kill("qk1", "test kill")
    assert global_metrics.snapshot()["counters"]["queries_killed"] == \
        before + 1
