"""Trace / metrics / timeout / EXPLAIN tests (BuiltInTracer + phase timer
+ ExplainPlanQueriesTest analogs)."""
import numpy as np
import pytest

from pinot_tpu.broker import Broker, QueryTimeoutError
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)
from pinot_tpu.utils.metrics import global_metrics


@pytest.fixture(scope="module")
def broker(tmp_path_factory):
    rng = np.random.default_rng(0)
    n = 2000
    cols = {
        "k": rng.choice(["a", "b", "c"], n),
        "v": rng.integers(0, 100, n).astype(np.int32),
    }
    schema = Schema("obs", [
        FieldSpec("k", DataType.STRING),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    d = SegmentBuilder(schema, TableConfig("obs")).build(
        cols, str(tmp_path_factory.mktemp("obs")), "s0")
    dm = TableDataManager("obs")
    dm.add_segment_dir(d)
    b = Broker()
    b.register_table(dm)
    return b


def test_trace_phases_and_counters(broker):
    res = broker.query("SELECT k, SUM(v) FROM obs GROUP BY k "
                       "OPTION(trace=true)")
    assert res.trace is not None
    assert {"planning", "execution", "reduce"} <= set(res.trace["phases"])
    assert res.trace["counters"]["numSegmentsQueried"] == 1
    assert res.trace["counters"]["numDocsScanned"] == 2000


def test_trace_off_by_default(broker):
    res = broker.query("SELECT COUNT(*) FROM obs")
    assert res.trace is None


def test_metrics_registry(broker):
    before = global_metrics.snapshot()["counters"].get("broker_queries", 0)
    broker.query("SELECT COUNT(*) FROM obs")
    snap = global_metrics.snapshot()
    assert snap["counters"]["broker_queries"] == before + 1
    assert "pinot_tpu_broker_queries_total" in global_metrics.prometheus()


def test_snapshot_is_counters_and_gauges_and_old_timers_validate(tmp_path):
    """The registry keeps no wall-ms samples (the phases' counters do
    that job); a capture from before still validates."""
    from pinot_tpu.utils import ledger as uledger
    from pinot_tpu.utils.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.count("c", 2)
    reg.gauge("g", 1.5)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "gauge_age_s"}
    assert reg.prometheus().splitlines() == ["pinot_tpu_c_total 2",
                                             "pinot_tpu_g 1.5"]
    path = str(tmp_path / "old.jsonl")
    uledger.append_record(uledger.make_record(
        "metrics_snapshot", counters={"c": 2},
        timers={"broker_query": {"count": 1, "p50": 1.0, "p99": 1.0,
                                 "max": 1.0}}), path)
    res = uledger.validate_file(path)
    assert res["v2"] == 1 and not res["errors"]


def test_timeout_raises(broker):
    with pytest.raises(QueryTimeoutError):
        broker.query("SELECT SUM(v) FROM obs OPTION(timeoutMs=0)")


def test_explain_plan(broker):
    res = broker.query("EXPLAIN PLAN FOR SELECT k, SUM(v), COUNT(*) FROM obs "
                       "WHERE v > 10 GROUP BY k ORDER BY k")
    assert res.columns == ["Operator", "Operator_Id", "Parent_Id"]
    ops = [r[0] for r in res.rows]
    assert any(o.startswith("BROKER_REDUCE") for o in ops)
    assert any(o.startswith("TPU_KERNEL") for o in ops)
    assert any("GROUP_BY_ONEHOT_DOT" in o for o in ops)
    assert any("FILTER_MASK:CMP" in o for o in ops)
    assert any(o == "AGGREGATE:SUM(v)" for o in ops)
    # parent ids form a tree rooted at -1
    ids = {r[1] for r in res.rows}
    assert all(r[2] == -1 or r[2] in ids for r in res.rows)


def test_explain_shows_pruning(broker):
    res = broker.query("EXPLAIN SELECT COUNT(*) FROM obs WHERE k = 'zzz'")
    ops = [r[0] for r in res.rows]
    assert any("SEGMENT_PRUNED" in o for o in ops)


def test_plan_and_for_remain_valid_identifiers(tmp_path):
    """Regression: EXPLAIN keywords must stay contextual."""
    from pinot_tpu.segment import SegmentBuilder
    schema = Schema("subs", [
        FieldSpec("plan", DataType.STRING),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    d = SegmentBuilder(schema, TableConfig("subs")).build(
        [{"plan": "pro", "v": 1}, {"plan": "free", "v": 2}],
        str(tmp_path), "s0")
    dm = TableDataManager("subs")
    dm.add_segment_dir(d)
    b = Broker()
    b.register_table(dm)
    res = b.query("SELECT plan, COUNT(*) FROM subs GROUP BY plan "
                  "ORDER BY plan")
    assert [tuple(r) for r in res.rows] == [("free", 1), ("pro", 1)]


def test_explain_join_does_not_execute(broker, tmp_path):
    from pinot_tpu.segment import SegmentBuilder
    schema = Schema("dim", [FieldSpec("k", DataType.STRING)])
    d = SegmentBuilder(schema, TableConfig("dim")).build(
        [{"k": "a"}], str(tmp_path), "s0")
    dm = TableDataManager("dim")
    dm.add_segment_dir(d)
    broker.register_table(dm)
    res = broker.query("EXPLAIN SELECT COUNT(*) FROM obs o "
                       "JOIN dim d ON o.k = d.k")
    ops = [r[0] for r in res.rows]
    assert any(o.startswith("HASH_JOIN") for o in ops)
    assert sum(1 for o in ops if o.startswith("LEAF_SCAN")) == 2


# ---------------------------------------------------------------------------
# pluggable metrics sinks (pinot-plugins/pinot-metrics analog)
# ---------------------------------------------------------------------------

def test_statsd_sink_emits_deltas_over_udp():
    import socket
    from pinot_tpu.utils.metrics import MetricsRegistry
    from pinot_tpu.utils.metrics_sinks import StatsdSink
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(2.0)
    port = rx.getsockname()[1]
    reg = MetricsRegistry()
    reg.count("queries", 5)
    reg.gauge("segments", 7)
    sink = StatsdSink("127.0.0.1", port)
    sink.emit(reg.snapshot())
    got = set()
    for _ in range(2):
        got.add(rx.recv(1024).decode())
    assert "pinot_tpu.queries:5|c" in got
    assert "pinot_tpu.segments:7|g" in got
    # second flush with no new counts emits no counter delta
    reg.count("queries", 2)
    sink.emit(reg.snapshot())
    assert rx.recv(1024).decode() == "pinot_tpu.queries:2|c"
    sink.close()
    rx.close()


def test_prometheus_file_sink_atomic(tmp_path):
    from pinot_tpu.utils.metrics import MetricsRegistry
    from pinot_tpu.utils.metrics_sinks import PrometheusFileSink
    reg = MetricsRegistry()
    reg.count("served", 3)
    path = str(tmp_path / "pinot.prom")
    sink = PrometheusFileSink(path)
    sink.emit(reg.snapshot())
    text = open(path).read()
    assert "pinot_tpu_served_total 3" in text


def test_metrics_flush_task_and_plugin_config():
    from pinot_tpu.utils.metrics import MetricsRegistry
    from pinot_tpu.utils.metrics_sinks import (MetricsFlushTask,
                                               sinks_from_config)
    seen = []
    reg = MetricsRegistry()
    reg.count("x", 1)
    sinks = sinks_from_config([{"type": "callback",
                                "fn": lambda s: seen.append(s)}])
    task = MetricsFlushTask(sinks, interval_s=0.01, registry=reg)
    task.run_once()
    assert seen and seen[0]["counters"]["x"] == 1
