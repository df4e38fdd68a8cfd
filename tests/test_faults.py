"""Deterministic chaos: fault injection (utils/faults.py) + the
deadline-aware partial-result scatter-gather (cluster/broker_node.py).

Contract under test (ISSUE 4 acceptance):
- same seed => identical outcome twice (decision streams are pure in
  (seed, point, key, hit));
- a seeded fault plan that kills a server mid-scatter fails over and
  returns byte-identical results to the fault-free run;
- allowPartialResults=true with all replicas of a segment down returns
  partialResult=true, populated exceptions[] and
  numServersResponded < numServersQueried;
- deadline exhaustion mid-scatter fails (default) / degrades (partial);
- an injected accountant OOM kill is survived by the next query;
- a straggling server's segments are hedged to a healthy replica.
"""
import itertools
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from pinot_tpu.broker.routing import make_selector  # noqa: E402
from pinot_tpu.cluster import (BrokerNode, Controller,  # noqa: E402
                               ServerNode)
from pinot_tpu.cluster.broker_node import (ERR_BROKER_TIMEOUT,  # noqa: E402
                                           FailureDetector)
from pinot_tpu.cluster.http_util import http_json  # noqa: E402
from pinot_tpu.segment import SegmentBuilder  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, FieldType,  # noqa: E402
                           Schema, TableConfig)
from pinot_tpu.tools import corpus  # noqa: E402
from pinot_tpu.utils import faults  # noqa: E402
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

N_SEGMENTS = 4
ROWS = 400


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.clear()
    yield
    faults.clear()


def _counter(name: str) -> int:
    return global_metrics.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# registry units: grammar + determinism
# ---------------------------------------------------------------------------

def test_plan_grammar():
    p = faults.FaultPlan.parse(
        "seed=42; rpc.drop: match=/query/bin, p=0.5, times=1; "
        "segment.slow: delay_ms=200, after=1; "
        "rpc.http_error: http_status=429")
    assert p.seed == 42
    assert [s.point for s in p.specs] == \
        ["rpc.drop", "segment.slow", "rpc.http_error"]
    assert p.specs[0].prob == 0.5 and p.specs[0].times == 1
    assert p.specs[1].delay_ms == 200.0 and p.specs[1].after == 1
    assert p.specs[2].http_status == 429
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("no.such.point: p=1")
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("rpc.drop: nope=1")


def test_same_seed_same_decisions():
    def stream(seed):
        p = faults.FaultPlan.parse(f"seed={seed}; rpc.drop: p=0.4")
        return [p.decide("rpc.drop", "k") is not None
                for _ in range(100)]
    a, b = stream(7), stream(7)
    assert a == b
    assert any(a) and not all(a)            # p=0.4 actually mixes
    assert stream(8) != a                   # seed matters


def test_per_key_decision_isolation():
    """Interleaving order across keys cannot perturb a key's stream."""
    def per_key(order):
        p = faults.FaultPlan.parse("seed=3; rpc.drop: p=0.5")
        out = {"a": [], "b": []}
        for k in order:
            out[k].append(p.decide("rpc.drop", k) is not None)
        return out
    interleaved = per_key(["a", "b"] * 20)
    blocked = per_key(["a"] * 20 + ["b"] * 20)
    assert interleaved == blocked


def test_after_and_times_windows():
    p = faults.FaultPlan.parse("seed=1; rpc.drop: after=2, times=2")
    hits = [p.decide("rpc.drop", "k") is not None for _ in range(6)]
    assert hits == [False, False, True, True, False, False]
    assert p.fired_summary() == [("rpc.drop", "k", 2), ("rpc.drop", "k", 3)]
    # the fire budget is per site key (a shared budget would be spent by
    # whichever thread won the race, breaking same-seed determinism)
    hits2 = [p.decide("rpc.drop", "k2") is not None for _ in range(6)]
    assert hits2 == [False, False, True, True, False, False]


def test_per_query_stream_keying():
    """Round-16 rekeying: a thread executing on behalf of a registered
    query draws from its own (query-id, site-key) stream — hit/fire
    windows and ``match`` are per query, so another query's (or
    no-context) traffic at the same site cannot perturb them."""
    from pinot_tpu.engine.accounting import global_accountant
    p = faults.FaultPlan.parse("seed=2; rpc.drop: times=1")
    # no query context: one shared per-site stream (pre-round-16 shape)
    assert p.decide("rpc.drop", "k") is not None
    assert p.decide("rpc.drop", "k") is None        # site budget spent
    # under a query context the same site is a FRESH stream per query
    global_accountant.register("qa")
    try:
        assert p.decide("rpc.drop", "k") is not None
        assert p.decide("rpc.drop", "k") is None    # qa's budget spent
    finally:
        global_accountant.unregister("qa")
    global_accountant.register("qb")
    try:
        assert p.decide("rpc.drop", "k") is not None  # qb unaffected
        # the fired log carries the owning query; the summary stays
        # site-keyed with per-stream hit indices (cross-run comparable
        # even when query ids are random)
        assert [f.get("q") for f in p.fired] == [None, "qa", "qb"]
        assert p.fired_summary() == [("rpc.drop", "k", 0)] * 3
        # match tests the composite stream name: pin to one named query
        p2 = faults.FaultPlan.parse("seed=2; rpc.drop: match=qb|")
        assert p2.decide("rpc.drop", "k") is not None
    finally:
        global_accountant.unregister("qb")
    assert p2.decide("rpc.drop", "k") is None       # no context: no match


def test_inactive_is_noop():
    assert not faults.active()
    faults.fault_point("rpc.drop", "anything")      # must not raise
    assert faults.fault_fires("device.overflow") is False
    data = b"PWR1" + b"x" * 16
    assert faults.corrupt_bytes("wire.corrupt", "k", data) == data


def test_install_from_env(monkeypatch):
    monkeypatch.setenv("PINOT_FAULTS", "seed=5; rpc.delay: delay_ms=1")
    plan = faults.install_from_env()
    assert plan is not None and faults.active()
    assert plan.seed == 5
    t0 = time.perf_counter()
    faults.fault_point("rpc.delay", "k")
    assert time.perf_counter() - t0 >= 0.001
    faults.clear()


def test_fault_point_raises_transport_shapes():
    faults.install("rpc.drop: match=dropme; "
                   "rpc.http_error: match=500me, http_status=418")
    with pytest.raises(urllib.error.URLError):
        faults.fault_point("rpc.drop", "dropme")
    with pytest.raises(urllib.error.HTTPError) as ei:
        faults.fault_point("rpc.http_error", "500me")
    assert ei.value.code == 418
    faults.fault_point("rpc.drop", "unmatched")     # filter holds


def test_corrupt_bytes_breaks_frame_magic():
    from pinot_tpu.engine.datablock import (decode_wire_frame,
                                            encode_wire_frame)
    faults.install("wire.corrupt: times=1")
    frame = encode_wire_frame({"segmentsQueried": 1}, [])
    bad = faults.corrupt_bytes("wire.corrupt", "srv", frame)
    assert bad != frame
    with pytest.raises(ValueError):
        decode_wire_frame(bad)
    # times=1 spent: the next frame passes through untouched
    assert faults.corrupt_bytes("wire.corrupt", "srv", frame) == frame


def test_adaptive_selector_estimate():
    sel = make_selector("adaptive")
    assert sel.estimate_ms("s0") is None
    sel.record_start("s0")
    sel.record_end("s0", 40.0)
    assert sel.estimate_ms("s0") == pytest.approx(40.0)


# ---------------------------------------------------------------------------
# cluster fixture: sales (replication 2) + sales_r1 (replication 1)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("chaos")
    ctrl = Controller(str(tmp / "ctrl"), heartbeat_timeout=30.0,
                      reconcile_interval=0.2)
    servers = [ServerNode(f"server_{i}", ctrl.url, poll_interval=0.1)
               for i in range(2)]
    broker = BrokerNode(ctrl.url, routing_refresh=0.1)

    rng = np.random.default_rng(11)
    data = {"region": [], "amount": []}
    for table, replication in (("sales", 2), ("sales_r1", 1)):
        schema = Schema(table, [
            FieldSpec("region", DataType.STRING),
            FieldSpec("amount", DataType.INT, FieldType.METRIC),
        ])
        builder = SegmentBuilder(schema, TableConfig(table))
        ctrl.add_table(table, schema.to_dict(), replication=replication)
        for i in range(N_SEGMENTS):
            cols = {
                "region": rng.choice(["east", "west", "north"], ROWS),
                "amount": rng.integers(0, 1000, ROWS).astype(np.int32),
            }
            d = builder.build(cols, str(tmp / "segments" / table),
                              f"{table}_seg_{i}")
            ctrl.add_segment(table, f"{table}_seg_{i}", d)
            if table == "sales":
                data["region"].append(cols["region"])
                data["amount"].append(cols["amount"])
    v = ctrl.routing_snapshot()["version"]
    for s in servers:
        assert s.wait_for_version(v)
    assert broker.wait_for_version(v)
    data = {k: np.concatenate(v) for k, v in data.items()}
    yield ctrl, servers, broker, data
    broker.stop()
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass
    ctrl.stop()


def _reset_broker(broker):
    """Identical starting state for determinism reruns: fresh failure
    detector, selector and round-robin cursor."""
    broker._failures = FailureDetector()
    broker._selector = make_selector("balanced")
    broker._rr = itertools.count(1)


def _q(broker, sql, timeout=120.0):
    # generous CLIENT timeout (first query pays XLA compile); the
    # query's own budget is OPTION(timeoutMs)
    return http_json("POST", f"{broker.url}/query/sql", {"sql": sql},
                     timeout=timeout)


GROUP_SQL = ("SELECT region, SUM(amount), COUNT(*) FROM sales "
             "GROUP BY region ORDER BY region")


def test_failover_exact_and_seed_deterministic(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    baseline = _q(broker, GROUP_SQL)["resultTable"]["rows"]
    expected = sorted(
        [r, int(data["amount"][data["region"] == r].sum()),
         int((data["region"] == r).sum())]
        for r in ["east", "north", "west"])
    assert baseline == expected

    def chaos_run():
        _reset_broker(broker)
        plan = faults.install(
            f"seed=9; rpc.drop: match=:{servers[0].port}/query/bin, "
            "times=1")
        try:
            rows = _q(broker, GROUP_SQL)["resultTable"]["rows"]
        finally:
            faults.clear()
        return rows, plan.fired_summary()

    f0 = _counter("scatter_failovers")
    rows_a, fired_a = chaos_run()
    rows_b, fired_b = chaos_run()
    # failover exactness: byte-identical to the fault-free run
    assert rows_a == baseline and rows_b == baseline
    # determinism: same seed, same starting state => identical faults
    assert fired_a == fired_b and len(fired_a) == 1
    assert _counter("scatter_failovers") >= f0 + 2


def test_wire_corruption_fails_over(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    baseline = _q(broker, GROUP_SQL)["resultTable"]["rows"]
    plan = faults.install("seed=1; wire.corrupt: match=server_0, times=1")
    rows = _q(broker, GROUP_SQL)["resultTable"]["rows"]
    faults.clear()
    assert rows == baseline
    assert plan.fired_summary() == [("wire.corrupt", "server_0", 0)]


def test_partial_result_metadata(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    total = _q(broker, "SELECT COUNT(*) FROM sales_r1"
               )["resultTable"]["rows"][0][0]
    assert total == N_SEGMENTS * ROWS

    _reset_broker(broker)
    faults.install(f"seed=2; rpc.drop: match=:{servers[0].port}"
                   "/query/bin")
    # default mode: whole-query failure (replication 1 — no replica left)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _q(broker, "SELECT COUNT(*) FROM sales_r1")
    assert ei.value.code == 400

    _reset_broker(broker)
    resp = _q(broker, "SELECT COUNT(*) FROM sales_r1 "
              "OPTION(allowPartialResults=true)")
    faults.clear()
    assert resp["partialResult"] is True
    assert resp["numServersResponded"] < resp["numServersQueried"]
    assert resp["numServersQueried"] == 2
    assert len(resp["exceptions"]) >= 1
    from pinot_tpu.cluster.broker_node import ERR_SERVER_NOT_RESPONDED
    assert any("no replica left" in e["message"]
               and e["errorCode"] == ERR_SERVER_NOT_RESPONDED
               for e in resp["exceptions"])
    partial_count = resp["resultTable"]["rows"][0][0]
    assert 0 < partial_count < total  # the surviving servers' docs only


def test_deadline_exhaustion_mid_scatter(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    faults.install("seed=3; segment.slow: match=server_, delay_ms=600")
    t0 = time.perf_counter()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _q(broker, "SELECT SUM(amount) FROM sales OPTION(timeoutMs=250)")
    elapsed = time.perf_counter() - t0
    body = ei.value.read().decode()
    assert ei.value.code == 400
    assert "deadline" in body.lower() or "timed out" in body.lower()
    assert elapsed < 5.0  # budget enforced, not the 10s http default

    # partial mode degrades instead of failing
    _reset_broker(broker)
    resp = _q(broker, "SELECT SUM(amount) FROM sales "
              "OPTION(timeoutMs=250,allowPartialResults=true)")
    faults.clear()
    assert resp["partialResult"] is True
    assert any(e["errorCode"] == ERR_BROKER_TIMEOUT
               for e in resp["exceptions"])
    # let the straggling server threads drain before the next test
    time.sleep(0.7)


def test_oom_kill_recovery(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    k0 = _counter("queries_killed_oom")
    # per-query fault streams (round 16): times=1 bounds the kill PER
    # QUERY — every query the plan matches dies once at its own sample
    # point while the plan is armed (the old process-global stream
    # spent the budget on the first query only)
    faults.install("seed=4; accounting.oom_kill: times=1")
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _q(broker, "SELECT SUM(amount) FROM sales")
        body = ei.value.read().decode()
        assert "heap pressure" in body
        assert _counter("queries_killed_oom") >= k0 + 1
        # an application-level kill is NOT a health signal: no
        # failover, servers stay healthy
        assert all(broker._failures.healthy(s.instance_id)
                   for s in servers)
    finally:
        faults.clear()
    # plan cleared: nothing latched — the very next query works
    resp = _q(broker, "SELECT SUM(amount) FROM sales")
    assert resp["resultTable"]["rows"] == [[int(data["amount"].sum())]]


def test_hedged_redispatch_of_straggler(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    baseline = _q(broker, GROUP_SQL)["resultTable"]["rows"]
    h0 = _counter("scatter_hedges")
    faults.install("seed=5; segment.slow: match=server_0, delay_ms=900")
    t0 = time.perf_counter()
    resp = _q(broker, GROUP_SQL +
              " OPTION(hedgeMs=80,timeoutMs=300000)")
    elapsed = time.perf_counter() - t0
    faults.clear()
    assert resp["resultTable"]["rows"] == baseline
    assert _counter("scatter_hedges") > h0
    # the hedge answered: the gather did not wait out the 900ms sleep
    # (generous headroom below the injected delay — CI-load tolerant)
    assert elapsed < 0.75
    # hedge targets count as queried, so responded stays a subset
    assert 1 <= resp["numServersResponded"] <= resp["numServersQueried"]
    time.sleep(1.0)  # drain the abandoned straggler call


def test_deadline_forwarded_to_server(cluster):
    """The server clamps its accountant deadline to the broker's
    forwarded remaining budget (min(own timeoutMs, deadlineMs))."""
    ctrl, servers, broker, data = cluster
    faults.install("seed=6; segment.slow: match=server_0, delay_ms=300")
    with pytest.raises(urllib.error.HTTPError) as ei:
        http_json("POST", f"{servers[0].url}/query",
                  {"sql": "SELECT SUM(amount) FROM sales",
                   "deadlineMs": 50})
    faults.clear()
    body = ei.value.read().decode()
    assert "deadline exceeded" in body


def test_scatter_health_export(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    faults.install(f"seed=7; rpc.drop: match=:{servers[0].port}"
                   "/query/bin")
    with pytest.raises(urllib.error.HTTPError):
        _q(broker, "SELECT COUNT(*) FROM sales_r1")
    faults.clear()
    m = http_json("GET", f"{broker.url}/metrics")
    assert m["servers"]["server_0"]["consecutiveFailures"] >= 1
    assert m["unhealthyServers"] >= 1 and m["knownServers"] >= 2
    for k in ("scatter_failovers", "scatter_hedges",
              "scatter_partial_responses", "scatter_server_errors"):
        assert k in m["counters"]
    with urllib.request.urlopen(f"{broker.url}/ui") as r:
        assert b"scatter health" in r.read()
    prom = urllib.request.urlopen(f"{broker.url}/metrics/prometheus")
    assert b"pinot_tpu_" in prom.read()


def test_segment_shortfall_fails_over(cluster, monkeypatch):
    """A server mid-(re)load after heartbeat churn answers 200 but runs
    fewer segments than asked; the broker must fail over instead of
    reducing over the silent subset (chaos-soak regression)."""
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    baseline = _q(broker, GROUP_SQL)["resultTable"]["rows"]
    orig = servers[0].execute_bin

    def shortfall(sql, segment_names=None, deadline_ms=None,
                  trace_ctx=None, workload=None):
        if segment_names and len(segment_names) > 1:
            segment_names = segment_names[:-1]  # silently skip one
        return orig(sql, segment_names, deadline_ms, trace_ctx,
                    workload)

    monkeypatch.setattr(servers[0], "execute_bin", shortfall)
    # run across several round-robin positions so server_0 is picked
    # with >1 segment at least once; every answer must stay exact
    for _ in range(6):
        _reset_broker(broker)
        rows = _q(broker, GROUP_SQL)["resultTable"]["rows"]
        assert rows == baseline


def test_invalid_hedge_option_is_400(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _q(broker, GROUP_SQL + " OPTION(hedgeMs=abc)")
    assert ei.value.code == 400
    assert "invalid hedgeMs" in ei.value.read().decode()


def test_setop_propagates_partial_metadata(cluster):
    """combine_setop rebuilds the table from rows; the compound must
    still carry a partial branch's partialResult/exceptions[] rather
    than presenting incomplete data as complete."""
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    faults.install(f"seed=12; rpc.drop: match=:{servers[0].port}"
                   "/query/bin")
    resp = _q(broker, "SELECT region FROM sales_r1 UNION "
              "SELECT region FROM sales_r1 WHERE amount > 500 "
              "OPTION(allowPartialResults=true)")
    faults.clear()
    assert resp["partialResult"] is True
    assert resp["exceptions"]
    assert resp["numServersResponded"] < resp["numServersQueried"]


def test_server_config_fault_plan_lifecycle(cluster):
    """A node's fault.plan arms the process-global registry; stop()
    disarms it (unless another plan replaced it meanwhile)."""
    ctrl, servers, broker, data = cluster
    assert not faults.active()
    node = ServerNode("chaos_node", ctrl.url, poll_interval=0.2,
                      scheduler_config={
                          "fault.plan": "seed=1; rpc.delay: delay_ms=1"})
    try:
        assert faults.active()
        assert faults.current_plan().specs[0].point == "rpc.delay"
    finally:
        node.stop()
    assert not faults.active()


def test_explain_survives_fault_and_deadline(cluster):
    ctrl, servers, broker, data = cluster
    _reset_broker(broker)
    faults.install(f"seed=8; rpc.drop: match=:{servers[0].port}/query, "
                   "times=1")
    resp = _q(broker, "EXPLAIN SELECT SUM(amount) FROM sales "
              "OPTION(timeoutMs=30000)")
    faults.clear()
    cols = resp["resultTable"]["dataSchema"]["columnNames"]
    assert cols == ["Operator", "Operator_Id", "Parent_Id"]


# ---------------------------------------------------------------------------
# device.overflow: forced retry ladder is result-identical (in-process)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ssb_broker(tmp_path_factory):
    seg = corpus.build_ssb_segment(1 << 12,
                                   str(tmp_path_factory.mktemp("ssb_flt")))
    from pinot_tpu.broker import Broker
    from pinot_tpu.server import TableDataManager
    dm = TableDataManager("lineorder")
    dm.add_segment(seg)
    broker = Broker()
    broker.register_table(dm)
    return broker


def test_device_overflow_forced_retry_identical(ssb_broker):
    by_id = {q[0]: q for q in corpus.SSB_QUERIES}
    _, preds, vexpr, gcols = by_id["q2.1"]
    sql = corpus.spec_to_sql(preds, vexpr, gcols) + \
        " OPTION(timeoutMs=300000,groupByStrategy=compact)"
    baseline = corpus.digest(ssb_broker.query(sql).rows)
    r0 = _counter("compact_overflow_retries")
    plan = faults.install("seed=11; device.overflow: times=1")
    rows = ssb_broker.query(sql).rows
    faults.clear()
    assert corpus.digest(rows) == baseline
    assert len(plan.fired) == 1
    assert _counter("compact_overflow_retries") == r0 + 1


# ---------------------------------------------------------------------------
# tier-1 chaos smoke CLI + slow randomized soak over the SSB corpus
# ---------------------------------------------------------------------------

def test_chaos_smoke_cli(capsys):
    import chaos_smoke
    assert chaos_smoke.main(["--rows", "512",
                             "--queries", "q1.1,q4.1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = __import__("json").loads(out[-1])
    # 3 query-plane fault plans + the round-20 compile-attribution
    # parity plan + the round-14 fleet-rollup pull kill
    assert summary["ok"] and summary["plans"] == 5
    assert summary["rollup_faults_fired"] >= 1
    assert summary["fleet_ledger_kinds"].get("fleet_rollup", 0) >= 1
    # compile-plane gate (ISSUE 15): every warmed plan landed >=1
    # validated compile_event (shape-hashed) during the baseline pass
    assert summary["compile_events"] >= 2
    assert summary["compile_shapes"] >= 2


def test_chaos_smoke_vector_cli(capsys):
    """Round-19 vector gate (ISSUE 14): seeded VECTOR_SIMILARITY top-k
    queries over a 2-server cluster fail over byte-identically under
    rpc.drop (same-seed runs fire identical streams), recover
    byte-identical top-k from a mid-query tier.evict demotion of the
    vector pool, reject bad-dim calls as structured 400s, and leave
    the vector devmem pool reconciled to the byte."""
    import chaos_smoke
    assert chaos_smoke.main(["--vector", "--rows", "1024"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = __import__("json").loads(out[-1])
    assert summary["ok"] and summary["mode"] == "vector"
    assert summary["faults_fired"] >= 2
    assert summary["vector_pool"]["tracked"] \
        == summary["vector_pool"]["actual"]


def test_chaos_smoke_rate_cli(capsys):
    """Round-16 rate gate (ISSUE 11): sustained multi-partition ingest
    concurrent with queries under the full armed ingest fault plan —
    final state byte-exact vs the oracle, a validated ingest_bench
    record + per-table ingest_stats rows, and the freshness-gate
    ratchet green against the checked-in baseline, with micro-batching
    at its (on) process default."""
    import chaos_smoke
    assert chaos_smoke.main(["--rate", "--rows", "400",
                             "--gate-iters", "2"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = __import__("json").loads(out[-1])
    assert summary["ok"] and summary["mode"] == "rate"
    assert summary["oracle_ok"] is True
    assert summary["faults_fired"] >= 1
    assert summary["queries"] >= 1 and summary["query_errors"] == 0
    assert summary["ledger_kinds"].get("ingest_bench", 0) >= 1
    assert summary["ledger_kinds"].get("ingest_stats", 0) >= 2
    assert summary["freshness_gate_exit"] == 0
    assert summary["batched"] is True  # default-on, armed during chaos


@pytest.mark.slow
def test_chaos_soak_ssb(tmp_path):
    """Randomized (but seeded) chaos over the SSB corpus: every answer
    is either byte-identical to the fault-free digest or an honest
    partial (partialResult + exceptions); the cluster recovers."""
    import chaos_smoke
    ctrl, servers, broker, stop = chaos_smoke.build_ssb_cluster(
        str(tmp_path), rows=4096)
    try:
        queries = chaos_smoke.smoke_queries()
        opt = (" OPTION(timeoutMs=30000,allowPartialResults=true)")
        baseline = {}
        for qid, sql in queries:
            baseline[qid] = chaos_smoke.digest(
                _q(broker, sql + " OPTION(timeoutMs=300000)"))
        for seed in (101, 202, 303):
            faults.install(
                f"seed={seed}; "
                "rpc.drop: match=/query/bin, p=0.25; "
                "rpc.delay: match=/query/bin, p=0.25, delay_ms=30; "
                "wire.corrupt: p=0.15")
            try:
                for qid, sql in queries:
                    resp = _q(broker, sql + opt)
                    if resp.get("partialResult"):
                        assert resp["exceptions"]
                    else:
                        assert chaos_smoke.digest(resp) == baseline[qid], \
                            f"seed {seed} {qid}: non-partial mismatch"
            finally:
                faults.clear()
        # recovery: backoffs heal, digests exact again
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            got = {qid: chaos_smoke.digest(
                _q(broker, sql + " OPTION(timeoutMs=300000)"))
                for qid, sql in queries}
            if got == baseline:
                break
            time.sleep(0.5)
        assert got == baseline
    finally:
        faults.clear()
        stop()
