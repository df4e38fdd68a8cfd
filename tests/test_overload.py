"""ISSUE 12: overload-resilient serving suite.

Coverage per the issue checklist: the pure shed ladder + deterministic
retryAfterMs, governor watermarks/hysteresis/pins + rung-1 speculative
shedding (hedge off, trace off, micro-batch window widened), per-tenant
budgets (in-flight, post-paid cpu/bytes via the accountant fence, retry
amplification guard), tier-aware OOM-kill ordering, structured 429
rendering on both planes (OverloadShedError + the SchedulerRejectedError
satellite), live-broker quota division, replay_bench ledger contract,
traffic-replay plan purity, fleet-rollup shed trending, the /metrics +
prometheus export, and the tier-1 ``chaos_smoke --overload`` closed-loop
gate.
"""
import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

from pinot_tpu.broker import Broker
from pinot_tpu.broker.workload import (BROWNOUT_DEADLINE_MS,
                                       OverloadGovernor,
                                       OverloadShedError, TenantSpec,
                                       WorkloadManager, global_governor,
                                       global_workload,
                                       parse_retry_attempt,
                                       retry_after_ms, shed_decision,
                                       tier_shed_rank)
from pinot_tpu.engine.accounting import ResourceAccountant
from pinot_tpu.engine.ragged import global_batcher
from pinot_tpu.query.sql import SqlError
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)
from pinot_tpu.utils.metrics import (global_metrics, overload_health,
                                     render_prometheus)


@pytest.fixture(autouse=True)
def _reset_workload():
    """Workload state is process-global (like the accountant): every
    test starts and ends inert so tenant specs/pins can never leak
    into the rest of the suite."""
    global_workload.reset()
    yield
    global_workload.reset()
    global_batcher.window_scale = 1.0


def _counter(name: str) -> int:
    return global_metrics.snapshot()["counters"].get(name, 0)


# -- the pure shed ladder ---------------------------------------------------

def test_shed_decision_ladder():
    # rungs 0/1 admit everyone
    for rung in (0, 1):
        for tier in ("protected", "standard", "besteffort"):
            assert shed_decision("q", "t", tier, rung) is None
    # protected is never rung-shed
    for rung in (2, 3):
        assert shed_decision("q", "t", "protected", rung) is None
    # besteffort sheds outright at rung >= 2
    assert shed_decision("q", "t", "besteffort", 2) == "tier_besteffort"
    assert shed_decision("q", "t", "besteffort", 3) == "tier_besteffort"
    # standard: full shed at rung 3, deterministic partial at rung 2
    assert shed_decision("q", "t", "standard", 3) == "tier_standard"
    decisions = {q: shed_decision(q, "t", "standard", 2)
                 for q in (f"q{i}" for i in range(64))}
    shed = [q for q, d in decisions.items() if d]
    assert 10 < len(shed) < 54, "rung-2 standard shed should be partial"
    # purity: identical inputs, identical outputs
    for q, d in decisions.items():
        assert shed_decision(q, "t", "standard", 2) == d


def test_retry_after_deterministic_and_rung_scaled():
    a = retry_after_ms("q1", "ten", 2)
    assert a == retry_after_ms("q1", "ten", 2)
    assert retry_after_ms("q2", "ten", 2) != a or \
        retry_after_ms("q3", "ten", 2) != a  # jitter spreads
    assert retry_after_ms("q1", "ten", 3) > retry_after_ms("q1", "ten", 1)


def test_parse_retry_attempt_validation():
    assert parse_retry_attempt({}) == 0
    assert parse_retry_attempt({"retryAttempt": "2"}) == 2
    with pytest.raises(SqlError):
        parse_retry_attempt({"retryAttempt": "soon"})
    with pytest.raises(SqlError):
        parse_retry_attempt({"retryAttempt": -1})


# -- governor ---------------------------------------------------------------

def test_rung_for_pressure_watermarks():
    f = OverloadGovernor.rung_for_pressure
    assert f(0.0) == 0 and f(0.49) == 0
    assert f(0.5) == 1 and f(0.74) == 1
    assert f(0.75) == 2 and f(0.89) == 2
    assert f(0.9) == 3 and f(5.0) == 3


def test_governor_live_signal_and_hysteresis():
    gov = OverloadGovernor()
    level = [0.0]
    gov.add_signal("x", lambda: level[0], 100.0)
    gov.POLL_S = 0.0  # no sample caching in this test
    assert gov.rung() == 0
    level[0] = 80.0   # pressure 0.8 -> rung 2
    assert gov.rung() == 2
    # hysteresis: just below the rung-2 watermark stays on rung 2
    level[0] = 72.0   # 0.72 >= 0.75 - 0.05
    assert gov.rung() == 2
    level[0] = 60.0   # clearly below: drop to rung 1
    assert gov.rung() == 1
    level[0] = 0.0
    assert gov.rung() == 0


def test_governor_pins_and_window_scale():
    gov = global_workload.governor
    gov.pin_rungs({"qa": 3, "qb": 0}, default=1)
    try:
        assert gov.rung_for("qa") == 3
        assert gov.rung_for("qb") == 0
        assert gov.rung_for("other") == 1
        # rung >= 1 side effect: the micro-batch admission window widens
        assert global_batcher.window_scale == 4.0
        assert gov.shed_speculative()
    finally:
        gov.unpin()
    assert global_batcher.window_scale == 1.0
    assert gov.brownout_deadline_ms() is None


# -- tenant budgets ---------------------------------------------------------

def test_inflight_budget_sheds_and_releases():
    m = WorkloadManager()
    m.set_tenant("cap", tier="standard", max_inflight=2)
    m.set_table_tenant("t", "cap")
    t1 = m.admit("q1", "t")
    t2 = m.admit("q2", "t")
    with pytest.raises(OverloadShedError) as ei:
        m.admit("q3", "t")
    assert ei.value.reason == "inflight_budget"
    assert ei.value.error_code == 429
    assert ei.value.retry_after_ms > 0
    m.release(t1)
    t3 = m.admit("q3", "t")   # capacity freed
    m.release(t2)
    m.release(t3)
    m.release(t3)             # idempotent
    assert m.inflight("cap") == 0


def test_post_paid_cpu_bucket_debt_and_refill():
    m = WorkloadManager()
    m.set_tenant("busy", cpu_ms_per_s=100.0)
    m.set_table_tenant("t", "busy")
    now = 1000.0
    t1 = m.admit("q1", "t", now=now)
    # post-paid: actual usage drives the balance negative
    m.release(t1, cpu_ms=500.0, now=now)
    with pytest.raises(OverloadShedError) as ei:
        m.admit("q2", "t", now=now)
    assert ei.value.reason == "cpu_budget"
    # the debt refills at 100 cpu-ms/s: admitted again 5s later
    t3 = m.admit("q2", "t", now=now + 5.0)
    m.release(t3)


def test_result_bytes_bucket():
    m = WorkloadManager()
    m.set_tenant("bytes", result_bytes_per_s=1000.0)
    m.set_table_tenant("t", "bytes")
    now = 50.0
    t1 = m.admit("q1", "t", now=now)
    m.release(t1, result_bytes=10_000.0, now=now)
    with pytest.raises(OverloadShedError) as ei:
        m.admit("q2", "t", now=now + 0.1)
    assert ei.value.reason == "bytes_budget"


def test_accountant_fence_feeds_tenant_buckets():
    """The post-paid loop end to end: usage tracked through the
    accountant's existing fence debits the tenant bucket at
    unregister (no extra metering on the hot path)."""
    global_workload.set_tenant("fed", result_bytes_per_s=1024.0)
    global_workload.set_table_tenant("t", "fed")
    acct = ResourceAccountant()
    acct.register("qf", tenant="fed", tier="standard")
    acct.track_memory(1 << 20)   # what track_result would add
    acct.unregister("qf")        # -> global_workload.observe(usage)
    with pytest.raises(OverloadShedError):
        global_workload.admit("q2", "t")


def test_retry_budget_amplification_guard():
    m = WorkloadManager()
    m.set_tenant("re", tier="protected", retries_per_s=0.001)
    m.set_table_tenant("t", "re")
    m.governor.pin_rungs({}, default=2)  # overload: retries charged
    try:
        now = 10.0
        t1 = m.admit("q1", "t", retry_attempt=1, now=now)  # burst token
        m.release(t1)
        c0 = _counter("overload_retries_suppressed")
        with pytest.raises(OverloadShedError) as ei:
            m.admit("q2", "t", retry_attempt=1, now=now + 0.01)
        assert ei.value.reason == "retry_budget"
        assert _counter("overload_retries_suppressed") == c0 + 1
        # a FRESH (non-retry) protected query is unaffected
        t3 = m.admit("q3", "t", retry_attempt=0, now=now + 0.02)
        m.release(t3)
    finally:
        m.governor.unpin()


def test_shed_log_stream_and_counters():
    m = WorkloadManager()
    m.set_tenant("be", tier="besteffort")
    m.set_table_tenant("t", "be")
    m.governor.pin_rungs({"q1": 3})
    try:
        c0 = _counter("overload_shed")
        with pytest.raises(OverloadShedError):
            m.admit("q1", "t")
        assert _counter("overload_shed") == c0 + 1
        stream = m.shed_stream()
        assert len(stream) == 1
        qid, tenant, rung, reason, after = stream[0]
        assert (qid, tenant, rung, reason) == \
            ("q1", "be", 3, "tier_besteffort")
        assert after == retry_after_ms("q1", "be", 3)
        m.clear_shed_log()
        assert m.shed_stream() == []
    finally:
        m.governor.unpin()


def test_arm_default_signals_live_shedding():
    """The repo's existing signals wired live: in-flight count, RSS,
    devmem bytes, a queue-depth callable — in-flight pressure alone
    pushes the ladder into rung 2 and sheds a besteffort query."""
    from pinot_tpu.broker.workload import arm_default_signals
    m = WorkloadManager()
    m.governor.POLL_S = 0.0
    arm_default_signals(m, inflight_capacity=4,
                        rss_limit_bytes=1 << 50,
                        devmem_budget_bytes=1 << 40,
                        queue_depth_fn=lambda: 0.0, queue_capacity=8)
    assert sorted(m.governor.snapshot()["signals"]) == \
        ["devmem", "inflight", "queue", "rss"]
    m.set_tenant("be", tier="besteffort")
    m.set_table_tenant("t", "be")
    tickets = [m.admit(f"q{i}", "t") for i in range(3)]
    assert m.governor.rung() == 2   # 3/4 in-flight = pressure 0.75
    with pytest.raises(OverloadShedError):
        m.admit("q3", "t")
    for t in tickets:
        m.release(t)
    assert m.governor.rung() == 0   # pressure cleared (hysteresis off 0)
    t4 = m.admit("q4", "t")
    m.release(t4)


# -- tier-aware kill ordering -----------------------------------------------

def test_kill_most_expensive_prefers_besteffort():
    assert tier_shed_rank("besteffort") < tier_shed_rank("standard") \
        < tier_shed_rank("protected")
    acct = ResourceAccountant()
    prot = acct.register("vip", tenant="a", tier="protected")
    be = acct.register("cheap", tenant="b", tier="besteffort")
    prot.mem_bytes = 1 << 30   # by cost alone, protected would die
    be.mem_bytes = 1 << 10
    assert acct.kill_most_expensive("pressure") == "cheap"
    assert prot.killed_reason is None
    # with only protected left, it is still killable (last resort)
    assert acct.kill_most_expensive("pressure") == "vip"
    acct.unregister("vip")
    acct.unregister("cheap")


# -- in-process broker integration ------------------------------------------

@pytest.fixture(scope="module")
def tenant_broker(tmp_path_factory):
    rng = np.random.default_rng(3)
    n = 512
    cols = {"k": rng.integers(0, 8, n).astype(np.int32),
            "v": rng.integers(0, 100, n).astype(np.int32)}
    schema_fields = [FieldSpec("k", DataType.INT, FieldType.DIMENSION),
                     FieldSpec("v", DataType.INT, FieldType.METRIC)]
    broker = Broker()
    for table, tenant in (("ovl_prot", "ten_p"), ("ovl_be", "ten_b")):
        schema = Schema(table, schema_fields)
        cfg = TableConfig(table, tenant=tenant)
        dm = TableDataManager(table)
        dm.table_config = cfg
        dm.add_segment_dir(SegmentBuilder(schema, cfg).build(
            cols, str(tmp_path_factory.mktemp(table)), "s0"))
        broker.register_table(dm)
    return broker


def _tenants_on(broker):
    broker.workload.set_tenant("ten_p", tier="protected")
    broker.workload.set_tenant("ten_b", tier="besteffort")
    broker.workload.set_table_tenant("ovl_prot", "ten_p")
    broker.workload.set_table_tenant("ovl_be", "ten_b")


def test_broker_sheds_besteffort_structured(tenant_broker):
    _tenants_on(tenant_broker)
    global_governor.pin_rungs({"sq1": 2, "sq2": 2})
    try:
        with pytest.raises(OverloadShedError) as ei:
            tenant_broker.query(
                "SELECT COUNT(*) FROM ovl_be OPTION(queryId=sq1)")
        p = ei.value.payload()
        assert p["errorCode"] == 429 and p["retryAfterMs"] > 0
        assert p["tenant"] == "ten_b" and p["rung"] == 2
        # protected sails through at the same rung
        res = tenant_broker.query(
            "SELECT COUNT(*) FROM ovl_prot OPTION(queryId=sq2)")
        assert res.rows[0][0] == 512
    finally:
        global_governor.unpin()


def test_broker_brownout_clamps_deadline(tenant_broker):
    _tenants_on(tenant_broker)
    global_governor.pin_rungs({"bq1": 3})
    c0 = _counter("overload_brownout_clamped")
    try:
        res = tenant_broker.query(
            "SELECT COUNT(*) FROM ovl_prot "
            "OPTION(queryId=bq1, timeoutMs=600000)")
        assert res.rows[0][0] == 512
    finally:
        global_governor.unpin()
    assert _counter("overload_brownout_clamped") == c0 + 1
    assert BROWNOUT_DEADLINE_MS < 600_000


def test_broker_rung1_sheds_trace_sampling(tenant_broker, tmp_path):
    """rung >= 1 pauses traceRatio sampling (speculative work)."""
    _tenants_on(tenant_broker)
    ledger = str(tmp_path / "trace.jsonl")
    tenant_broker._trace_ratio = 1.0
    tenant_broker._trace_ledger_path = ledger
    try:
        global_governor.pin_rungs({}, default=1)
        try:
            tenant_broker.query(
                "SELECT COUNT(*) FROM ovl_prot OPTION(queryId=tr1)")
        finally:
            global_governor.unpin()
        assert not os.path.exists(ledger), "sampled under rung 1"
        tenant_broker.query(
            "SELECT COUNT(*) FROM ovl_prot OPTION(queryId=tr2)")
        assert os.path.exists(ledger), "ratio=1 must sample at rung 0"
    finally:
        tenant_broker._trace_ratio = 0.0
        tenant_broker._trace_ledger_path = None


def test_default_tables_stay_unaffected(tenant_broker):
    """No tenants configured / rung 0: admission is inert (the whole
    existing suite depends on this default)."""
    res = tenant_broker.query("SELECT COUNT(*) FROM ovl_prot")
    assert res.rows[0][0] == 512
    assert global_workload.resolve("never_configured") == \
        ("default", "standard")


# -- scheduler rejection satellite ------------------------------------------

def test_scheduler_rejected_is_structured_sql_error():
    from pinot_tpu.engine.scheduler import (FcfsScheduler,
                                            SchedulerRejectedError)
    import threading
    sched = FcfsScheduler(num_workers=1, max_pending=1)
    gate = threading.Event()
    sched.submit(lambda: gate.wait(5), "q0")
    time.sleep(0.05)
    sched.submit(lambda: None, "q1")
    with pytest.raises(SchedulerRejectedError) as ei:
        sched.submit(lambda: None, "q2")
    e = ei.value
    assert isinstance(e, SqlError)
    assert e.error_code == 211 and e.retry_after_ms > 0
    assert e.payload()["errorCode"] == 211
    gate.set()
    sched.stop()


def test_http_plane_renders_capacity_errors_as_429():
    """The JsonHandler satellite: a SchedulerRejectedError escaping a
    handler (the old 500 path) now renders as structured retryable
    JSON — the server /query plane's regression pin."""
    from pinot_tpu.cluster.http_util import JsonHandler, start_http
    from pinot_tpu.engine.scheduler import SchedulerRejectedError

    class H(JsonHandler):
        routes = {("POST", "/query"): lambda h, b: (_ for _ in ()).throw(
            SchedulerRejectedError("queue full", retry_after_ms=120))}

    srv, port, _t = start_http(H, 0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/query", data=b"{}",
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 429
        body = json.loads(ei.value.read().decode())
        assert body["errorCode"] == 211
        assert body["retryAfterMs"] == 120
    finally:
        srv.shutdown()
        srv.server_close()


def test_http_plane_listens_with_a_backlog_for_concurrent_clients():
    """Eight closed-loop clients connect in the same instant; the
    stdlib's backlog of 5 made the sixth to eighth wait a second for
    TCP's retransmit (PR 33). Every node's plane comes from start_http."""
    from pinot_tpu.cluster.http_util import JsonHandler, start_http

    srv, _port, _t = start_http(JsonHandler, 0)
    try:
        assert srv.request_queue_size >= 128
    finally:
        srv.shutdown()
        srv.server_close()


def test_governor_unsticks_when_signals_removed():
    """Removing the last signal with pressure high must drop back to
    rung 0 — nothing could ever lower a stale cached rung again."""
    gov = OverloadGovernor()
    gov.POLL_S = 0.0
    gov.add_signal("x", lambda: 95.0, 100.0)
    assert gov.rung() == 3
    gov.remove_signal("x")
    assert gov.rung() == 0


def test_governor_no_clock_read_in_pinned_or_inert_mode(monkeypatch):
    """The detlint round-23 fix stays fixed: pinned (replay) and inert
    governors must answer admission checks without EVER touching the
    wall clock — wall time must not leak into replayable decisions.
    Pre-fix, rung() read time.monotonic() before the early return."""
    from pinot_tpu.broker import workload as wl

    def _no_clock():
        raise AssertionError(
            "deterministic plane read time.monotonic()")

    gov = OverloadGovernor()
    monkeypatch.setattr(wl.time, "monotonic", _no_clock)
    # inert: nothing armed — the process default on every admission
    assert gov.rung() == 0
    assert gov.rung_for("q1") == 0
    # pinned: the replay schedule answers, live signals stay silent
    gov.add_signal("x", lambda: 95.0, 100.0)
    gov.pin_rungs({"q2": 2}, default=1)
    assert gov.rung_for("q2") == 2
    assert gov.rung_for("q3") == 1
    assert gov.rung() == 2  # pinned rung() reports cached state only
    # live mode takes the injected poll clock, not the wall clock
    gov.unpin()
    assert gov.rung(now=1000.0) == 3


def test_inert_fast_path_counts_nothing():
    """The process default (no tenants, nothing armed) must not churn
    metrics or in-flight state per query."""
    m = WorkloadManager()
    c0 = _counter("tenant_admitted_default")
    t = m.admit("q1", "whatever")
    assert t.counted is False and t.rung == 0
    m.release(t)
    assert _counter("tenant_admitted_default") == c0
    assert m.inflight() == 0


@pytest.fixture(scope="module")
def mini_cluster(tmp_path_factory):
    """Controller + 1 server + broker over one tenant table (the
    wire-attribution and capacity-429-propagation pins)."""
    from pinot_tpu.cluster import BrokerNode, Controller, ServerNode
    tmp = tmp_path_factory.mktemp("ovl_cluster")
    ctrl = Controller(str(tmp / "ctrl"), heartbeat_timeout=5.0,
                      reconcile_interval=0.2)
    server = ServerNode("server_0", ctrl.url, poll_interval=0.1)
    broker = BrokerNode(ctrl.url, routing_refresh=0.1)
    rng = np.random.default_rng(5)
    cols = {"k": rng.integers(0, 4, 128).astype(np.int32),
            "v": rng.integers(0, 50, 128).astype(np.int32)}
    schema = Schema("wt", [FieldSpec("k", DataType.INT,
                                    FieldType.DIMENSION),
                           FieldSpec("v", DataType.INT,
                                     FieldType.METRIC)])
    ctrl.add_table("wt", schema.to_dict(), config={"tenant": "acme"})
    seg = SegmentBuilder(schema, TableConfig("wt")).build(
        cols, str(tmp), "s0")
    ctrl.add_segment("wt", "s0", seg)
    v = ctrl.routing_snapshot()["version"]
    assert server.wait_for_version(v, timeout=30.0)
    assert broker.wait_for_version(v, timeout=30.0)
    yield ctrl, server, broker
    broker.stop()
    server.stop()
    ctrl.stop()


def test_tenant_attribution_crosses_the_wire(mini_cluster):
    """The broker forwards tenant/tier on every server dispatch, so the
    server-side accountant entry carries them — the tier-aware
    HeapWatcher kill ordering acts where the kernels execute."""
    from pinot_tpu.engine.accounting import global_accountant
    _ctrl, _server, broker = mini_cluster
    global_workload.set_tenant("acme", tier="protected")
    seen = []
    orig = global_accountant.register

    def spy(query_id, deadline=None, tenant=None, tier=None, sql=None):
        seen.append((tenant, tier))
        return orig(query_id, deadline=deadline, tenant=tenant,
                    tier=tier, sql=sql)
    global_accountant.register = spy
    try:
        import json as _json
        req = urllib.request.Request(
            f"{broker.url}/query/sql",
            data=_json.dumps({"sql": "SELECT COUNT(*) FROM wt"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
    finally:
        global_accountant.register = orig
    assert ("acme", "protected") in seen, seen


def test_broker_propagates_server_capacity_429(mini_cluster):
    """A server's SchedulerRejectedError (HTTP 429 + retryAfterMs) must
    surface from the BROKER as the same structured retryable shape —
    never flattened to a 400 (the cross-node half of the satellite)."""
    from pinot_tpu.engine.scheduler import SchedulerRejectedError
    _ctrl, server, broker = mini_cluster

    def busy(*a, **kw):
        raise SchedulerRejectedError("queue full", retry_after_ms=170)
    server.execute = busy
    try:
        import json as _json
        req = urllib.request.Request(
            f"{broker.url}/query/sql",
            data=_json.dumps({"sql": "SELECT COUNT(*) FROM wt"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 429
        body = _json.loads(ei.value.read().decode())
        assert body["errorCode"] == 211
        assert body["retryAfterMs"] == 170
    finally:
        del server.execute  # restore the class method


# -- quota / live brokers satellite -----------------------------------------

def test_quota_set_num_brokers_redivides():
    from pinot_tpu.broker.quota import QueryQuotaManager
    q = QueryQuotaManager()
    q.set_quota("t", 8.0)
    assert q.effective_qps("t") == 8.0
    q.set_num_brokers(2)
    assert q.effective_qps("t") == 4.0
    q.set_num_brokers(4)
    assert q.effective_qps("t") == 2.0
    q.set_num_brokers(4)  # unchanged: no bucket churn
    assert q.effective_qps("t") == 2.0
    q.set_quota("t", None)
    assert q.effective_qps("t") is None


def test_quota_flap_does_not_mint_fresh_burst():
    """A live-broker-count flip RESCALES the bucket in place: heartbeat
    flapping must not grant a fresh full burst per flip (that would let
    a client sustain a multiple of the configured table QPS)."""
    from pinot_tpu.broker.quota import QueryQuotaManager, \
        QuotaExceededError
    q = QueryQuotaManager()
    q.set_quota("t", 2.0)
    q.check("t")
    q.check("t")                      # burst spent (capacity 2)
    with pytest.raises(QuotaExceededError):
        q.check("t")
    q.set_num_brokers(2)              # flap down...
    q.set_num_brokers(1)              # ...and back
    with pytest.raises(QuotaExceededError):
        q.check("t")                  # still over quota — no new burst


def test_two_brokers_divide_table_quota(tmp_path):
    """Round-14 brokers register+heartbeat; the controller now ships
    liveBrokers in every routing snapshot and each broker enforces
    quota/N (reference HelixExternalViewBasedQueryQuotaManager
    behavior)."""
    from pinot_tpu.cluster import BrokerNode, Controller
    ctrl = Controller(str(tmp_path / "ctrl"), heartbeat_timeout=5.0,
                      reconcile_interval=0.2)
    b1 = b2 = None
    try:
        schema = Schema("qt", [FieldSpec("v", DataType.INT,
                                         FieldType.METRIC)])
        ctrl.add_table("qt", schema.to_dict(),
                       config={"quotaQps": 8.0})
        b1 = BrokerNode(ctrl.url, routing_refresh=0.1)
        b2 = BrokerNode(ctrl.url, routing_refresh=0.1)
        snap = ctrl.routing_snapshot()
        assert sorted(snap["liveBrokers"]) == sorted(
            [b1.instance_id, b2.instance_id])
        v = snap["version"]
        assert b1.wait_for_version(v) and b2.wait_for_version(v)
        for b in (b1, b2):
            # instance liveness is heartbeat-driven, not versioned: b1
            # may have cached a snapshot from before b2 registered
            b._refresh_routing()
            b._check_quota("qt")
            assert b._quota.num_brokers == 2
            assert b._quota.effective_qps("qt") == 4.0
        # the overload block is served at GET /metrics (and the
        # Prometheus endpoint renders without an illegal line)
        with urllib.request.urlopen(f"{b1.url}/metrics",
                                    timeout=5) as r:
            m = json.loads(r.read().decode())
        assert "overload" in m and "rung" in m["overload"]
        assert "governor" in m["overload"]
        with urllib.request.urlopen(f"{b1.url}/metrics/prometheus",
                                    timeout=5) as r:
            assert r.status == 200 and r.read()
    finally:
        for b in (b1, b2):
            if b is not None:
                b.stop()
        ctrl.stop()


# -- observability ----------------------------------------------------------

def test_overload_health_block_and_prometheus():
    global_metrics.count("overload_shed", 3)
    global_metrics.count("overload_shed_rung_2", 2)
    global_metrics.count("tenant_shed_acme", 3)
    global_metrics.gauge("tenant_inflight_bad.tenant-v2", 5)
    global_metrics.gauge("overload_rung", 2)
    snap = global_metrics.snapshot()
    h = overload_health(snap)
    assert h["overload_shed"] >= 3
    assert h["shed_by_rung"]["2"] >= 2
    assert h["shed_by_tenant"]["acme"] >= 3
    assert h["inflight_by_tenant"]["bad.tenant-v2"] == 5
    assert h["rung"] == 2
    # user-supplied tenant names render through _prom_name: every
    # exposition line stays legal
    text = render_prometheus(snap)
    assert "pinot_tpu_tenant_inflight_bad_tenant_v2 5" in text
    for line in text.strip().splitlines():
        name = line.split(" ")[0]
        assert all(c.isalnum() or c in "_:" for c in name), line


def test_rollup_trends_shed_rates():
    from pinot_tpu.cluster.rollup import aggregate_tables
    recs = [
        {"kind": "query_stats", "table": "t1", "wall_ms": 5.0,
         "ts": "2026-08-05T00:00:00Z"},
        {"kind": "query_stats", "table": "t1", "wall_ms": 1.0,
         "shed": True, "tenant": "acme", "shed_rung": 2,
         "error": "shed", "ts": "2026-08-05T00:00:01Z"},
        {"kind": "query_stats", "table": "t1", "wall_ms": 1.0,
         "shed": True, "tenant": "acme", "shed_rung": 3,
         "error": "shed", "ts": "2026-08-05T00:00:02Z"},
    ]
    tables = aggregate_tables(recs)
    assert tables["t1"]["queries"] == 3
    assert tables["t1"]["shed"] == 2
    assert tables["t1"]["shed_by_tenant"] == {"acme": 2}


def test_webapp_fleet_view_renders_shed_column():
    from pinot_tpu.cluster.webapp import render_app
    page = render_app({"tables": {}, "instances": {}, "version": 1})
    assert "shed" in page and "shed_by_tenant" in page


# -- ledger contracts -------------------------------------------------------

def test_replay_bench_contract():
    from pinot_tpu.utils import ledger as uledger
    rec = uledger.make_record(
        "replay_bench", backend="cpu", ok=True, scenario="overload",
        seed=1, multiple=4.0, offered=64, completed=30, shed=30,
        goodput_qps=25.0, duration_s=1.2,
        shed_by_tenant={"be": 30}, protected_sheds=0,
        deterministic=True, recovered=True)
    assert not uledger.validate_record(rec)
    with pytest.raises(ValueError):
        uledger.make_record("replay_bench", backend="cpu", ok=True,
                            scenario="x", seed=1, multiple=4.0,
                            offered=1, completed=1, shed=0,
                            goodput_qps=1.0, duration_s=1.0,
                            bogus_field=1)
    with pytest.raises(ValueError):  # missing required
        uledger.make_record("replay_bench", backend="cpu", ok=True)


def test_query_stats_workload_fields_valid():
    from pinot_tpu.utils import ledger as uledger
    rec = uledger.make_record(
        "query_stats", qid="q", table="t", wall_ms=1.0, partial=False,
        servers_queried=1, servers_responded=1, exception_codes=[],
        tenant="acme", tier="besteffort", shed=True, shed_rung=2,
        retry_after_ms=250, arrival_ms=12.5)
    assert not uledger.validate_record(rec)


def test_check_ledger_reports_replay_bench(tmp_path):
    from pinot_tpu.utils import ledger as uledger
    path = str(tmp_path / "l.jsonl")
    uledger.append_record(uledger.make_record(
        "replay_bench", backend="cpu", ok=True, scenario="s", seed=1,
        multiple=2.0, offered=4, completed=4, shed=0,
        goodput_qps=8.0, duration_s=0.5), path)
    res = uledger.validate_file(path)
    assert not res["errors"]
    assert res["kinds"] == {"replay_bench": 1}


# -- traffic replay plan purity ---------------------------------------------

def _synthetic_records(n=24, gap_ms=50.0):
    recs = []
    tenants = ["ten_protected", "ten_standard", "ten_besteffort"]
    for i in range(n):
        recs.append({"kind": "query_stats", "qid": f"s{i}",
                     "table": "t", "wall_ms": 2.0, "partial": False,
                     "servers_queried": 0, "servers_responded": 0,
                     "exception_codes": [], "sql": "SELECT 1 FROM t",
                     "tenant": tenants[i % 3],
                     "arrival_ms": i * gap_ms})
    return recs


def test_plan_replay_pure_and_multiple_scales():
    import traffic_replay as TR
    tier_of = {"ten_protected": "protected", "ten_standard": "standard",
               "ten_besteffort": "besteffort"}
    recs = _synthetic_records()
    p1 = TR.plan_replay(recs, 4.0, 11, tier_of=tier_of)
    p2 = TR.plan_replay(recs, 4.0, 11, tier_of=tier_of)
    assert p1["shed_stream"] == p2["shed_stream"]
    assert p1["pins"] == p2["pins"]
    assert any(s[1] == "ten_besteffort" for s in p1["shed_stream"])
    assert all(s[1] != "ten_protected" for s in p1["shed_stream"])
    # at 1x the offered rate sits under every watermark: no sheds
    calm = TR.plan_replay(recs, 1.0, 11, tier_of=tier_of)
    assert calm["shed_stream"] == []
    # every shed qid's rung is pinned for the live run to look up
    for qid, _t, rung, _r, _a in p1["shed_stream"]:
        assert p1["pins"][qid] == rung


# -- the tier-1 closed-loop gate --------------------------------------------

def test_chaos_smoke_overload_cli(capsys):
    """ISSUE 12 acceptance: sustained 4x replay with chaos armed —
    protected untouched inside its bar, besteffort absorbs, every shed
    a structured 429, same-seed shed streams identical, recovery to
    the pre-spike noise floor, one validated replay_bench record."""
    import chaos_smoke
    assert chaos_smoke.main(["--overload"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert summary["ok"] and summary["mode"] == "overload"
    assert summary["deterministic"] is True
    assert summary["protected_sheds"] == 0
    assert summary["tiers"]["protected"]["errors"] == 0
    assert summary["shed_by_tenant"].get("ten_besteffort", 0) >= 1
    assert summary["structured_429"] == summary["shed"] >= 1
    assert summary["faults_fired"] >= 1
    assert summary["recovered"] is True
    assert summary["ledger_kinds"].get("replay_bench", 0) >= 1
