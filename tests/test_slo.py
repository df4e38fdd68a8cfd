"""SLO plane: error budgets, multi-window burn alerting and the
incident flight recorder (ISSUE 17 acceptance).

Contract under test:
- burn-rate math against hand oracles (burn = bad_fraction / budget
  per window; 0.0 on an idle window) and the Google-SRE pairing: the
  alert arms only when BOTH the fast and the slow window burn over the
  threshold, latched with hysteresis through utils/alerts;
- classification: shed rows are EXCLUDED from latency (the round-17
  rollup rule) but COUNT as bad for availability; errors/partials are
  availability-bad; a dead freshness gauge (no write for stale_s) is a
  bad sample — frozen writers trip the SLO instead of passing it;
- determinism: every window decision derives from record timestamps
  (``arrival_ms + wall_ms``), never the wall clock —
  ``plan_alert_stream`` over the same corpus is byte-identical;
- the incident flight recorder captures ONE bounded, ledger-validated
  bundle per fire with every surface independently fenced, served at
  GET /debug/incidents beside the GET /debug index;
- cluster/rollup.aggregate_slo: proc-deduped worst-replica fleet view;
- tools/slo_report.py gate: trips on a burned corpus, passes a clean
  one, and refuses the vacuous green (no query_stats records).
"""
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from pinot_tpu.segment import SegmentBuilder  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, FieldType,  # noqa: E402
                           Schema, TableConfig)
from pinot_tpu.utils import ledger as uledger  # noqa: E402
from pinot_tpu.utils.alerts import AlertManager  # noqa: E402
from pinot_tpu.utils.slo import (  # noqa: E402
    IncidentRecorder, Objective, SloPlane, burn_rate, classify_query,
    evaluate_objective, event_time, normalize_alerts, plan_alert_stream)

import slo_report  # noqa: E402  (tools/ on sys.path, chaos_smoke-style)


def _plane(**objective_kw) -> SloPlane:
    """An isolated plane (own AlertManager — never the global one)."""
    p = SloPlane(alerts=AlertManager("testproc"), proc_token="testproc")
    if objective_kw:
        p.set_objective(**objective_kw)
    return p


# ---------------------------------------------------------------------------
# pure window math vs hand oracles
# ---------------------------------------------------------------------------

def test_burn_rate_hand_oracle():
    # objective 0.9 => budget 0.1; 2 bad of 10 => bad frac 0.2 => 2.0x
    events = tuple((float(i), i not in (3, 7)) for i in range(10))
    burn, total, bad = burn_rate(events, 9.0, 60.0, 0.1)
    assert (burn, total, bad) == (pytest.approx(2.0), 10, 2)
    # a window covering only the good tail burns 0.0x
    assert burn_rate(events, 9.0, 1.0, 0.1)[0] == 0.0
    # idle window (no events) and zero budget both burn nothing
    assert burn_rate((), 9.0, 60.0, 0.1) == (0.0, 0, 0)
    assert burn_rate(events, 9.0, 60.0, 0.0)[0] == 0.0
    # events in the future of ``now`` are outside the window
    assert burn_rate(events, 0.0, 60.0, 0.1)[1] == 1


def test_evaluate_objective_row_shape_and_clamp():
    obj = Objective("t1", "availability", objective=0.9,
                    fast_s=2.0, slow_s=60.0, burn_threshold=4.0)
    # 5 bad of 5 => burn 10.0x; budget_remaining clamps at 0.0
    events = tuple((float(i), False) for i in range(5))
    row = evaluate_objective(events, 4.0, obj)
    assert row["burn_slow"] == pytest.approx(10.0)
    assert row["budget_remaining"] == 0.0
    assert row["events"] == 5 and row["bad"] == 5
    assert row["window_s"] == 60.0 and row["fast_window_s"] == 2.0
    # the row is the slo_status contract minus envelope/proc
    assert {"scope", "kind", "objective", "burn_fast", "burn_slow",
            "budget_remaining", "window_s"} <= set(row)


def test_classify_query_shed_exclusion():
    shed = {"wall_ms": 0.3, "shed": True}
    slow = {"wall_ms": 900.0}
    fast = {"wall_ms": 3.0}
    err = {"wall_ms": 5.0, "error": "boom"}
    part = {"wall_ms": 5.0, "partial": True}
    # latency: shed rows are NOT counted (they'd mask the regression)
    assert classify_query(shed, 100.0)["latency"][0] is False
    assert classify_query(slow, 100.0)["latency"] == (True, False)
    assert classify_query(fast, 100.0)["latency"] == (True, True)
    # availability: every query counts; shed/error/partial are bad
    for rec in (shed, err, part):
        assert classify_query(rec, 100.0)["availability"] == (True, False)
    assert classify_query(fast, 100.0)["availability"] == (True, True)


def test_event_time_is_record_derived():
    assert event_time({"arrival_ms": 1500.0, "wall_ms": 500.0}) == 2.0
    assert event_time({"wall_ms": 5.0}) is None


def test_objective_validation():
    with pytest.raises(ValueError):
        Objective("t", "throughput")          # unknown kind
    with pytest.raises(ValueError):
        Objective("t", "latency")             # latency requires bar_ms
    with pytest.raises(ValueError):
        Objective("t", "availability", objective=1.0)  # not a fraction


# ---------------------------------------------------------------------------
# the tracking plane: fire / latch / clear, all on injected event time
# ---------------------------------------------------------------------------

def test_burn_alert_fires_once_and_clears_on_drain():
    p = _plane(scope="tenant:acme", kind="availability", objective=0.9,
               fast_s=2.0, slow_s=10.0, burn_threshold=2.0)
    fired = []
    # 4 bad of 8 inside both windows: burn 5.0x >= 2.0x in each
    for i in range(8):
        rec = {"tenant": "acme", "arrival_ms": i * 100.0,
               "wall_ms": 0.0, "shed": i % 2 == 0}
        fired += p.observe_query(rec)
    assert len(fired) == 1, "latched rule must fire exactly once"
    a = fired[0]
    assert a["alert"] == "slo_burn" and a["severity"] == "page"
    assert a["extra"]["scope"] == "tenant:acme"
    assert uledger.validate_record(a) == []
    assert p.status_block()["objectives"][0]["alerting"] is True
    # 3s of clean traffic: the 2s fast window drains to 0.0x and the
    # paired level drops below threshold — the latch clears
    for i in range(6):
        p.observe_query({"tenant": "acme", "wall_ms": 0.0,
                         "arrival_ms": 1000.0 + i * 500.0})
    row = p.status_block()["objectives"][0]
    assert row["alerting"] is False and row["burn_fast"] == 0.0


def test_fast_window_alone_does_not_fire():
    # ONE bad event in a long good history: the fast window burns hot
    # but the slow window stays under threshold => paired level holds
    p = _plane(scope="t1", kind="availability", objective=0.9,
               fast_s=1.0, slow_s=1000.0, burn_threshold=4.0)
    fired = []
    for i in range(200):
        fired += p.observe_query(
            {"table": "t1", "arrival_ms": i * 2000.0, "wall_ms": 0.0})
    fired += p.observe_query(
        {"table": "t1", "arrival_ms": 400000.0, "wall_ms": 0.0,
         "error": "x"})
    row = p.status_block()["objectives"][0]
    assert row["burn_fast"] >= 4.0       # the fast window is all-bad
    assert fired == [] and row["alerting"] is False


def test_latency_plane_skips_shed_rows():
    p = _plane(scope="t1", kind="latency", bar_ms=10.0, objective=0.5,
               fast_s=60.0, slow_s=60.0, burn_threshold=1.0)
    # sheds report wall_ms ~0 (admission-rejected): counting them as
    # fast queries would mask the overload they signal
    for i in range(10):
        p.observe_query({"table": "t1", "arrival_ms": float(i),
                         "wall_ms": 0.2, "shed": True})
    assert p.status_block()["objectives"][0]["events"] == 0


def test_unarmed_observe_is_inert():
    p = SloPlane(alerts=AlertManager("x"))
    assert p.armed is False
    assert p.observe_query({"table": "t", "wall_ms": 1.0}) == []
    assert p.observe_freshness() == []
    assert p.status_block() == {"armed": False, "objectives": []}


# ---------------------------------------------------------------------------
# freshness: dead-gauge trip
# ---------------------------------------------------------------------------

def test_freshness_dead_gauge_is_bad_sample():
    p = _plane(scope="orders", kind="freshness", bar_ms=5000.0,
               objective=0.5, fast_s=60.0, slow_s=60.0,
               burn_threshold=1.0, stale_s=120.0)
    # live gauge under the bar => good sample
    p.observe_freshness("orders", freshness_ms=1000.0, age_s=1.0, now=1.0)
    row = p.status_block()["objectives"][0]
    assert row["bad"] == 0 and "stale" not in row
    # gauge value over the bar => bad sample; 1 bad of 2 at budget 0.5
    # => 1.0x >= 1.0x in both windows: fires (and latches)
    fired = p.observe_freshness("orders", freshness_ms=9000.0,
                                age_s=1.0, now=2.0)
    assert len(fired) == 1
    # DEAD gauge (age past stale_s) => bad even with a healthy value;
    # the latch holds (no duplicate page)
    fired = p.observe_freshness("orders", freshness_ms=1000.0,
                                age_s=500.0, now=3.0)
    assert fired == []
    row = p.status_block()["objectives"][0]
    assert row["bad"] == 2 and row["stale"] is True


def test_freshness_reads_live_gauge_registry():
    from pinot_tpu.utils.metrics import global_metrics
    p = _plane(scope="orders", kind="freshness", bar_ms=5000.0,
               objective=0.5, fast_s=60.0, slow_s=60.0,
               burn_threshold=1.0, stale_s=120.0)
    old_now = global_metrics._now
    base = old_now()
    global_metrics.gauge("ingest_freshness_ms_orders", 1200.0)
    p.observe_freshness(now=1.0)
    assert p.status_block()["objectives"][0]["bad"] == 0
    try:
        # freeze the writer: same value, clock advanced past stale_s
        global_metrics._now = lambda: base + 1000.0
        p.observe_freshness(now=2.0)
        row = p.status_block()["objectives"][0]
        assert row["bad"] == 1 and row["stale"] is True
    finally:
        global_metrics._now = old_now


# ---------------------------------------------------------------------------
# determinism: the pure replay evaluator
# ---------------------------------------------------------------------------

CORPUS = [{"table": "t1", "tenant": "acme",
           "arrival_ms": i * 50.0, "wall_ms": 40.0 if i % 3 else 400.0,
           "shed": i in (10, 11)} for i in range(24)]
OBJECTIVES = [
    {"scope": "t1", "kind": "latency", "bar_ms": 100.0,
     "objective": 0.9, "fast_s": 1.0, "slow_s": 5.0,
     "burn_threshold": 2.0},
    {"scope": "tenant:acme", "kind": "availability", "objective": 0.95,
     "fast_s": 1.0, "slow_s": 5.0, "burn_threshold": 1.0},
]


def test_plan_alert_stream_byte_deterministic():
    a = plan_alert_stream(CORPUS, OBJECTIVES)
    b = plan_alert_stream(CORPUS, OBJECTIVES)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert len(a["alerts"]) >= 2          # both objectives burn
    # process identity and wall clock are pinned out of the plan
    assert all(r["proc"] == "plan" and r["ts"].startswith("t+")
               for r in a["alerts"])
    norm = normalize_alerts(a["alerts"])
    assert ("slo_burn", "t1", "latency", "page") in norm
    assert ("slo_burn", "tenant:acme", "availability", "page") in norm


def test_plan_alert_stream_is_silent_telemetry():
    from pinot_tpu.utils.metrics import global_metrics
    before = global_metrics.snapshot()["counters"].get("slo_alerts", 0)
    plan_alert_stream(CORPUS, OBJECTIVES)
    after = global_metrics.snapshot()["counters"].get("slo_alerts", 0)
    assert after == before, "a replay plan must not bump live telemetry"


# ---------------------------------------------------------------------------
# ledger contracts: slo_status + incident
# ---------------------------------------------------------------------------

def test_slo_status_records_written_on_transitions(tmp_path):
    led = str(tmp_path / "led.jsonl")
    p = _plane(scope="t1", kind="availability", objective=0.9,
               fast_s=2.0, slow_s=10.0, burn_threshold=2.0)
    p.path = led
    for i in range(8):
        p.observe_query({"table": "t1", "arrival_ms": i * 100.0,
                         "wall_ms": 0.0, "shed": i % 2 == 0})
    p.emit_status(now=0.8)
    rows = [json.loads(x) for x in open(led)]
    kinds = [r["kind"] for r in rows]
    assert "alert" in kinds and "slo_status" in kinds
    for r in rows:
        assert uledger.validate_record(r) == [], r
    st = [r for r in rows if r["kind"] == "slo_status"]
    # the objective kind ships as slo_kind (the envelope owns ``kind``)
    assert all(r["slo_kind"] == "availability" for r in st)
    # transition emissions: one on fire, one explicit snapshot — not
    # one per query (the hot path only appends to a deque)
    assert len(st) < 8


def test_incident_capture_bundle_and_ring():
    rec = IncidentRecorder("testproc")
    rec.register_surface("slow_queries", lambda: [{"qid": "q1"}])
    rec.register_surface("broken", lambda: 1 / 0)
    alert = {"alert": "slo_burn", "severity": "page",
             "detail": "t", "extra": {"scope": "t1"}}
    out = rec.request(alert, slo={"burn_slow": 9.9}, sync=True)
    assert uledger.validate_record(out) == []
    assert out["incident_id"] == f"testproc-{out['seq']}"
    assert out["scope"] == "t1" and out["slo"] == {"burn_slow": 9.9}
    # defaults + registered extras; the broken surface is fenced as its
    # error string, never a lost bundle
    assert {"overload", "tier", "devmem", "compile", "slo",
            "slow_queries", "broken"} <= set(out["surfaces"])
    assert out["surfaces"]["slow_queries"] == [{"qid": "q1"}]
    assert "error" in out["surfaces"]["broken"]
    snap = rec.snapshot()
    assert snap["count"] == 1 and snap["captured"] == 1
    # snapshot(0) still reports the ring size (the /debug/ledger count)
    assert rec.snapshot(0)["count"] == 1
    assert rec.snapshot(0)["incidents"] == []
    # seq survives reset: (proc, seq) is the fleet-dedup identity
    seq0 = out["seq"]
    rec.reset()
    assert rec.snapshot()["count"] == 0
    again = rec.request(alert, sync=True)
    assert again["seq"] == seq0 + 1
    # registered surfaces are config-time wiring and survive reset
    assert "slow_queries" in again["surfaces"]


def test_fire_to_incident_hook_end_to_end(tmp_path):
    led = str(tmp_path / "led.jsonl")
    p = _plane(scope="t1", kind="availability", objective=0.9,
               fast_s=2.0, slow_s=10.0, burn_threshold=2.0)
    p.path = led
    p.recorder = IncidentRecorder("testproc")
    p.recorder.path = led
    for i in range(8):
        p.observe_query({"table": "t1", "arrival_ms": i * 100.0,
                         "wall_ms": 0.0, "shed": i % 2 == 0})
    assert p.recorder.drain(5.0), "background capture did not finish"
    snap = p.recorder.snapshot()
    assert snap["count"] == 1
    inc = snap["incidents"][0]
    assert inc["alert"] == "slo_burn" and inc["scope"] == "t1"
    assert inc["slo"]["burn_slow"] >= 2.0
    on_disk = [json.loads(x) for x in open(led)]
    assert any(r["kind"] == "incident" for r in on_disk)


# ---------------------------------------------------------------------------
# fleet aggregation
# ---------------------------------------------------------------------------

def test_aggregate_slo_worst_replica_and_proc_dedup():
    from pinot_tpu.cluster.rollup import aggregate_slo
    row = {"scope": "t1", "kind": "availability", "objective": 0.99,
           "burn_fast": 1.0, "burn_slow": 2.0, "budget_remaining": 0.5,
           "events": 10, "bad": 2, "alerting": False}
    hot = dict(row, burn_fast=6.0, burn_slow=5.0, budget_remaining=0.0,
               events=4, bad=4, alerting=True, stale=True)
    blocks = {
        "broker_1": {"proc": "pA", "slo": {"armed": True,
                                           "objectives": [row]},
                     "incidents": {"count": 1}},
        # same process as broker_1 (in-process roles share the plane):
        # MUST dedupe, not double-count
        "server_1": {"proc": "pA", "slo": {"armed": True,
                                           "objectives": [row]},
                     "incidents": {"count": 1}},
        "broker_2": {"proc": "pB", "slo": {"armed": True,
                                           "objectives": [hot]},
                     "incidents": {"count": 2}},
    }
    out = aggregate_slo(blocks)
    assert out["armed"] is True and out["open_incidents"] == 3
    (m,) = out["objectives"]
    # worst-replica view: max burns, min budget, OR of flags
    assert m["burn_fast"] == 6.0 and m["burn_slow"] == 5.0
    assert m["budget_remaining"] == 0.0
    assert m["events"] == 14 and m["bad"] == 6
    assert m["alerting"] is True and m["stale"] is True
    assert aggregate_slo({}) == {"armed": False, "objectives": [],
                                 "open_incidents": 0}


# ---------------------------------------------------------------------------
# tools/slo_report.py gate
# ---------------------------------------------------------------------------

def _write_corpus(path, n=40, bad_every=0):
    recs = []
    for i in range(n):
        f = {"qid": f"q{i}", "table": "t1", "sql": "SELECT 1",
             "wall_ms": 5.0, "partial": False, "servers_queried": 1,
             "servers_responded": 1, "exception_codes": [], "hedges": 0,
             "failovers": 0, "arrival_ms": i * 25.0}
        if bad_every and i % bad_every == 0:
            f["error"] = "boom"
        recs.append(uledger.make_record("query_stats", **f))
    with open(path, "w") as fh:
        for r in recs:
            fh.write(json.dumps(r) + "\n")


def test_slo_report_gate_trips_on_burned_corpus(tmp_path, capsys):
    led = str(tmp_path / "led.jsonl")
    _write_corpus(led, bad_every=4)   # 25% errors vs 0.1% budget
    rc = slo_report.main(["gate", led, "--availability-objective",
                          "0.999", "--burn-threshold", "4.0"])
    assert rc == 1
    cap = capsys.readouterr()
    assert "GATE FAIL" in cap.err
    last = json.loads(cap.out.strip().splitlines()[-1])
    assert last["ok"] is False and last["worst_burn_slow"] >= 4.0


def test_slo_report_gate_passes_clean_corpus(tmp_path, capsys):
    led = str(tmp_path / "led.jsonl")
    _write_corpus(led)
    rc = slo_report.main(["gate", led, "--availability-objective",
                          "0.999", "--latency-bar-ms", "100"])
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is True and last["objectives"] == 2


def test_slo_report_gate_refuses_vacuous_green(tmp_path, capsys):
    led = str(tmp_path / "empty.jsonl")
    open(led, "w").close()
    rc = slo_report.main(["gate", led, "--availability-objective",
                          "0.999"])
    assert rc == 1
    assert "vacuous" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the wired cluster: /debug index, /debug/incidents, webapp panel
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def slo_cluster(tmp_path_factory):
    from pinot_tpu.cluster import BrokerNode, Controller, ServerNode
    tmp = tmp_path_factory.mktemp("slo_cluster")
    ctrl = Controller(str(tmp / "ctrl"), heartbeat_timeout=5.0,
                      reconcile_interval=0.2)
    server = ServerNode("server_0", ctrl.url, poll_interval=0.1)
    broker = BrokerNode(ctrl.url, routing_refresh=0.1,
                        query_stats_path=str(tmp / "stats.jsonl"))
    rng = np.random.default_rng(7)
    cols = {"v": rng.integers(0, 50, 64).astype(np.int32)}
    schema = Schema("st", [FieldSpec("v", DataType.INT,
                                     FieldType.METRIC)])
    ctrl.add_table("st", schema.to_dict())
    seg = SegmentBuilder(schema, TableConfig("st")).build(
        cols, str(tmp), "s0")
    ctrl.add_segment("st", "s0", seg)
    v = ctrl.routing_snapshot()["version"]
    assert server.wait_for_version(v, timeout=30.0)
    assert broker.wait_for_version(v, timeout=30.0)
    yield ctrl, server, broker
    broker.stop()
    server.stop()
    ctrl.stop()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


def test_debug_index_per_role(slo_cluster):
    ctrl, server, broker = slo_cluster
    b = _get(f"{broker.url}/debug")
    assert b["role"] == "broker"
    assert {"/debug/queries", "/debug/compile", "/debug/slo",
            "/debug/incidents", "/debug/ledger",
            "/debug/memory"} <= set(b["surfaces"])
    s = _get(f"{server.url}/debug")
    assert s["role"] == "server"
    assert "/debug/incidents" in s["surfaces"]
    assert "/debug/queries" not in s["surfaces"]   # truthful per role
    c = _get(f"{ctrl.url}/debug")
    assert c["role"] == "controller"
    assert set(c["surfaces"]) == {"/debug/fleet", "/debug/incidents",
                                  "/debug/rebalance", "/debug/autopsy"}


def test_live_burn_alert_incident_over_http(slo_cluster):
    from pinot_tpu.utils.slo import global_incidents, global_slo
    ctrl, server, broker = slo_cluster
    global_slo.set_objective("st", "availability", objective=0.9,
                             fast_s=30.0, slow_s=60.0,
                             burn_threshold=2.0)
    sql = "SELECT COUNT(*) FROM st"
    for i in range(6):
        broker.query(f"{sql} OPTION(queryId=slo_ok_{i})")
    # /debug/slo serves the live burn table before any burn
    blk = _get(f"{broker.url}/debug/slo")
    assert blk["armed"] and blk["objectives"][0]["burn_slow"] == 0.0
    # 6 failing of 12: burn (0.5/0.1) = 5.0x in both windows => page
    for i in range(6):
        try:
            broker.query(
                f"SELECT nope FROM st OPTION(queryId=slo_bad_{i})")
        except Exception:
            pass
    assert global_incidents.drain(5.0)
    blk = _get(f"{broker.url}/debug/slo")
    row = blk["objectives"][0]
    assert row["alerting"] is True and row["burn_slow"] >= 2.0
    inc = _get(f"{broker.url}/debug/incidents")
    assert inc["count"] >= 1
    first = inc["incidents"][0]
    assert uledger.validate_record(first) == []
    assert "slow_queries" in first["surfaces"]
    # the broker /metrics health block carries the same table
    m = _get(f"{broker.url}/metrics")
    assert m["slo"]["objectives"][0]["scope"] == "st"
    # the fleet rollup aggregates it (proc-deduped, worst replica)
    rollup = ctrl.rollup.run()
    assert uledger.validate_record(rollup) == []
    slo = rollup["slo"]
    assert slo["armed"] and slo["open_incidents"] >= 1
    assert any(r["scope"] == "st" and r["alerting"]
               for r in slo["objectives"])


class _CountingLock:
    """``with``-compatible stand-in for SloPlane._lock that counts
    acquisitions."""

    def __init__(self):
        self.acquired = 0
        self._lock = threading.Lock()

    def __enter__(self):
        self.acquired += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def test_unarmed_hot_path_overhead_under_one_percent(slo_cluster,
                                                     monkeypatch,
                                                     count_calls):
    """What "unarmed" means, in work done and not in seconds: with no
    objective declared, every query still reaches the hook
    (``observe_query`` once a query) and the hook does nothing past the
    armed check — no classification or objective evaluation, no event
    ingested, no alert fired, no status or incident record, and the
    plane's lock never taken. One armed query then moves the same
    counters, so the spies are shown to see the work."""
    from pinot_tpu.utils import slo as slo_mod
    from pinot_tpu.utils.slo import global_slo
    _ctrl, _server, broker = slo_cluster
    assert not global_slo.armed            # conftest cleared objectives
    calls = count_calls(global_slo, "observe_query", "_ingest",
                        "_evaluate", "_emit_status")
    count_calls(slo_mod, "classify_query", "evaluate_objective")
    count_calls(global_slo.alerts, "fire")
    count_calls(global_slo.recorder, "request")
    lock = _CountingLock()
    monkeypatch.setattr(global_slo, "_lock", lock)

    sql = "SELECT COUNT(*) FROM st OPTION(queryId=slo_ovh)"
    n = 12
    for _ in range(n):
        broker.query(sql)
    assert calls == {"observe_query": n}, dict(calls)
    assert lock.acquired == 0

    try:
        global_slo.set_objective("st", "availability", objective=0.99)
        before = lock.acquired
        broker.query(sql)
        assert calls["classify_query"] == 1 and calls["_ingest"] == 1
        assert calls["_evaluate"] == 1
        assert calls["evaluate_objective"] == 1
        assert lock.acquired > before
    finally:
        global_slo.clear()


def test_webapp_renders_slo_panel(slo_cluster):
    ctrl, _server, _broker = slo_cluster
    with urllib.request.urlopen(f"{ctrl.url}/ui", timeout=10) as r:
        page = r.read().decode()
    for marker in ("SLO error budgets", "budget left", "open incidents",
                   "/debug/incidents"):
        assert marker in page, marker


# ---------------------------------------------------------------------------
# satellite 1: the compile-storm detector rides the generic plane
# ---------------------------------------------------------------------------

def test_compile_storm_uses_generic_alert_plane():
    from pinot_tpu.utils.alerts import global_alerts
    from pinot_tpu.utils.compileplane import global_compile_log
    rule = global_alerts.rule("compile_storm")
    assert rule is not None, "storm rule must live on the shared manager"
    assert rule is global_compile_log._storm_rule
    # the shared RateWindowRule fires once per crossing and latches
    fire = None
    for i in range(20):
        fire, _rate = rule.note(float(i) * 0.01, tag="retrace",
                                count=True, watermark=5)
        if fire:
            break
    assert fire is not None and fire["rate"] >= 5
    again, _ = rule.note(0.2, tag="retrace", count=True, watermark=5)
    assert again is None, "latched: one alert per crossing"
