"""Round-14 observability: the fleet forensics rollup plane.

Contract under test (ISSUE 9 acceptance):
- ledger shipping: incremental ``GET /debug/ledger?since=<seq>`` on
  brokers/servers, controller ForensicsRollupTask pulls + re-validates
  + node-stamps into the fleet ledger, a dead broker is skipped and
  counted and per-table query totals exactly equal the sum of the
  surviving brokers' query_stats rows;
- rollup math: hand-built per-broker ledgers aggregate to an
  independently computed oracle (counts, percentiles, heat ranking with
  per-process dedupe), and check_ledger reports the new
  ``fleet_rollup`` kind;
- fleet span-diff: ``span_diff.py check --fleet`` calibrates PER NODE
  (a uniformly 3x-slower node never false-trips; one node's one-phase
  2x regression does, tagged with the node);
- environment pinning: ``check`` fails loudly (exit 3) on a baseline/
  environment mismatch;
- device-memory telemetry: ``GET /debug/memory`` live-byte gauges
  reconcile with cache entry counts across an eviction, for the
  segment-column, stack-cache and cube-cache pools.
"""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from pinot_tpu.broker import Broker  # noqa: E402
from pinot_tpu.cluster import (BrokerNode, Controller,  # noqa: E402
                               ServerNode)
from pinot_tpu.cluster.forensics import (ledger_debug_payload,  # noqa: E402
                                         parse_since,
                                         read_ledger_since)
from pinot_tpu.cluster.http_util import http_json  # noqa: E402
from pinot_tpu.cluster.rollup import (aggregate_tables,  # noqa: E402
                                      fleet_totals, merge_heat,
                                      slow_queries)
from pinot_tpu.segment import SegmentBuilder  # noqa: E402
from pinot_tpu.server import TableDataManager  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, FieldType,  # noqa: E402
                           Schema, TableConfig)
from pinot_tpu.utils import ledger as uledger  # noqa: E402
from pinot_tpu.utils.devmem import (global_device_memory,  # noqa: E402
                                    nbytes_of)
from pinot_tpu.utils.heat import global_segment_heat  # noqa: E402

import span_diff  # noqa: E402  (tools/ on sys.path, chaos_smoke-style)


# ---------------------------------------------------------------------------
# ledger shipping primitives
# ---------------------------------------------------------------------------

def _stats_rec(table, wall_ms, ts="2026-08-04T10:00:00Z", **kw):
    fields = {"qid": kw.pop("qid", "q%s" % wall_ms), "table": table,
              "wall_ms": wall_ms, "partial": kw.pop("partial", False),
              "servers_queried": 1, "servers_responded": 1,
              "exception_codes": [], "ts": ts}
    fields.update(kw)
    return uledger.make_record("query_stats", **fields)


def test_read_ledger_since_incremental(tmp_path):
    path = str(tmp_path / "led.jsonl")
    for i in range(5):
        uledger.append_record(_stats_rec("t", float(i)), path)
    recs, seq = read_ledger_since(path, 0)
    assert len(recs) == 5 and seq == 5
    recs, seq = read_ledger_since(path, 3)
    assert [r["wall_ms"] for r in recs] == [3.0, 4.0] and seq == 5
    # cursor at (or past) the end: nothing to ship, nextSeq = truth
    recs, seq = read_ledger_since(path, 5)
    assert recs == [] and seq == 5
    recs, seq = read_ledger_since(path, 99)
    assert recs == [] and seq == 5
    assert read_ledger_since(None, 0) == ([], 0)


def test_parse_since():
    assert parse_since("/debug/ledger") == 0
    assert parse_since("/debug/ledger?since=7") == 7
    assert parse_since("/debug/ledger?since=-3") == 0
    assert parse_since("/debug/ledger?since=abc") == 0


def test_ledger_debug_payload_blocks(tmp_path):
    path = str(tmp_path / "led.jsonl")
    uledger.append_record(_stats_rec("t", 1.0), path)
    p = ledger_debug_payload("node_x", "broker", path, 0)
    assert p["node"] == "node_x" and p["role"] == "broker"
    assert p["proc"] and p["nextSeq"] == 1 and len(p["records"]) == 1
    # the one-pull-gathers-everything blocks
    for key in ("counters", "gauges", "batching", "memory", "heat"):
        assert key in p


def test_fleet_rollup_kind_contract():
    rec = uledger.make_record("fleet_rollup", nodes_polled=2,
                              nodes_skipped=1, records_pulled=3,
                              tables={})
    assert not uledger.validate_record(rec)
    with pytest.raises(ValueError):
        uledger.make_record("fleet_rollup", nodes_polled=2)
    # `node` is envelope-level provenance: every kind may carry it
    stamped = dict(_stats_rec("t", 1.0), node="broker_1")
    assert not uledger.validate_record(stamped)


# ---------------------------------------------------------------------------
# rollup math vs an independently computed oracle
# ---------------------------------------------------------------------------

def test_aggregate_tables_matches_oracle():
    rng = np.random.default_rng(14)
    walls = {"a": sorted(rng.uniform(1, 400, 37)),
             "b": sorted(rng.uniform(5, 50, 11))}
    records = []
    for t, ws in walls.items():
        for i, w in enumerate(ws):
            records.append(_stats_rec(
                t, round(float(w), 3), qid=f"{t}{i}",
                ts=f"2026-08-04T10:00:{i % 30:02d}Z",
                partial=(i % 5 == 0), hedges=i % 3, failovers=i % 2,
                rows=i, **({"slow": True} if i % 7 == 0 else {}),
                **({"batched": 2, "batch_size": 4}
                   if i % 4 == 0 else {})))
    records.append(uledger.make_record(
        "ingest_stats", table="a", rows=100, rows_per_s=10.0,
        freshness_ms=123.4, commits=1, commit_retries=0,
        faults_fired=0))
    got = aggregate_tables(records)
    for t, ws in walls.items():
        n = len(ws)
        s = sorted(round(float(w), 3) for w in ws)
        e = got[t]
        assert e["queries"] == n
        assert e["p50_ms"] == round(s[n // 2], 3)
        assert e["p99_ms"] == round(s[min(n - 1, int(n * 0.99))], 3)
        assert e["partial"] == sum(1 for i in range(n) if i % 5 == 0)
        assert e["slow"] == sum(1 for i in range(n) if i % 7 == 0)
        assert e["hedges"] == sum(i % 3 for i in range(n))
        assert e["failovers"] == sum(i % 2 for i in range(n))
        assert e["batched"] == sum(2 for i in range(n) if i % 4 == 0)
        assert e["batched_queries"] == sum(1 for i in range(n)
                                           if i % 4 == 0)
        assert e["rows"] == sum(range(n))
        assert e["partial_ratio"] == round(e["partial"] / n, 4)
        # qps over the observed ts window (1s envelope resolution)
        span = max(min(29, n - 1), 1)
        assert e["qps"] == round(n / span, 3)
    assert got["a"]["freshness_ms"] == 123.4
    assert "freshness_ms" not in got["b"]


def test_slow_queries_ranking():
    records = [dict(_stats_rec("t", w, qid=f"q{w}"), node=f"n{w}")
               for w in (5.0, 500.0, 50.0)]
    top = slow_queries(records, top=2)
    assert [r["wall_ms"] for r in top] == [500.0, 50.0]
    assert top[0]["node"] == "n500.0"


def test_merge_heat_dedupes_shared_process():
    heat = [{"table": "t", "segment": "s0", "touches": 4,
             "rows_scanned": 400, "device_hits": 6, "device_misses": 2},
            {"table": "t", "segment": "s1", "touches": 1,
             "rows_scanned": 100, "device_hits": 0, "device_misses": 1}]
    # broker+server in ONE process (same proc token) report the SAME
    # registry: dedupe, never double-count
    same_proc = {"b1": {"proc": "p1", "heat": heat},
                 "s1": {"proc": "p1", "heat": heat}}
    merged = merge_heat(same_proc)
    assert merged[0] == {"table": "t", "segment": "s0", "touches": 4,
                         "rows_scanned": 400, "device_hits": 6,
                         "device_misses": 2, "device_hit_ratio": 0.75}
    # two real processes hosting replicas: touches are additive
    two_proc = {"b1": {"proc": "p1", "heat": heat},
                "s1": {"proc": "p2", "heat": heat}}
    merged = merge_heat(two_proc)
    assert merged[0]["touches"] == 8
    assert merged[0]["device_hit_ratio"] == 0.75
    # ranking: hottest first
    assert [m["segment"] for m in merged] == ["s0", "s1"]


def test_fleet_totals_unique_process_sum():
    blk = {"counters": {"plan_cache_retraces": 3,
                        "batched_dispatches": 7},
           "memory": {"total": {"bytes": 1000, "entries": 2,
                                "evictions": 0}}}
    same = fleet_totals({"a": dict(blk, proc="p1"),
                         "b": dict(blk, proc="p1")})
    assert same["plan_cache_retraces"] == 3
    assert same["device_bytes"] == 1000
    two = fleet_totals({"a": dict(blk, proc="p1"),
                        "b": dict(blk, proc="p2")})
    assert two["plan_cache_retraces"] == 6
    assert two["device_bytes"] == 2000


def test_check_ledger_reports_fleet_rollup_kind(tmp_path, capsys):
    import check_ledger
    path = str(tmp_path / "fleet.jsonl")
    uledger.append_record(_stats_rec("t", 1.0), path)
    uledger.append_record(uledger.make_record(
        "fleet_rollup", nodes_polled=1, nodes_skipped=0,
        records_pulled=1, tables={"t": {"queries": 1}}), path)
    assert check_ledger.check(path) == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert summary["kinds"] == {"query_stats": 1, "fleet_rollup": 1}


# ---------------------------------------------------------------------------
# multi-node smoke: the acceptance pin
# ---------------------------------------------------------------------------

def _make_fleet(tmp_path, n_brokers):
    # (the autouse conftest fixture resets the process-global heat
    # registry between tests, so earlier tests' hotter segments can't
    # crowd "ft" out of the top-N rankings this smoke asserts on)
    schema = Schema("ft", [
        FieldSpec("k", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC)])
    ctrl = Controller(str(tmp_path / "ctrl"), heartbeat_timeout=5.0,
                      reconcile_interval=0.2)
    srv = ServerNode("server_0", ctrl.url, poll_interval=0.1)
    brokers = [BrokerNode(ctrl.url, routing_refresh=0.1,
                          query_stats_path=str(tmp_path / f"b{i}.jsonl"),
                          trace_ratio=1.0,
                          instance_id=f"broker_{i}")
               for i in range(n_brokers)]
    ctrl.add_table("ft", schema.to_dict(), replication=1)
    d = SegmentBuilder(schema, TableConfig("ft")).build(
        {"k": (np.arange(200, dtype=np.int32) % 7),
         "v": np.arange(200, dtype=np.int32)},
        str(tmp_path / "ft"), "s0")
    ctrl.add_segment("ft", "s0", d)
    v = ctrl.routing_snapshot()["version"]
    assert srv.wait_for_version(v, timeout=30.0)
    for b in brokers:
        assert b.wait_for_version(v, timeout=30.0)
    try:
        yield ctrl, srv, brokers
    finally:
        for b in brokers:
            try:
                b.stop()
            except Exception:
                pass
        try:
            srv.stop()
        except Exception:
            pass
        ctrl.stop()


@pytest.fixture()
def fleet(tmp_path):
    yield from _make_fleet(tmp_path, n_brokers=2)


@pytest.fixture()
def fleet1(tmp_path):
    # the single-broker variant for tier-1 tests that only drive one
    # broker — the 2-broker spin-up/teardown stays on the slow smoke
    yield from _make_fleet(tmp_path, n_brokers=1)


SMOKE_SQL = ("SELECT k, SUM(v) FROM ft GROUP BY k ORDER BY k LIMIT 10 "
             "OPTION(timeoutMs=60000)")


def _count_stats(path):
    out = {}
    if not os.path.exists(path):
        return out
    for line in open(path):
        rec = json.loads(line)
        if rec.get("kind") == "query_stats":
            out[rec["table"]] = out.get(rec["table"], 0) + 1
    return out


@pytest.mark.slow
def test_fleet_rollup_multi_node_smoke(fleet):
    ctrl, srv, (b1, b2) = fleet
    for b, n in ((b1, 3), (b2, 2)):
        for _ in range(n):
            http_json("POST", f"{b.url}/query/sql",
                      {"sql": SMOKE_SQL}, timeout=60.0)
    # brokers registered with the controller (role broker, live)
    inst = {i["id"]: i for i in http_json(
        "GET", f"{ctrl.url}/instances")["instances"]}
    assert inst["broker_0"]["role"] == "broker"
    assert inst["broker_0"]["live"] and inst["broker_1"]["live"]

    # kill broker_1 BEFORE any pull: a dead node must be skipped and
    # counted, and its rows must never reach the fleet totals
    b2.stop()
    rollup = ctrl.rollup.run()
    assert not uledger.validate_record(rollup)
    assert rollup["nodes_skipped"] >= 1
    assert "broker_1" in rollup["skipped_nodes"]
    # exactness: per-table totals == sum of SURVIVING brokers' rows
    expected = _count_stats(b1.forensics.ledger_path)
    assert expected == {"ft": 3}
    got = {t: s["queries"] for t, s in rollup["tables"].items()}
    assert got == expected
    # per-node blocks + fleet heat made it into the record
    assert "broker_0" in rollup["nodes"] and "server_0" in rollup["nodes"]
    assert any(h["table"] == "ft" for h in rollup["heat"])

    # the fleet ledger is contract-valid end to end, traces included
    res = uledger.validate_file(ctrl.rollup.ledger_path)
    assert not res["errors"], res["errors"][:3]
    assert res["kinds"]["query_stats"] == 3
    assert res["kinds"]["query_trace"] == 3
    assert res["kinds"]["fleet_rollup"] == 1
    # node provenance stamped onto every pulled record
    for line in open(ctrl.rollup.ledger_path):
        rec = json.loads(line)
        if rec["kind"] != "fleet_rollup":
            assert rec["node"] == "broker_0"

    # served at GET /debug/fleet
    snap = http_json("GET", f"{ctrl.url}/debug/fleet")
    assert snap["rollup"]["records_pulled"] == rollup["records_pulled"]
    assert snap["cursors"]["broker_0"] >= 6  # 3 stats + 3 traces

    # incremental: new queries pull ONLY the delta, totals track exactly
    for _ in range(2):
        http_json("POST", f"{b1.url}/query/sql", {"sql": SMOKE_SQL},
                  timeout=60.0)
    rollup2 = ctrl.rollup.run()
    assert rollup2["records_pulled"] == 4   # 2 stats + 2 traces
    assert rollup2["tables"]["ft"]["queries"] == 5
    # the webapp renders the fleet view off this snapshot
    assert "Fleet forensics" in ctrl.ui_page()


def test_rollup_never_wedges_on_unreachable_node(tmp_path):
    ctrl = Controller(str(tmp_path / "ctrl"), heartbeat_timeout=60.0)
    try:
        # a registered node whose port nothing listens on: the pull
        # must fail fast (bounded timeout), count it, and carry on
        http_json("POST", f"{ctrl.url}/instances",
                  {"id": "ghost", "host": "127.0.0.1", "port": 9,
                   "role": "broker"})
        rollup = ctrl.rollup.run()
        assert rollup["nodes_polled"] == 1
        assert rollup["nodes_skipped"] == 1
        assert rollup["skipped_nodes"] == ["ghost"]
    finally:
        ctrl.stop()


# ---------------------------------------------------------------------------
# device-memory telemetry: /debug/memory reconciles across an eviction
# ---------------------------------------------------------------------------

def test_debug_memory_reconciles_across_eviction(fleet1):
    ctrl, srv, (b1,) = fleet1
    http_json("POST", f"{b1.url}/query/sql", {"sql": SMOKE_SQL},
              timeout=60.0)
    seg = srv._tables["ft"].acquire_segments()[0]
    assert seg._device, "query should have device-cached columns"
    seg_bytes = sum(int(a.nbytes) for a in seg._device.values())
    n_entries = len(seg._device)

    before = http_json("GET", f"{srv.url}/debug/memory")
    pool0 = before["pools"]["segment_cols"]
    # live-byte gauge == sum of tracked entries (the registry invariant)
    from pinot_tpu.utils.metrics import global_metrics
    gauges = global_metrics.snapshot()["gauges"]
    assert gauges["device_bytes_segment_cols"] == pool0["bytes"]
    assert gauges["device_entries_segment_cols"] == pool0["entries"]
    assert pool0["bytes"] >= seg_bytes
    assert pool0["entries"] >= n_entries

    seg.evict_device()
    after = http_json("GET", f"{srv.url}/debug/memory")
    pool1 = after["pools"]["segment_cols"]
    assert pool1["bytes"] == pool0["bytes"] - seg_bytes
    assert pool1["entries"] == pool0["entries"] - n_entries
    assert pool1["evictions"] == pool0["evictions"] + n_entries
    gauges = global_metrics.snapshot()["gauges"]
    assert gauges["device_bytes_segment_cols"] == pool1["bytes"]


def test_stack_cache_pool_tracks_bytes():
    from pinot_tpu.engine import batch as eb
    key0 = set(eb._STACK_CACHE)
    b = Broker()
    dm = TableDataManager("stk")
    schema = Schema("stk", [FieldSpec("k", DataType.INT),
                            FieldSpec("v", DataType.INT,
                                      FieldType.METRIC)])
    builder = SegmentBuilder(schema, TableConfig("stk"))
    import tempfile
    tmp = tempfile.mkdtemp(prefix="ptpu_stk_")
    for i in range(2):
        dm.add_segment_dir(builder.build(
            {"k": (np.arange(300, dtype=np.int32) % 4),
             "v": np.arange(300, dtype=np.int32)}, tmp, f"stk_{i}"))
    b.register_table(dm)
    b.query("SELECT k, SUM(v) FROM stk GROUP BY k ORDER BY k LIMIT 10")
    new_keys = set(eb._STACK_CACHE) - key0
    assert new_keys, "2-segment dense group-by should stack"
    for key in new_keys:
        tracked = global_device_memory._pools["stack_cache"][key]
        assert tracked == sum(int(c.nbytes)
                              for c in eb._STACK_CACHE[key][1])
    ev0 = global_device_memory.snapshot()["stack_cache"]["evictions"]
    for seg in dm.acquire_segments():
        eb.evict_stacks_containing(seg.name)
    snap = global_device_memory.snapshot()["stack_cache"]
    assert snap["evictions"] == ev0 + len(new_keys)
    for key in new_keys:
        assert key not in global_device_memory._pools.get(
            "stack_cache", {})


def test_cube_cache_pool_tracks_bytes():
    import jax.numpy as jnp

    from pinot_tpu.ops.plan_cache import CubeCache

    class FakeSeg:
        uid, name = 987654, "cube_seg"

    cache = CubeCache()
    built = {"cnt": jnp.ones((64,), jnp.int64)}
    out = cache.entry(("spec",), FakeSeg(), lambda: built)
    assert out is built
    key = (("spec",), FakeSeg.uid, FakeSeg.name)
    assert global_device_memory._pools["cube_cache"][key] == \
        nbytes_of(built)
    cache.evict_containing("cube_seg")
    assert key not in global_device_memory._pools["cube_cache"]


# ---------------------------------------------------------------------------
# segment heat
# ---------------------------------------------------------------------------

def test_segment_heat_touches_and_device_hit_ratio(tmp_path):
    schema = Schema("hot", [FieldSpec("k", DataType.INT),
                            FieldSpec("v", DataType.INT,
                                      FieldType.METRIC)])
    d = SegmentBuilder(schema, TableConfig("hot")).build(
        {"k": (np.arange(128, dtype=np.int32) % 3),
         "v": np.arange(128, dtype=np.int32)}, str(tmp_path), "h0")
    dm = TableDataManager("hot")
    dm.add_segment_dir(d)
    b = Broker()
    b.register_table(dm)
    sql = "SELECT k, SUM(v) FROM hot GROUP BY k ORDER BY k LIMIT 5"
    b.query(sql)
    b.query(sql)
    rows = [e for e in global_segment_heat.snapshot()
            if e["segment"] == "h0"]
    assert len(rows) == 1
    e = rows[0]
    assert e["table"] == "hot" and e["touches"] == 2
    assert e["rows_scanned"] == 2 * 128
    # first query uploads (misses), the second reads warm (hits)
    assert e["device_misses"] >= 1 and e["device_hits"] >= 1
    assert 0.0 < e["device_hit_ratio"] < 1.0


# ---------------------------------------------------------------------------
# fleet span-diff: per-node calibration + environment pinning
# ---------------------------------------------------------------------------

def _synth_traces(path, node, scale=1.0, slow_phase=None,
                  slow_shape=None, iters=3):
    """Deterministic query_trace records synthesized FROM the checked-in
    baseline's own shapes (sql + per-phase medians), so the diff math is
    exercised without an engine capture."""
    with open(span_diff.DEFAULT_BASELINE) as fh:
        shapes = json.load(fh)["shapes"]
    with open(path, "a") as fh:
        for k, s in sorted(shapes.items()):
            for _ in range(iters):
                children = []
                for name, p in s["phases"].items():
                    ms = p["ms"] * scale
                    if slow_phase == name and slow_shape == k:
                        ms *= 2.0
                    children.append({"name": name, "ms": ms,
                                     "children": []})
                # wall = the baseline's own wall scaled (phases never
                # sum to the wall — broker residual), so calibration
                # recovers `scale` exactly
                root = {"name": "query", "ms": s["wall_ms"] * scale,
                        "children": children}
                rec = {"v": 2, "ts": "2026-08-04T10:00:00Z",
                       "kind": "query_trace", "backend": "cpu",
                       "sql": s["sql"], "root": root, "node": node}
                fh.write(json.dumps(rec) + "\n")


def test_fleet_check_per_node_calibration(tmp_path, capsys):
    led = str(tmp_path / "fleet.jsonl")
    _synth_traces(led, "broker_a", scale=1.0)
    _synth_traces(led, "broker_b", scale=3.0)   # uniformly slower node
    rc = span_diff.main(["check", "--fleet", led])
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert rc == 0, summary
    assert summary["fleet"] is True
    assert summary["nodes"]["broker_a"]["calibration"] == \
        pytest.approx(1.0, abs=0.05)
    # the slower node's calibration absorbed the uniform 3x — a single
    # global calibration would have read ~1.7x and tripped the bar
    assert summary["nodes"]["broker_b"]["calibration"] == \
        pytest.approx(3.0, abs=0.15)
    assert summary["nodes"]["broker_b"]["checked_phases"] >= 1


def test_fleet_check_flags_one_nodes_phase(tmp_path, capsys):
    with open(span_diff.DEFAULT_BASELINE) as fh:
        base = json.load(fh)["shapes"]
    # pick a shape whose execution phase clears the min-ms floor
    shape = max(base, key=lambda k: base[k]["phases"]
                .get("execution", {}).get("ms", 0.0))
    led = str(tmp_path / "fleet.jsonl")
    _synth_traces(led, "broker_a", scale=1.0)
    _synth_traces(led, "broker_b", scale=3.0, slow_phase="execution",
                  slow_shape=shape)
    rc = span_diff.main(["check", "--fleet", led])
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert rc == 1, summary
    regs = summary["regressions"]
    assert regs and all(r["node"] == "broker_b" for r in regs)
    assert any(r["shape"] == shape and r["phase"] == "execution"
               for r in regs)


def test_env_mismatch_fails_loudly(tmp_path, capsys):
    led = str(tmp_path / "trace.jsonl")
    _synth_traces(led, "x")
    bad = str(tmp_path / "baseline.json")
    with open(span_diff.DEFAULT_BASELINE) as fh:
        data = json.load(fh)
    data["env"] = {"jax_platforms": "tpu", "x64": True,
                   "backend": "tpu"}
    with open(bad, "w") as fh:
        json.dump(data, fh)
    rc = span_diff.main(["check", led, "--baseline", bad])
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert rc == span_diff.EXIT_ENV_MISMATCH
    assert summary["env_mismatch"]["jax_platforms"] == ["tpu", "cpu"]
    # a legacy baseline WITHOUT an env header stays checkable
    del data["env"]
    with open(bad, "w") as fh:
        json.dump(data, fh)
    assert span_diff.main(["check", led, "--baseline", bad]) == 0
    capsys.readouterr()


def test_update_stamps_env_header(tmp_path, capsys):
    led = str(tmp_path / "trace.jsonl")
    _synth_traces(led, "x")
    out_baseline = str(tmp_path / "new_baseline.json")
    rc = span_diff.main(["update", led, "--baseline", out_baseline])
    capsys.readouterr()
    assert rc == 0
    with open(out_baseline) as fh:
        data = json.load(fh)
    assert data["env"] == {"jax_platforms": "cpu", "x64": True,
                           "backend": "cpu"}
    # refuse to stamp an env that contradicts the records' backend
    _synth_traces(led, "x")
    for line in open(led):
        pass
    with open(led, "a") as fh:
        rec = json.loads(line)
        rec["backend"] = "tpu"
        fh.write(json.dumps(rec) + "\n")
    rc = span_diff.main(["update", led, "--baseline", out_baseline])
    capsys.readouterr()
    assert rc == 2


# ---------------------------------------------------------------------------
# round-15 concurrency fix pin (concur CC201): rollup cursor guard
# ---------------------------------------------------------------------------

def test_rollup_cursor_mutation_holds_serving_lock(tmp_path, monkeypatch):
    """The per-node pull cursors are SERVED by snapshot() (GET
    /debug/fleet copies the dict under ``_lock``) while ``_run_locked``
    advances them mid-pass under ``_run_lock`` only — two different
    locks guarding one dict (concur CC201 mixed-guard), so a /debug/
    fleet hit during a pull could observe a resizing dict and raise.
    Pinned by lock-assertion: every cursor mutation must hold the
    serving lock."""
    import threading
    import time as _time

    from pinot_tpu.cluster import rollup as R

    class _Ctrl:
        def __init__(self):
            self._lock = threading.RLock()
            self.heartbeat_timeout = 60.0
            self._instances = {
                "b1": {"id": "b1", "role": "broker", "host": "h",
                       "port": 12345,
                       "lastHeartbeat": _time.monotonic()}}

    task = R.ForensicsRollupTask(
        _Ctrl(), ledger_path=str(tmp_path / "fleet_ledger.jsonl"))

    class _Guarded(dict):
        def __setitem__(self, key, value):
            assert task._lock.locked(), \
                "rollup cursor mutated without the serving lock"
            dict.__setitem__(self, key, value)

    task._cursors = _Guarded()
    monkeypatch.setattr(
        R, "http_json",
        lambda *a, **k: {"records": [], "nextSeq": 7, "role": "broker",
                        "proc": "p1"})
    task.run()
    assert dict(task._cursors) == {"b1": 7}
    # the served copy agrees and is taken under the same lock
    assert task.snapshot()["cursors"] == {"b1": 7}
