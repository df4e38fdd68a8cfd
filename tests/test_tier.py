"""HBM-tiered segment store (ISSUE 13, engine/tier.py).

- heat decay: a recently-touched small segment outranks an
  anciently-scanned big one (the eviction-ranking fix);
- tier state machine: the same heat/admission sequence produces the
  same promote/demote decision log (determinism contract);
- digest equality: an SSB query answers byte-identically from hot,
  warm and cold placement, with promotions counted;
- constrained budget vs the evict-all strawman: strictly fewer uploads,
  demotions fire, and every devmem pool reconciles to the byte;
- chaos: ``tools/chaos_smoke.py --tier`` (mid-query tier.evict
  recovery, same-seed stream determinism, budget churn reconciliation);
- placement-aware routing over a live 2-server cluster: residency rides
  heartbeats into the routing snapshot, the adaptive selector sticks to
  the hot replica (tier_affinity_hits rising, zero new uploads), the
  balanced selector keeps paying uploads, and /debug/memory stays
  reconciled across a demote/promote cycle over HTTP.
"""
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from pinot_tpu.broker import Broker  # noqa: E402
from pinot_tpu.broker.routing import AdaptiveServerSelector  # noqa: E402
from pinot_tpu.cluster import (BrokerNode, Controller,  # noqa: E402
                               ServerNode)
from pinot_tpu.cluster.http_util import http_json  # noqa: E402
from pinot_tpu.engine.tier import (TIER_COLD, TIER_HOT,  # noqa: E402
                                   TIER_WARM, TierManager, global_tier,
                                   reconcile_devmem, segment_tier)
from pinot_tpu.segment import SegmentBuilder  # noqa: E402
from pinot_tpu.server import TableDataManager  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, FieldType,  # noqa: E402
                           Schema, TableConfig)
from pinot_tpu.tools import corpus  # noqa: E402
from pinot_tpu.utils import ledger as uledger  # noqa: E402
from pinot_tpu.utils.devmem import DeviceMemoryRegistry  # noqa: E402
from pinot_tpu.utils.devmem import global_device_memory  # noqa: E402
from pinot_tpu.utils.heat import SegmentHeat  # noqa: E402
from pinot_tpu.utils.heat import global_segment_heat  # noqa: E402
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

import chaos_smoke  # noqa: E402  (tools/ on sys.path)


class _Seg:
    """Bare segment stand-in for heat/tier unit tests."""

    def __init__(self, uid, name, devmem=None):
        self.uid = uid
        self.name = name
        self._devmem = devmem
        self._device = {}
        self._warm = {}

    def demote_device(self, drop_warm: bool = False) -> None:
        for key in list(self._device):
            self._devmem.remove("segment_cols", (self.uid, key))
        self._device.clear()
        if drop_warm:
            self._warm.clear()


# ---------------------------------------------------------------------------
# heat decay (satellite: cumulative-forever scores could pin a segment)
# ---------------------------------------------------------------------------

def test_heat_decay_recent_small_beats_ancient_big():
    h = SegmentHeat(half_life_s=10.0)
    big, small = _Seg(1, "big"), _Seg(2, "small")
    # a one-time full scan of 100M rows...
    h.touch(big, "t", rows=100_000_000, now=1000.0)
    # ...then, 100 half-lives later, one touch of a 1k-row segment
    h.touch(small, "t", rows=1_000, now=2000.0)
    scores = h.scores(now=2000.0)
    assert scores[2] > scores[1], scores
    # at the time of the big scan the ranking was the other way around
    assert h.scores(now=1000.0)[1] > h.scores(now=1000.0)[2]


def test_heat_decay_halves_per_half_life():
    h = SegmentHeat(half_life_s=10.0)
    s = _Seg(7, "s")
    h.touch(s, "t", rows=0, now=0.0)          # heat 1.0
    assert h.scores(now=0.0)[7] == pytest.approx(1.0)
    assert h.scores(now=10.0)[7] == pytest.approx(0.5)
    # a second touch folds the decayed history in at write time
    h.touch(s, "t", rows=0, now=10.0)         # 0.5 + 1.0
    assert h.scores(now=10.0)[7] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# tier state machine: deterministic decisions
# ---------------------------------------------------------------------------

def _replay(seq):
    """Feed one admission/touch sequence into a fresh private
    (devmem, heat, tier) triple; returns the decision log."""
    devmem = DeviceMemoryRegistry()
    heat = SegmentHeat(half_life_s=60.0)
    mgr = TierManager(devmem=devmem, heat=heat, budget_bytes=3000)
    segs = {i: _Seg(i, f"s{i}", devmem) for i in range(1, 6)}
    for ev in seq:
        if ev[0] == "touch":
            _, uid, rows, now = ev
            heat.touch(segs[uid], "t", rows, now=now)
        else:
            _, uid, nbytes = ev
            key = f"c{len(segs[uid]._device)}"
            segs[uid]._device[key] = None
            devmem.add("segment_cols", (uid, key), nbytes)
            mgr.admitted(segs[uid])
    return mgr


SEQ = [
    ("touch", 1, 1000, 1.0), ("admit", 1, 1000),
    ("touch", 2, 1000, 2.0), ("admit", 2, 1000),
    ("touch", 3, 1000, 3.0), ("admit", 3, 1000),
    # over budget: uid 1 is coldest -> demoted
    ("touch", 4, 1000, 4.0), ("admit", 4, 1000),
    # re-touch 2 so 3 becomes the coldest for the next admission
    ("touch", 2, 1000, 5.0),
    ("touch", 5, 1000, 6.0), ("admit", 5, 1000),
]


def test_tier_state_machine_deterministic():
    a, b = _replay(SEQ), _replay(SEQ)
    assert a.decisions == b.decisions
    demotes = [d for d in a.decisions if d[0] == "demote"]
    assert demotes, "the sequence must exercise budget demotion"
    # coldest-first: uid 1 (oldest touch) is the first victim
    assert demotes[0][1] == "s1" and demotes[0][4] == "budget"
    assert a.demotions == len(demotes)


def test_tier_demote_promote_transitions():
    devmem = DeviceMemoryRegistry()
    mgr = TierManager(devmem=devmem, heat=SegmentHeat(half_life_s=60.0))
    s = _Seg(11, "s11", devmem)
    s._device["c0"] = None
    devmem.add("segment_cols", (11, "c0"), 100)
    mgr.admitted(s)
    assert mgr.occupancy()["hot"]["segments"] == 1
    s._warm["c0"] = np.zeros(4)
    assert mgr.demote(s, TIER_WARM)
    assert not s._device and s._warm
    assert mgr.occupancy()["warm"]["segments"] == 1
    # warm -> warm is a no-op, warm -> cold drops the host arrays
    assert not mgr.demote(s, TIER_WARM)
    assert mgr.demote(s, TIER_COLD)
    assert not s._warm
    assert mgr.occupancy()["cold"]["segments"] == 1
    # cold -> hot on the next admission counts a promotion
    p0 = mgr.promotions
    s._device["c0"] = None
    devmem.add("segment_cols", (11, "c0"), 100)
    mgr.admitted(s)
    assert mgr.promotions == p0 + 1


def test_warm_budget_trims_hot_segments_stash(tmp_path):
    """PINOT_WARM_BUDGET_BYTES must be enforceable even when every
    segment stays HOT (their stashes are the warm bytes): the coldest
    hot segments' host copies drop, device residents untouched."""
    dm, _dirs = chaos_smoke.build_ssb_table(str(tmp_path), 256, 2)
    b = Broker()
    b.register_table(dm)
    global_tier.configure(budget_bytes=1 << 40)
    try:
        by_id = {q[0]: q for q in corpus.SSB_QUERIES}
        sql = corpus.spec_to_sql(*by_id["q1.1"][1:]) + \
            " OPTION(timeoutMs=300000)"
        rows = b.query(sql).rows
        segs = dm.acquire_segments()
        assert all(s._warm for s in segs), "armed runs stash warm"
        dev_before = {s.uid: dict(s._device) for s in segs}
        global_tier.configure(warm_budget_bytes=1)
        assert all(not s._warm for s in segs), \
            "warm budget should trim hot segments' stashes"
        # device residents untouched, answers identical
        assert {s.uid: dict(s._device) for s in segs} == dev_before
        assert b.query(sql).rows == rows
    finally:
        global_tier.configure(budget_bytes=None, warm_budget_bytes=None)


# ---------------------------------------------------------------------------
# digest equality hot vs warm vs cold (SSB query)
# ---------------------------------------------------------------------------

def _ssb_broker(tmp, rows=512, n_segments=2):
    dm, _dirs = chaos_smoke.build_ssb_table(str(tmp), rows, n_segments)
    b = Broker()
    b.register_table(dm)
    return b, dm


def test_digest_equal_hot_warm_cold(tmp_path):
    b, dm = _ssb_broker(tmp_path)
    by_id = {q[0]: q for q in corpus.SSB_QUERIES}
    sql = corpus.spec_to_sql(*by_id["q4.1"][1:]) + \
        " OPTION(timeoutMs=300000)"
    # arm an ample budget so warm host arrays are stashed
    global_tier.configure(budget_bytes=1 << 40)
    try:
        hot = corpus.digest([tuple(r) for r in b.query(sql).rows])
        segs = dm.acquire_segments()
        assert all(segment_tier(s) == TIER_HOT for s in segs)
        p0 = global_tier.promotions
        # demote to WARM: padded host arrays remain
        for s in segs:
            assert global_tier.demote(s, TIER_WARM)
        assert all(segment_tier(s) == TIER_WARM for s in segs)
        warm = corpus.digest([tuple(r) for r in b.query(sql).rows])
        assert warm == hot
        assert global_tier.promotions >= p0 + len(segs)
        # demote to COLD: mmap only
        for s in segs:
            assert global_tier.demote(s, TIER_COLD)
        assert all(segment_tier(s) == TIER_COLD for s in segs)
        cold = corpus.digest([tuple(r) for r in b.query(sql).rows])
        assert cold == hot
        assert global_metrics.snapshot()["counters"].get(
            "tier_promotions", 0) > 0
    finally:
        global_tier.configure(budget_bytes=None)


# ---------------------------------------------------------------------------
# constrained budget: fewer uploads than the evict-all strawman,
# devmem reconciles across the churn
# ---------------------------------------------------------------------------

def _total_uploads():
    return sum(e["device_misses"]
               for e in global_segment_heat.snapshot())


def test_constrained_budget_beats_evict_all_uploads(tmp_path):
    # start from devmem-synced caches: earlier suite tests' cube/stack
    # entries survive the per-test accounting reset (conftest fixture
    # doc) and would fail the byte-exact reconcile through no fault of
    # the tier's
    from pinot_tpu.engine.batch import clear_stack_cache
    from pinot_tpu.ops.plan_cache import global_cube_cache
    clear_stack_cache()
    global_cube_cache.clear()
    dm, _d1 = chaos_smoke.build_ssb_table(str(tmp_path), 512, 2)
    dm2, _d2 = chaos_smoke.build_ssb_table(str(tmp_path), 512, 2,
                                           table="lineorder2",
                                           seg_prefix="t2seg_")
    b = Broker()
    b.register_table(dm)
    b.register_table(dm2)
    by_id = {q[0]: q for q in corpus.SSB_QUERIES}
    mix = []
    for qid in ("q1.1", "q4.1"):
        sql = corpus.spec_to_sql(*by_id[qid][1:]) + \
            " OPTION(timeoutMs=300000)"
        mix.append((qid, "a", sql))
        mix.append((qid, "b", sql.replace("FROM lineorder ",
                                          "FROM lineorder2 ")))
    segs = dm.acquire_segments() + dm2.acquire_segments()

    def run_mix():
        return {(qid, t): corpus.digest([tuple(r)
                                         for r in b.query(sql).rows])
                for qid, t, sql in mix}

    def evict_all():
        for s in segs:
            s.evict_device()

    base = run_mix()                       # warm compiles + uploads
    peak = global_device_memory.snapshot()["total"]["bytes"]
    # strawman: evict EVERYTHING between queries (re-upload per query)
    u0 = _total_uploads()
    straw = {}
    for qid, t, sql in mix:
        evict_all()
        straw[qid, t] = corpus.digest([tuple(r)
                                       for r in b.query(sql).rows])
    straw_uploads = _total_uploads() - u0
    assert straw == base
    # tier under a budget below the two-table working set
    evict_all()
    global_tier.configure(budget_bytes=max(peak // 2, 1))
    try:
        d0 = global_tier.demotions
        run_mix()                          # settle under budget
        u1 = _total_uploads()
        tiered = run_mix()
        tier_uploads = _total_uploads() - u1
        assert tiered == base
        assert global_tier.demotions > d0, \
            "the constrained budget never demoted"
        assert tier_uploads < straw_uploads, \
            f"tier paid {tier_uploads} uploads vs strawman " \
            f"{straw_uploads}"
        # zero unaccounted devmem bytes across the demotion churn
        rec = reconcile_devmem(
            segs, pools=("segment_cols", "stack_cache", "cube_cache",
                         "cube_stacked"))
        assert all(r["tracked"] == r["actual"] for r in rec.values()), \
            rec
        # churn bounded: demotions are per-phase work, not a runaway
        assert global_tier.demotions - d0 <= 8 * len(mix)
    finally:
        global_tier.configure(budget_bytes=None)


# ---------------------------------------------------------------------------
# chaos_smoke --tier (mid-query tier.evict + same-seed determinism)
# ---------------------------------------------------------------------------

def test_chaos_smoke_tier_cli(capsys):
    import json

    import chaos_smoke as cs
    assert cs.main(["--tier", "--rows", "1024",
                    "--queries", "q1.1,q4.1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert summary["ok"] and summary["mode"] == "tier"
    assert summary["faults_fired"] >= 2     # both same-seed runs fired
    assert summary["demotions"] >= 1
    for pool, r in summary["reconcile"].items():
        assert r["tracked"] == r["actual"], (pool, r)


# ---------------------------------------------------------------------------
# placement-aware routing + /debug/memory over a live 2-server cluster
# ---------------------------------------------------------------------------

def test_adaptive_selector_placement_affinity_unit():
    sel = AdaptiveServerSelector()
    for _ in range(3):
        sel.record_start("a")
        sel.record_end("a", 10.0)
        sel.record_start("b")
        sel.record_end("b", 10.0)
    # equal latency: placement breaks the tie toward the hot holder
    picks = sel.select({"s1": ["a", "b"]}, lambda h: True,
                       placement={"s1": {"b": "hot"}})
    assert picks["s1"] == "b"
    # a never-measured replica must not out-bid a hot holder (the
    # unknown-latency default follows the known mean on this path)
    picks = sel.select({"s1": ["a", "zz_new"]}, lambda h: True,
                       placement={"s1": {"a": "hot"}})
    assert picks["s1"] == "a"
    # without placement the stock behavior stands
    assert sel.select({"s1": ["a", "b"]},
                      lambda h: True)["s1"] == "a"


@pytest.fixture()
def affinity_cluster(tmp_path):
    tmp = str(tmp_path)
    ctrl = Controller(os.path.join(tmp, "ctrl"), heartbeat_timeout=5.0,
                      reconcile_interval=0.2)
    servers = [ServerNode(f"tiersrv_{i}", ctrl.url, poll_interval=0.1)
               for i in range(2)]
    qs_path = os.path.join(tmp, "qs.jsonl")
    broker = BrokerNode(ctrl.url, routing_refresh=0.1,
                        instance_selector="adaptive",
                        query_stats_path=qs_path)
    schema = Schema("aff", [FieldSpec("k", DataType.INT),
                            FieldSpec("v", DataType.INT,
                                      FieldType.METRIC)])
    builder = SegmentBuilder(schema, TableConfig("aff"))
    ctrl.add_table("aff", schema.to_dict(), replication=2)
    for i in range(3):
        d = builder.build(
            {"k": (np.arange(256, dtype=np.int32) % 4),
             "v": np.arange(256, dtype=np.int32) + 1000 * i},
            os.path.join(tmp, "aff"), f"aseg_{i}")
        ctrl.add_segment("aff", f"aseg_{i}", d)
    v = ctrl.routing_snapshot()["version"]
    for s in servers:
        assert s.wait_for_version(v, timeout=30.0)
    assert broker.wait_for_version(v, timeout=30.0)
    yield ctrl, servers, broker, qs_path
    broker.stop()
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass
    ctrl.stop()


SQL_AFF = ("SELECT k, SUM(v) FROM aff GROUP BY k ORDER BY k LIMIT 10 "
           "OPTION(timeoutMs=60000)")


def _wait_residency(broker, segs=("aseg_0", "aseg_1", "aseg_2"),
                    timeout=10.0):
    """Wait until EVERY segment reports hot on some server (a snapshot
    mid-heartbeat can show a query's later segments still cold — the
    flow is server heartbeat -> controller -> broker refresh, each on
    its own 0.1 s cadence)."""
    res = None
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = broker._snapshot()
        res = {sid: (inst.get("residency") or {}).get("aff")
               for sid, inst in (snap.get("instances") or {}).items()}
        hot = {s for r in res.values() if r
               for s, t in r.items() if t == "hot"}
        if hot >= set(segs):
            return res
        time.sleep(0.1)
    raise AssertionError(
        f"residency never showed all segments hot: {res}")


def test_placement_affinity_routing_smoke(affinity_cluster):
    ctrl, servers, broker, qs_path = affinity_cluster
    base = http_json("POST", f"{broker.url}/query/sql",
                     {"sql": SQL_AFF}, timeout=60.0)
    base_rows = base["resultTable"]["rows"]
    assert base_rows
    # residency flows: server heartbeat -> controller -> broker snapshot
    _wait_residency(broker)
    # two stabilization queries (latency EWMAs settle), then measure
    for _ in range(2):
        http_json("POST", f"{broker.url}/query/sql", {"sql": SQL_AFF},
                  timeout=60.0)
    c0 = global_metrics.snapshot()["counters"].get(
        "tier_affinity_hits", 0)
    u0 = _total_uploads()
    for _ in range(4):
        got = http_json("POST", f"{broker.url}/query/sql",
                        {"sql": SQL_AFF}, timeout=60.0)
        assert got["resultTable"]["rows"] == base_rows
    c1 = global_metrics.snapshot()["counters"].get(
        "tier_affinity_hits", 0)
    # affinity hits rise (3 segments per query) and the hot replica
    # answers without ANY new upload
    assert c1 - c0 >= 6, (c0, c1)
    assert _total_uploads() == u0, "placement-aware routing re-uploaded"
    # the balanced selector keeps paying uploads for the same queries
    # (the other replica's copies go device-resident too)
    b2 = BrokerNode(ctrl.url, routing_refresh=0.1,
                    instance_selector="balanced")
    try:
        assert b2.wait_for_version(
            ctrl.routing_snapshot()["version"], timeout=30.0)
        u1 = _total_uploads()
        for _ in range(4):
            got = http_json("POST", f"{b2.url}/query/sql",
                            {"sql": SQL_AFF}, timeout=60.0)
            assert got["resultTable"]["rows"] == base_rows
        assert _total_uploads() > u1, \
            "balanced routing should have uploaded on the cold replica"
    finally:
        b2.stop()
    # per-query ledger trend line: tier_affinity_hits on query_stats
    lres = uledger.validate_file(qs_path)
    assert not lres["errors"], lres["errors"][:3]
    import json
    hits = [json.loads(line).get("tier_affinity_hits", 0)
            for line in open(qs_path)]
    assert any(h >= 1 for h in hits)


def test_debug_memory_reconciles_across_demote_promote(affinity_cluster):
    _ctrl, servers, broker, _qs = affinity_cluster
    http_json("POST", f"{broker.url}/query/sql", {"sql": SQL_AFF},
              timeout=60.0)
    srv = next(s for s in servers
               if any(seg._device
                      for seg in s._tables["aff"].acquire_segments()))
    seg = next(s for s in srv._tables["aff"].acquire_segments()
               if s._device)
    seg_bytes = sum(int(a.nbytes) for a in seg._device.values())

    before = http_json("GET", f"{srv.url}/debug/memory")
    pool0 = before["pools"]["segment_cols"]
    assert before["tier"]["hot"]["segments"] >= 1
    assert before["residency"]["aff"][seg.name] == "hot"
    gauges = global_metrics.snapshot()["gauges"]
    assert gauges["device_bytes_segment_cols"] == pool0["bytes"]

    # demote over the tier manager: the HTTP view must reconcile
    assert global_tier.demote(seg, TIER_WARM)
    after = http_json("GET", f"{srv.url}/debug/memory")
    pool1 = after["pools"]["segment_cols"]
    assert pool1["bytes"] == pool0["bytes"] - seg_bytes
    assert after["residency"]["aff"][seg.name] in (TIER_WARM, TIER_COLD)
    gauges = global_metrics.snapshot()["gauges"]
    assert gauges["device_bytes_segment_cols"] == pool1["bytes"]

    # the next query over HTTP transparently re-promotes — dispatched
    # at THIS server directly: the broker's affinity routing would
    # (correctly) steer around the demoted replica
    p0 = global_tier.promotions
    http_json("POST", f"{srv.url}/query", {"sql": SQL_AFF},
              timeout=60.0)
    assert global_tier.promotions > p0
    again = http_json("GET", f"{srv.url}/debug/memory")
    assert again["residency"]["aff"][seg.name] == "hot"
    assert again["pools"]["segment_cols"]["bytes"] == pool0["bytes"]

    # full evict zeroes this segment's accounting
    seg.evict_device()
    final = http_json("GET", f"{srv.url}/debug/memory")
    assert final["pools"]["segment_cols"]["bytes"] == \
        pool0["bytes"] - seg_bytes
    rec = reconcile_devmem(
        [s for sv in servers
         for s in sv._tables["aff"].acquire_segments()])
    assert rec["segment_cols"]["tracked"] == \
        rec["segment_cols"]["actual"]
