"""PR 8: cross-query micro-batching (ragged fused dispatch) suite.

Coverage per the issue checklist: fused-vs-solo digest exactness over
SSB shapes at concurrency 2-32, same-seed determinism under the chaos
fault plan, deadline-pressured queries bypassing the admission queue,
zero post-warmup retraces across the ragged pow2 ladder
(RetraceDetector-checked), per-query span attribution inside a fused
dispatch, the q4.3 sparse sorted-post contract, and the metrics/ledger
plumbing (batched/batch_size query_stats fields, /metrics block).
"""
import threading
import time

import numpy as np
import pytest

from pinot_tpu.broker import Broker
from pinot_tpu.engine.ragged import (RaggedBatcher, batching_health,
                                     cube_spec_for, global_batcher)
from pinot_tpu.ops.plan_cache import (global_cube_cache,
                                      global_plan_cache)
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)
from pinot_tpu.tools import corpus
from pinot_tpu.utils import faults
from pinot_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _batcher_defaults_after():
    """Tests flip the batcher's knobs; restore the PROCESS DEFAULT
    (enabled since round 16, PINOT_MICROBATCH=0 disables) so the rest
    of the suite runs the configuration production ships."""
    from pinot_tpu.engine.ragged import default_enabled
    yield
    global_batcher.configure(enabled=default_enabled(),
                             window_ms=4.0, max_batch=32)
    faults.clear()


def _counter(name: str) -> int:
    return global_metrics.snapshot()["counters"].get(name, 0)


# -- fixtures ---------------------------------------------------------------

N_SSB = 1 << 14


@pytest.fixture(scope="module")
def ssb(tmp_path_factory):
    seg = corpus.build_ssb_segment(N_SSB, str(tmp_path_factory.mktemp("rb")))
    dm = TableDataManager("lineorder")
    dm.add_segment(seg)
    broker = Broker()
    broker.register_table(dm)
    return seg, broker


@pytest.fixture(scope="module")
def grouped(tmp_path_factory):
    """Small table whose group-by cube fits at test scale: GROUP BY
    (g1 x g2) with predicate dims well under the row count."""
    rng = np.random.default_rng(7)
    n = 8192
    cols = {
        "g1": rng.choice([f"a{i}" for i in range(8)], n),
        "g2": rng.choice([f"b{i}" for i in range(10)], n),
        "f": rng.integers(0, 20, n).astype(np.int32),
        "v": rng.integers(0, 1000, n).astype(np.int32),
    }
    schema = Schema("grp", [
        FieldSpec("g1", DataType.STRING),
        FieldSpec("g2", DataType.STRING),
        FieldSpec("f", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    dm = TableDataManager("grp")
    dm.add_segment_dir(SegmentBuilder(schema, TableConfig("grp")).build(
        cols, str(tmp_path_factory.mktemp("grp")), "g_0"))
    broker = Broker()
    broker.register_table(dm)
    return dm, broker


def _q11(i: int) -> str:
    return (f"SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder"
            f" WHERE d_year = {1992 + i % 7}"
            f" AND lo_discount BETWEEN {i % 4} AND {i % 4 + 2}"
            f" AND lo_quantity < {20 + i % 13}")


def _grp(i: int) -> str:
    return (f"SELECT g1, g2, SUM(v), COUNT(*), AVG(v) FROM grp"
            f" WHERE f < {5 + i % 12} GROUP BY g1, g2"
            f" ORDER BY g1, g2 LIMIT 1000")


def _concurrent(broker, sqls, barrier_timeout=30):
    results = [None] * len(sqls)
    errs = []
    barrier = threading.Barrier(len(sqls))

    def run(i):
        try:
            barrier.wait(barrier_timeout)
            results[i] = broker.query(sqls[i])
        except Exception as e:  # noqa: BLE001 — surfaced in the assert
            errs.append(f"q{i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(sqls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    return results


def _warmed(broker, sqls, rounds=8):
    """Run the wave until one of its rounds meets nothing cold: since PR
    33 a cube or a fused program that is not ready sends its batch solo
    (``solo_fallback_cold``) and is made behind the queries, so the first
    rounds of a shape build and compile and a later one can fuse."""
    for _ in range(rounds):
        cold0 = _counter("solo_fallback_cold")
        results = _concurrent(broker, sqls)
        assert global_batcher.wait_ready(120.0)
        if _counter("solo_fallback_cold") == cold0:
            return results
    raise AssertionError(f"still cold after {rounds} rounds")


# -- fused-vs-solo digest exactness -----------------------------------------

@pytest.mark.parametrize("concurrency", [2, 8, 32])
def test_fused_vs_solo_digests(ssb, grouped, concurrency):
    """Plan-shape-sharing variants at concurrency 2-32: fused results
    must be byte-identical to the serial per-query dispatch path, for
    both the scalar (q1.1 shape) and grouped cube paths."""
    _seg, broker = ssb
    _dm, gbroker = grouped
    for brk, make in ((broker, _q11), (gbroker, _grp)):
        sqls = [make(i) + corpus.OPTION for i in range(concurrency)]
        global_batcher.configure(enabled=False)
        solo = [brk.query(s) for s in sqls]
        global_batcher.configure(enabled=True, window_ms=30.0,
                                 max_batch=concurrency)
        fused0 = _counter("batched_queries")
        results = _warmed(brk, sqls)
        for r, s in zip(results, solo):
            assert corpus.digest(r.rows) == corpus.digest(s.rows)
        if concurrency >= 8:
            # enough peers hit the window together to actually fuse
            assert _counter("batched_queries") > fused0


def test_ssb_corpus_under_concurrency(ssb):
    """The 13-query SSB corpus fired concurrently with batching on:
    mixed eligible/ineligible shapes all stay digest-exact (ineligible
    ones dispatch solo, counted by reason)."""
    _seg, broker = ssb
    picks = [q for q in corpus.SSB_QUERIES
             if q[0] in ("q1.1", "q2.1", "q3.1", "q4.3")]
    sqls = [corpus.spec_to_sql(p, v, g) + corpus.OPTION
            for _q, p, v, g in picks]
    global_batcher.configure(enabled=False)
    solo = [broker.query(s) for s in sqls]
    global_batcher.configure(enabled=True, window_ms=10.0)
    results = _concurrent(broker, sqls)
    for r, s in zip(results, solo):
        assert corpus.digest(r.rows) == corpus.digest(s.rows)


# -- determinism under the chaos fault plan ---------------------------------
# (runtime trim, round 17: the three-mode solo/batched/staggered parity
# soak below is slow-marked — ~11 s for a property the round-16 rekeying
# made structural. test_same_seed_determinism_under_chaos stays as the
# fast tier-1 gate: same seed + batching on => identical digests AND
# fired streams, which is the invariant every chaos soak depends on.)

def test_same_seed_determinism_under_chaos(ssb, grouped):
    """Same seed + same (barrier-synchronized) composition => identical
    digests AND identical fired fault streams with batching on. The
    fault actually fires: device.overflow forces the solo compact
    path's overflow retry ladder on the sequential (ineligible) query
    while the fused wave runs around it."""
    _seg, sbroker = ssb
    _dm, broker = grouped
    sqls = [_grp(i) + corpus.OPTION for i in range(6)]
    q21 = next(q for q in corpus.SSB_QUERIES if q[0] == "q2.1")
    solo_sql = corpus.spec_to_sql(q21[1], q21[2], q21[3]) + corpus.OPTION
    global_batcher.configure(enabled=False)
    baseline = [corpus.digest(broker.query(s).rows) for s in sqls]
    solo_base = corpus.digest(sbroker.query(solo_sql).rows)

    def chaos_run():
        plan = faults.install("seed=11; device.overflow: times=2",
                              seed=11)
        global_batcher.configure(enabled=True, window_ms=30.0)
        try:
            s1 = corpus.digest(sbroker.query(solo_sql).rows)
            results = _concurrent(broker, sqls)
            s2 = corpus.digest(sbroker.query(solo_sql).rows)
            return ([corpus.digest(r.rows) for r in results] + [s1, s2],
                    plan.fired_summary())
        finally:
            faults.clear()

    d1, f1 = chaos_run()
    d2, f2 = chaos_run()
    assert d1 == d2 == baseline + [solo_base, solo_base]
    assert f1 == f2
    assert f1, "the chaos plan never fired — the gate is vacuous"


@pytest.mark.slow
def test_chaos_streams_solo_vs_batched_vs_interleaved(ssb, grouped):
    """Round-16 acceptance (ISSUE 11): with per-query fault streams
    (utils/faults.py rekeying), a query's same-seed fired-fault stream
    is IDENTICAL whether the concurrent wave around it dispatches solo
    (batching disabled), fuses behind a barrier, or fuses with
    arbitrary staggered arrival — no barrier-deterministic composition
    required any more, which is what lets chaos soaks run with
    micro-batching on by default."""
    _seg, sbroker = ssb
    _dm, broker = grouped
    sqls = [_grp(i) + corpus.OPTION for i in range(6)]
    q21 = next(q for q in corpus.SSB_QUERIES if q[0] == "q2.1")
    solo_sql = corpus.spec_to_sql(q21[1], q21[2], q21[3]) + corpus.OPTION
    global_batcher.configure(enabled=False)
    baseline = [corpus.digest(broker.query(s).rows) for s in sqls]
    solo_base = corpus.digest(sbroker.query(solo_sql).rows)

    def chaos_run(batched, stagger):
        # match pins the armed point to the probe's segment: the wave's
        # own overflow sites are composition-DEPENDENT by construction
        # (a fused query never reaches the solo retry ladder), so the
        # cross-mode invariant is the probe's stream
        plan = faults.install(
            f"seed=16; device.overflow: match={_seg.name}, times=1",
            seed=16)
        global_batcher.configure(enabled=batched, window_ms=30.0)
        try:
            probe_digests = []

            def probe():
                probe_digests.append(
                    corpus.digest(sbroker.query(solo_sql).rows))
            pt = threading.Thread(target=probe)
            pt.start()
            if stagger:
                results = [None] * len(sqls)
                errs = []

                def run(i, s):
                    try:
                        results[i] = broker.query(s)
                    except Exception as e:  # noqa: BLE001 — asserted
                        errs.append(f"q{i}: {e}")
                threads = []
                for i, s in enumerate(sqls):
                    th = threading.Thread(target=run, args=(i, s))
                    threads.append(th)
                    th.start()
                    time.sleep(0.002 * (i % 3))  # ragged arrival
                for th in threads:
                    th.join()
                assert not errs, errs
            else:
                results = _concurrent(broker, sqls)
            pt.join()
            return ([corpus.digest(r.rows) for r in results]
                    + probe_digests, plan.fired_summary())
        finally:
            faults.clear()

    runs = [chaos_run(batched=False, stagger=True),
            chaos_run(batched=True, stagger=False),
            chaos_run(batched=True, stagger=True)]
    for d, _f in runs:
        assert d == baseline + [solo_base]
    f_solo, f_barrier, f_staggered = (f for _d, f in runs)
    assert f_solo == f_barrier == f_staggered
    assert f_solo, "the chaos plan never fired — the gate is vacuous"


# -- admission fairness -----------------------------------------------------

def test_deadline_pressured_query_bypasses_queue(ssb):
    """A query near its deadline dispatches solo immediately — never
    queue-blocked behind the admission window."""
    _seg, broker = ssb
    global_batcher.configure(enabled=True, window_ms=2000.0)
    # a peer must exist or the no-peers fast path fires first
    from pinot_tpu.engine.accounting import global_accountant
    global_accountant.register("peer-query")
    try:
        before = _counter("solo_fallback_deadline")
        t0 = time.perf_counter()
        res = broker.query(_q11(0) + " OPTION(timeoutMs=1500)")
        wall = time.perf_counter() - t0
    finally:
        global_accountant.unregister("peer-query")
    assert res.rows
    assert _counter("solo_fallback_deadline") == before + 1
    assert wall < 1.5, f"deadline query waited the window ({wall:.2f}s)"


def test_lone_query_never_waits_the_window(ssb):
    """No peers -> solo dispatch without paying the admission window
    (the <5% solo-latency acceptance gate's mechanism)."""
    _seg, broker = ssb
    global_batcher.configure(enabled=True, window_ms=2000.0)
    before = _counter("solo_fallback_no_peers")
    t0 = time.perf_counter()
    res = broker.query(_q11(1) + corpus.OPTION)
    wall = time.perf_counter() - t0
    assert res.rows
    assert _counter("solo_fallback_no_peers") == before + 1
    assert wall < 1.5, f"lone query waited the window ({wall:.2f}s)"


def test_incompatible_plan_counts_reason(ssb):
    """A cube-ineligible shape (huge group space) falls back solo with
    the reason counted."""
    _seg, broker = ssb
    q43 = next(q for q in corpus.SSB_QUERIES if q[0] == "q4.3")
    sql = corpus.spec_to_sql(q43[1], q43[2], q43[3]) + corpus.OPTION
    global_batcher.configure(enabled=True, window_ms=5.0)
    from pinot_tpu.engine.accounting import global_accountant
    global_accountant.register("peer-query-2")
    try:
        before = _counter("solo_fallback_incompatible")
        broker.query(sql)
    finally:
        global_accountant.unregister("peer-query-2")
    assert _counter("solo_fallback_incompatible") > before


# -- zero post-warmup retraces across the pow2 ladder -----------------------

def test_zero_retraces_across_pow2_ladder(grouped):
    """Warm the ragged ladder at several batch sizes, then re-run every
    size: the RetraceDetector must stay silent (pow2 padding keeps the
    fused shapes cache-stable)."""
    _dm, broker = grouped
    global_batcher.configure(enabled=True, window_ms=30.0)
    sizes = (2, 3, 8)          # pads to 2 / 4 / 8
    for n in sizes:            # warmup: compiles are expected here
        _warmed(broker, [_grp(i) + corpus.OPTION for i in range(n)])
    det0 = global_plan_cache.detector.retraces
    fused0 = _counter("batched_queries")
    for n in sizes:
        _concurrent(broker, [_grp(i) + corpus.OPTION for i in range(n)])
    assert _counter("batched_queries") > fused0  # really fused again
    assert global_plan_cache.detector.retraces == det0


# -- per-query span attribution ---------------------------------------------

def test_span_attribution_inside_fused_dispatch(grouped, tmp_path):
    """Every fused query's sampled trace carries its own
    ragged_dispatch span (queue-wait annotated), and per-phase wall
    attribution still sums within the 10% gate."""
    from pinot_tpu.utils import ledger as uledger

    _dm, broker = grouped
    path = str(tmp_path / "trace.jsonl")
    traced = Broker(trace_ratio=1.0, trace_ledger_path=path)
    traced._tables = broker._tables
    global_batcher.configure(enabled=True, window_ms=30.0)
    n = 4
    # a standing peer keeps the no-peers fast path (which returns
    # BEFORE the ragged_dispatch span opens) from racing the wave's
    # own accountant registrations
    from pinot_tpu.engine.accounting import global_accountant
    global_accountant.register("span-test-peer")
    try:
        sqls = [_grp(i) + corpus.OPTION for i in range(n)]
        _warmed(broker, sqls)        # cubes and programs in place first
        _concurrent(traced, sqls)
    finally:
        global_accountant.unregister("span-test-peer")
    recs = [r for r in _read_jsonl(path) if r.get("kind") == "query_trace"]
    assert len(recs) == n
    assert not uledger.validate_file(path)["errors"]
    fused = 0
    for rec in recs:
        root = rec["root"]
        spans = _find_spans(root, "ragged_dispatch")
        assert spans, "fused query lost its ragged_dispatch span"
        attrs = spans[0]["attrs"]
        if attrs.get("batched"):
            fused += 1
            assert attrs.get("queue_wait_ms") is not None
            assert attrs.get("batch_size", 0) >= 2
        # the 10% wall gate: direct children never exceed the root
        child_ms = sum(c["ms"] for c in root["children"])
        assert child_ms <= root["ms"] * 1.10 + 1.0
    assert fused >= 2


def _read_jsonl(path):
    import json
    out = []
    with open(path) as fh:
        for line in fh:
            if line.strip():
                out.append(json.loads(line))
    return out


def _find_spans(node, name):
    found = [node] if node.get("name") == name else []
    for c in node.get("children") or []:
        found.extend(_find_spans(c, name))
    return found


# -- cube cache & eligibility ----------------------------------------------

def test_cube_cache_hits_and_eviction(grouped):
    _dm, broker = grouped
    global_batcher.configure(enabled=True, window_ms=30.0)
    _warmed(broker, [_grp(i) + corpus.OPTION for i in range(3)])
    hits0 = _counter("cube_cache_hits")
    for _ in range(5):          # who meets whom is the scheduler's
        _concurrent(broker, [_grp(i) + corpus.OPTION for i in range(3)])
        if _counter("cube_cache_hits") > hits0:
            break
    assert _counter("cube_cache_hits") > hits0
    # eviction by segment name drops the device cube
    seg = _dm.acquire_segments()[0]
    entries0 = global_cube_cache.stats()["entries"]
    assert entries0 >= 1
    seg.evict_device()
    assert global_cube_cache.stats()["entries"] < entries0


def test_cube_spec_eligibility_gates(ssb):
    """The cost model's documented refusals: float sums, huge cubes,
    and per-row mask params never fuse."""
    seg, _broker = ssb
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    def spec_of(sql):
        plan = SegmentPlanner(
            build_query_context(parse_sql(sql)), seg).plan()
        assert plan.kind == "kernel"
        return cube_spec_for(plan)

    ok, _ = spec_of(_q11(0))
    assert ok is not None and ok.group_space == 1 \
        and ok.pred_space == 7 * 11 * 50
    # q4.3: 1.75M-group cube can never fit under the caps at this scale
    q43 = next(q for q in corpus.SSB_QUERIES if q[0] == "q4.3")
    none_spec, why = spec_of(corpus.spec_to_sql(q43[1], q43[2], q43[3]))
    assert none_spec is None and why == "incompatible"
    # float aggregation values reassociate -> ineligible
    none_spec, _ = spec_of(
        "SELECT AVG(lo_revenue / lo_quantity) FROM lineorder "
        "WHERE d_year = 1993")
    assert none_spec is None


def test_cube_requires_exact_int64(ssb):
    """With jax_enable_x64 off the cube's int64 cells would silently
    canonicalize to int32 and wrap; the solo compact path errors
    loudly on that condition, so fusion must refuse rather than mask
    it with wrong numbers."""
    import jax

    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    seg, _broker = ssb
    plan = SegmentPlanner(
        build_query_context(parse_sql(_q11(0))), seg).plan()
    assert cube_spec_for(plan)[0] is not None
    jax.config.update("jax_enable_x64", False)
    try:
        assert cube_spec_for(plan)[0] is None
    finally:
        jax.config.update("jax_enable_x64", True)


# -- q4.3 sparse sorted-post contract ---------------------------------------

def test_pred_col_discovery_recurses_func_and_case():
    """A predicate column reached only through Func/Case (WHERE
    YEAR(ts) = x) must be discovered: missing it from the cube dims
    would evaluate the fused predicate over a zero placeholder grid
    and return silently wrong results."""
    from pinot_tpu.ops.ir import Bin, Case, Cmp, Col, Func, Lit, TrueP
    from pinot_tpu.ops.kernels import _pred_col_indices

    # the planner's expr-vs-expr lowering shape: (YEAR(col3) - 0) == p
    p = Cmp(op="==", lhs=Bin(op="-", lhs=Func(name="year",
                                              args=(Col(col=3),)),
                             rhs=Lit(param=0)), param=1)
    assert _pred_col_indices(p) == {3}
    case = Cmp(op="==", lhs=Case(
        whens=((Cmp(op="<", lhs=Col(col=2), param=0), Col(col=4)),),
        else_=Lit(param=1)), param=2)
    assert _pred_col_indices(case) == {2, 4}
    assert _pred_col_indices(TrueP()) == set()


def test_q43_sparse_sorted_post_contract(ssb):
    """At group space >= GROUP_XFER_SPACE the sorted post emits
    (group_idx, value) pairs directly: outputs are cap-sized, never
    space-sized, and digests match the dense (xfer_compact=False)
    path exactly."""
    import jax

    from pinot_tpu.engine.executor import (extract_partial,
                                           resolve_params)
    from pinot_tpu.ops.kernels import GROUP_XFER_CAP, jitted_kernel
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    seg, _broker = ssb
    q43 = next(q for q in corpus.SSB_QUERIES if q[0] == "q4.3")
    sql = corpus.spec_to_sql(q43[1], q43[2], q43[3])
    plan = SegmentPlanner(
        build_query_context(parse_sql(sql)), seg).plan()
    assert plan.kind == "kernel" and plan.kernel_plan.strategy == "compact"
    space = plan.kernel_plan.group_space
    assert space >= (1 << 15)
    cols = seg.device_cols(plan.col_names)
    params = resolve_params(plan)
    n = np.int32(seg.n_docs)

    sparse = jax.device_get(jitted_kernel(
        plan.kernel_plan, seg.bucket, plan.slots_cap)(cols, n, params))
    assert "group_idx" in sparse
    assert sparse["group_idx"].shape[0] == GROUP_XFER_CAP
    for name, v in sparse.items():
        assert np.asarray(v).size <= GROUP_XFER_CAP, \
            f"{name} is space-sized — densify-then-compact came back"
    assert int(sparse.pop("group_overflow")) == 0
    sparse.pop("overflow", None)

    dense = jax.device_get(jitted_kernel(
        plan.kernel_plan, seg.bucket, plan.slots_cap,
        xfer_compact=False)(cols, n, params))
    assert dense["group_count"].shape[0] == space
    dense.pop("overflow", None)

    ps = extract_partial(plan, dict(sparse))
    pd = extract_partial(plan, dict(dense))
    assert ps.groups == pd.groups and len(ps.groups) > 0


# -- metrics / ledger plumbing ---------------------------------------------

def test_batching_health_and_ledger_fields():
    snap = global_metrics.snapshot()
    block = batching_health(snap)
    assert set(block["solo_fallbacks"]) == {
        "incompatible", "no_peers", "deadline",
        "window_expired", "timeout", "leader_error", "cold"}
    assert "le_8" in block["batch_size_histogram"]
    assert "enabled" in block and "batch_queue_depth" in block
    assert {"cube_builds_pending", "cube_builds_background",
            "fused_compiles_background"} <= set(block)
    # query_stats grows batched/batch_size — writer-validated
    from pinot_tpu.utils import ledger as uledger
    rec = uledger.make_record(
        "query_stats", qid="q1", table="t", wall_ms=1.0, partial=False,
        servers_queried=1, servers_responded=1, exception_codes=[],
        batched=2, batch_size=8)
    assert not uledger.validate_record(rec)
    with pytest.raises(ValueError):
        uledger.make_record(
            "query_stats", qid="q1", table="t", wall_ms=1.0,
            partial=False, servers_queried=1, servers_responded=1,
            exception_codes=[], batchedTypo=1)


def test_query_stats_batched_fields_from_scatter():
    """Server wire header -> ScatterResult -> forensics query_stats:
    the batched/batch_size trend-line fields survive the plumbing."""
    from pinot_tpu.cluster.broker_node import ScatterResult
    from pinot_tpu.cluster.forensics import QueryForensics

    sc = ScatterResult()
    sc.add_batching(2, 8)
    sc.add_batching(1, 16)
    rec = QueryForensics(slow_query_ms=1e9).record(
        "qid-x", "t", "SELECT 1", time.perf_counter(), None, [sc])
    assert rec["batched"] == 3 and rec["batch_size"] == 16
    # an abandoned hedge straggler can't mutate a closed result
    sc.close_wire_times()
    sc.add_batching(5, 32)
    assert sc.batched_dispatches == 3 and sc.batch_size_max == 16


def test_micro_batch_queue_leader_follower():
    """The scheduler's admission primitive: leader collects the window,
    follower returns None immediately; max_items closes early."""
    from pinot_tpu.engine.scheduler import MicroBatchQueue
    q = MicroBatchQueue()
    got = {}

    def leader():
        got["batch"] = q.offer("k", "L", window_s=1.0, max_items=2)

    t = threading.Thread(target=leader)
    t.start()
    time.sleep(0.05)
    t0 = time.perf_counter()
    assert q.offer("k", "F", window_s=1.0, max_items=2) is None
    assert time.perf_counter() - t0 < 0.5  # follower never blocks
    t.join(5)
    assert sorted(got["batch"]) == ["F", "L"]  # closed at max_items,
    assert q.depth() == 0                      # well before the window

    # the weight budget is a HARD bound: an item that would overflow it
    # closes the bucket for its leader and leads a fresh one instead
    def leader_w():
        got["wbatch"] = q.offer("w", "L", window_s=2.0, max_items=8,
                                max_weight=10, weight=6)

    t = threading.Thread(target=leader_w)
    t.start()
    time.sleep(0.05)
    big = q.offer("w", "B", window_s=0.05, max_items=8,
                  max_weight=10, weight=6)  # 6+6 > 10: new bucket
    t.join(5)
    assert got["wbatch"] == ["L"]   # closed without the overflow item
    assert big == ["B"]             # which led its own (solo) window


# -- PR 33: a query never pays for a cube or a fused program ----------------

@pytest.fixture
def cold(grouped):
    """The grouped table with nothing of the fusion plane in place, a
    standing peer (so ``no_peers`` never races the wave) and a window
    that one wave always fills: every wave below is ONE batch."""
    from pinot_tpu.engine.accounting import global_accountant
    dm, broker = grouped
    assert global_batcher.wait_ready(120.0)
    global_cube_cache.clear()
    global_batcher.clear()
    global_accountant.register("cold-test-peer")
    yield dm, broker
    global_accountant.unregister("cold-test-peer")
    assert global_batcher.wait_ready(120.0)


def _wave(broker, n, offset=0):
    """n variants of the grouped shape at once, as one batch."""
    global_batcher.configure(enabled=True, window_ms=2000.0, max_batch=n)
    return _concurrent(
        broker, [_grp(offset + i) + corpus.OPTION for i in range(n)])


def _solo_digests(broker, n, offset=0):
    global_batcher.configure(enabled=False)
    return [corpus.digest(broker.query(_grp(offset + i) + corpus.OPTION).rows)
            for i in range(n)]


def test_a_cold_group_answers_solo_and_right(cold):
    _dm, broker = cold
    want = _solo_digests(broker, 4)
    cold0, fused0 = (_counter("solo_fallback_cold"),
                     _counter("batched_queries"))
    got = _wave(broker, 4)
    assert [corpus.digest(r.rows) for r in got] == want
    assert _counter("solo_fallback_cold") == cold0 + 4
    assert _counter("batched_queries") == fused0
    assert global_batcher.wait_ready(120.0)


def test_the_background_builds_each_cube_once(cold):
    """Four cold submissions of one shape over one segment ask for one
    cube; a second cold wave (the program is missing now) builds none."""
    _dm, broker = cold
    builds0 = _counter("kernel_dispatches_cube_build")
    bg0 = _counter("cube_builds_background")
    _wave(broker, 4)
    assert global_batcher.wait_ready(120.0)
    assert _counter("kernel_dispatches_cube_build") == builds0 + 1
    assert _counter("cube_builds_background") == bg0 + 1
    compiles0 = _counter("fused_compiles_background")
    _wave(broker, 4)
    assert global_batcher.wait_ready(120.0)
    assert _counter("kernel_dispatches_cube_build") == builds0 + 1
    # the shape's whole ladder under max_batch 4: 2 and 4 padded items
    assert _counter("fused_compiles_background") == compiles0 + 2


def test_after_wait_ready_the_same_burst_fuses_byte_for_byte(cold):
    _dm, broker = cold
    want = _solo_digests(broker, 4)
    for _ in range(2):      # the cubes, then the 4-item program
        _wave(broker, 4)
        assert global_batcher.wait_ready(120.0)
    cold0, fused0 = (_counter("solo_fallback_cold"),
                     _counter("batched_queries"))
    n_wait, n_exec = (_counter("phase_n_ragged_wait"),
                      _counter("phase_n_fused_execute"))
    got = _wave(broker, 4)
    assert [corpus.digest(r.rows) for r in got] == want
    assert _counter("batched_queries") == fused0 + 4
    assert _counter("solo_fallback_cold") == cold0
    # every member crosses it in MicroBatchQueue.offer (the leader holds
    # the window there, a follower returns at once) and the three
    # followers again while they wait for the leader; one launch
    assert _counter("phase_n_ragged_wait") == n_wait + 4 + 3
    assert _counter("phase_n_fused_execute") == n_exec + 1
    assert batching_health(
        global_metrics.snapshot())["cube_builds_pending"] == 0


def test_one_cold_batch_makes_the_whole_ladder(cold):
    """Who meets whom later decides no compile: after one batch of four
    was cold, batches of two and of three (4 padded items) fuse at once."""
    _dm, broker = cold
    for _ in range(2):      # the cubes, then the programs at 2 and 4
        _wave(broker, 4)
        assert global_batcher.wait_ready(120.0)
    cold0, compiles0 = (_counter("solo_fallback_cold"),
                        _counter("fused_compiles_background"))
    for n in (2, 3):
        fused0 = _counter("batched_queries")
        want = _solo_digests(broker, n, offset=n)
        got = _wave(broker, n, offset=n)
        assert [corpus.digest(r.rows) for r in got] == want
        assert _counter("batched_queries") == fused0 + n
    assert global_batcher.wait_ready(1.0)
    assert _counter("solo_fallback_cold") == cold0
    assert _counter("fused_compiles_background") == compiles0


def test_a_build_slower_than_the_deadline_fails_no_query(cold, monkeypatch):
    """The cube build outlasts every query's timeoutMs: the queries
    answer solo inside it, and the build still lands."""
    _dm, broker = cold
    want = _solo_digests(broker, 3)
    real = RaggedBatcher._build_cube
    started = threading.Event()

    def slow(self, spec, plan):
        started.set()
        time.sleep(1.5)
        return real(self, spec, plan)

    monkeypatch.setattr(RaggedBatcher, "_build_cube", slow)
    global_batcher.configure(enabled=True, window_ms=20.0, max_batch=3)
    t0 = time.perf_counter()
    got = _concurrent(broker, [_grp(i) + " OPTION(timeoutMs=1000)"
                               for i in range(3)])
    wall = time.perf_counter() - t0
    assert [corpus.digest(r.rows) for r in got] == want
    assert started.wait(5.0) and wall < 1.0, wall
    assert not global_batcher.wait_ready(0.01)      # still building
    assert global_batcher.wait_ready(120.0)
    assert not global_cube_cache.missing(
        cube_spec_for(_plan_of(_dm, _grp(0)))[0], _dm.acquire_segments())


def _plan_of(dm, sql):
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql
    return SegmentPlanner(build_query_context(parse_sql(sql)),
                          dm.acquire_segments()[0]).plan()


def test_the_latency_estimate_is_fed_by_warm_dispatches_only(cold):
    _dm, broker = cold
    for _ in range(2):
        _wave(broker, 2)
        assert global_batcher.wait_ready(120.0)
        # two cold rounds, a cube build and a compile behind them: no
        # estimate, so no shape is sent solo for what its first try cost
        assert not global_batcher._est_ms
    _wave(broker, 2)
    assert len(global_batcher._est_ms) == 1


def test_a_lone_query_returns_no_peers_first_even_when_cold(grouped):
    _dm, broker = grouped
    assert global_batcher.wait_ready(120.0)
    global_cube_cache.clear()
    global_batcher.clear()
    global_batcher.configure(enabled=True, window_ms=2000.0)
    lone0, cold0, builds0 = (_counter("solo_fallback_no_peers"),
                             _counter("solo_fallback_cold"),
                             _counter("kernel_dispatches_cube_build"))
    assert broker.query(_grp(0) + corpus.OPTION).rows
    assert _counter("solo_fallback_no_peers") == lone0 + 1
    assert _counter("solo_fallback_cold") == cold0
    assert global_batcher.wait_ready(1.0)
    assert _counter("kernel_dispatches_cube_build") == builds0


def test_an_evicted_cube_is_rebuilt_in_the_background(cold):
    dm, broker = cold
    for _ in range(3):
        _wave(broker, 2)
        assert global_batcher.wait_ready(120.0)
    dm.acquire_segments()[0].evict_device()     # drops cube and stack
    want = _solo_digests(broker, 2)
    cold0, builds0 = (_counter("solo_fallback_cold"),
                      _counter("kernel_dispatches_cube_build"))
    got = _wave(broker, 2)
    assert [corpus.digest(r.rows) for r in got] == want
    assert _counter("solo_fallback_cold") == cold0 + 2
    assert global_batcher.wait_ready(120.0)
    assert _counter("kernel_dispatches_cube_build") == builds0 + 1


def test_a_failed_build_fails_no_query_and_is_not_asked_for_again(
        cold, monkeypatch):
    _dm, broker = cold
    want = _solo_digests(broker, 2)
    calls = []

    def broken(self, spec, plan):
        calls.append(plan.segment.name)
        raise RuntimeError("planted: the cube program does not build")

    monkeypatch.setattr(RaggedBatcher, "_build_cube", broken)
    errors0 = _counter("cube_build_errors")
    for _ in range(2):
        got = _wave(broker, 2)
        assert [corpus.digest(r.rows) for r in got] == want
        assert global_batcher.wait_ready(120.0)
    assert len(calls) == 1
    assert _counter("cube_build_errors") == errors0 + 1


def test_a_group_the_budget_cannot_hold_twice_is_incompatible(
        cold, monkeypatch):
    """No peer could ever join it: it neither waits the window nor has
    a cube built for nothing."""
    from pinot_tpu.engine import ragged
    dm, broker = cold
    spec, _ = cube_spec_for(_plan_of(dm, _grp(0)))
    monkeypatch.setattr(ragged, "ITEM_CELL_BUDGET", spec.cube_space)
    inc0 = _counter("solo_fallback_incompatible")
    t0 = time.perf_counter()
    _wave(broker, 2)
    assert time.perf_counter() - t0 < 1.5       # the window is 2 s
    assert _counter("solo_fallback_incompatible") == inc0 + 2
    assert global_batcher.wait_ready(1.0)
    assert global_cube_cache.missing(spec, dm.acquire_segments())


def test_the_two_programs_carry_their_own_scopes(grouped):
    """``pinot.cube_build`` and ``pinot.cube_combine`` name the batcher's
    operations in a device trace (HLO metadata only)."""
    import jax

    from pinot_tpu.engine import ragged
    from pinot_tpu.engine.executor import resolve_params
    from pinot_tpu.utils import phases as ph
    dm, _broker = grouped
    plan = _plan_of(dm, _grp(0))
    spec, _ = cube_spec_for(plan)
    seg = plan.segment
    params = resolve_params(plan)
    built = jax.jit(ragged.build_cube_kernel(spec)).lower(
        seg.device_cols(plan.col_names), np.int32(seg.n_docs), params)
    assert ph.SCOPE_CUBE_BUILD in built.as_text(debug_info=True)
    cube = {k: np.zeros((1, spec.cube_space), np.int64)
            for k in ("cnt", "s0")}
    stacked = tuple(np.stack([np.asarray(p)] * 2) for p in params)
    combined = jax.jit(ragged.build_cube_combine_kernel(spec)).lower(
        cube, np.zeros(2, np.int32), stacked)
    assert ph.SCOPE_CUBE_COMBINE in combined.as_text(debug_info=True)
    assert {ph.SCOPE_CUBE_BUILD, ph.SCOPE_CUBE_COMBINE} <= ph.KERNEL_SCOPES
    assert {ph.RAGGED_WAIT, ph.FUSED_EXECUTE} <= ph.METERED_PHASES


def test_prometheus_shows_the_new_names(cold):
    """They are ``global_metrics`` names, so ``GET /metrics/prometheus``
    (cluster/broker_node.py renders this text) carries them once a cold
    wave and a warm one have passed."""
    _dm, broker = cold
    for _ in range(3):
        _wave(broker, 2)
        assert global_batcher.wait_ready(120.0)
    text = global_metrics.prometheus()
    for line in ("pinot_tpu_solo_fallback_cold_total",
                 "pinot_tpu_cube_builds_background_total",
                 "pinot_tpu_fused_compiles_background_total",
                 "pinot_tpu_phase_us_ragged_wait_total",
                 "pinot_tpu_phase_n_fused_execute_total",
                 "pinot_tpu_cube_builds_pending 0"):
        assert line in text, line


def test_the_background_runs_each_key_once_under_contention():
    """More requesters than cores, a short switch interval: every key's
    job runs exactly once while it is queued or running, a job that
    raised is not asked for again, ``wait_idle`` sees the queue drain and
    the worker thread is gone afterwards."""
    import sys

    from pinot_tpu.engine.ragged import _Background
    bg = _Background()
    ran: dict = {}
    lock = threading.Lock()

    def job(k):
        def run():
            with lock:
                ran[k] = ran.get(k, 0) + 1
            if k % 7 == 0:
                raise RuntimeError("planted")
        return run

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def requester(i):
            for k in range(64):
                bg.request(("stress", k), job(k))

        threads = [threading.Thread(target=requester, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
            assert not t.is_alive()
        assert bg.wait_idle(30.0)
        # the failed ones stay refused, the others may be asked again
        for k in range(64):
            bg.request(("stress", k), job(k))
        assert bg.wait_idle(30.0)
    finally:
        sys.setswitchinterval(interval)
    for k in range(64):
        if k % 7 == 0:
            assert ran[k] == 1, (k, ran[k])
        else:
            # once while queued or running; again only after it finished
            assert 1 <= ran[k] <= 33, (k, ran[k])
    assert len(ran) == 64 and not bg._keys and not bg._jobs
    deadline = time.time() + 5.0
    while bg._thread is not None and time.time() < deadline:
        time.sleep(0.01)
    assert bg._thread is None
