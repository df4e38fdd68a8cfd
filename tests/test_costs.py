"""Cost-based multistage optimization: selectivity estimates, greedy
INNER-join reordering with LEFT-join barriers, build-side selection.

Reference test strategy analog: pinot-query-planner QueryEnvironment
plan tests (Calcite CBO rule coverage asserts operator trees + join
strategies chosen per statistics)."""
import numpy as np
import pytest

from pinot_tpu.broker import Broker
from pinot_tpu.multistage.costs import (TableStats, join_cardinality,
                                        scan_cardinality, selectivity)
from pinot_tpu.query.sql import parse_sql
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)


def _table(broker, name, data, schema, tmpdir):
    d = SegmentBuilder(schema, TableConfig(name)).build(
        data, str(tmpdir), "s0")
    dm = TableDataManager(name)
    dm.add_segment_dir(d)
    broker.register_table(dm)
    return dm


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    rng = np.random.default_rng(21)
    b = Broker()
    root = tmp_path_factory.mktemp("cost_tables")
    # facts: 60k rows, keys into both dims
    n = 60000
    _table(b, "facts", {
        "cust_id": rng.integers(0, 5000, n).astype(np.int64),
        "item_id": rng.integers(0, 40, n).astype(np.int64),
        "amount": rng.integers(1, 100, n).astype(np.int64),
    }, Schema("facts", [
        FieldSpec("cust_id", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("item_id", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("amount", DataType.LONG, FieldType.METRIC)]), root / "f")
    # big dim: 5000 customers
    _table(b, "customers", {
        "cust_id": np.arange(5000, dtype=np.int64),
        "region": rng.choice(["eu", "us", "apac"], 5000),
    }, Schema("customers", [
        FieldSpec("cust_id", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("region", DataType.STRING, FieldType.DIMENSION)]),
        root / "c")
    # tiny dim: 40 items
    _table(b, "items", {
        "item_id": np.arange(40, dtype=np.int64),
        "cat": rng.choice(["a", "b"], 40),
    }, Schema("items", [
        FieldSpec("item_id", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("cat", DataType.STRING, FieldType.DIMENSION)]),
        root / "i")
    return b


def _stats(broker, name):
    return TableStats.from_segments(
        broker.table(name).acquire_segments())


def test_selectivity_shapes(cluster):
    st = _stats(cluster, "facts")
    eq = selectivity(parse_sql(
        "SELECT 1 FROM facts WHERE item_id = 7").where, st)
    assert eq == pytest.approx(1 / 40, rel=0.2)
    rng_sel = selectivity(parse_sql(
        "SELECT 1 FROM facts WHERE amount < 50").where, st)
    assert 0.3 < rng_sel < 0.7
    both = selectivity(parse_sql(
        "SELECT 1 FROM facts WHERE item_id = 7 AND amount < 50").where, st)
    assert both == pytest.approx(eq * rng_sel, rel=1e-6)
    inl = selectivity(parse_sql(
        "SELECT 1 FROM facts WHERE item_id IN (1, 2, 3, 4)").where, st)
    assert inl == pytest.approx(4 / 40, rel=0.2)


def test_scan_and_join_cardinality(cluster):
    st = _stats(cluster, "facts")
    est = scan_cardinality(st, parse_sql(
        "SELECT 1 FROM facts WHERE item_id = 7").where)
    assert 500 < est < 4500   # true ~1500
    # FK join facts->customers on cust_id: ~|facts|
    jc = join_cardinality(60000, 5000, 5000, 5000)
    assert jc == pytest.approx(60000)


def test_join_reorder_small_table_first(cluster):
    from pinot_tpu.multistage.executor import MultiStageExecutor
    stmt = parse_sql(
        "SELECT COUNT(*) FROM facts "
        "JOIN customers ON facts.cust_id = customers.cust_id "
        "JOIN items ON facts.item_id = items.item_id "
        "WHERE items.cat = 'a'")
    ex = MultiStageExecutor(cluster, stmt)
    pushed, _ = ex._split_where()
    ordered, trace = ex.plan_join_order(pushed)
    # the filtered 40-row items table joins before the 5000-row customers
    assert [j.table.label for j in ordered] == ["items", "customers"]
    assert trace[0]["table"] == "items"


def test_left_join_is_reorder_barrier(cluster):
    from pinot_tpu.multistage.executor import MultiStageExecutor
    stmt = parse_sql(
        "SELECT COUNT(*) FROM facts "
        "LEFT JOIN customers ON facts.cust_id = customers.cust_id "
        "JOIN items ON facts.item_id = items.item_id")
    ex = MultiStageExecutor(cluster, stmt)
    pushed, _ = ex._split_where()
    ordered, _ = ex.plan_join_order(pushed)
    # the LEFT join must stay first even though items is far smaller
    assert [j.table.label for j in ordered] == ["customers", "items"]


def test_reordered_results_match_textual_order(cluster):
    # same answer whichever order the optimizer picks
    sql = ("SELECT items.cat, COUNT(*), SUM(facts.amount) FROM facts "
           "JOIN customers ON facts.cust_id = customers.cust_id "
           "JOIN items ON facts.item_id = items.item_id "
           "WHERE customers.region = 'eu' "
           "GROUP BY items.cat ORDER BY items.cat")
    swapped = ("SELECT items.cat, COUNT(*), SUM(facts.amount) FROM facts "
               "JOIN items ON facts.item_id = items.item_id "
               "JOIN customers ON facts.cust_id = customers.cust_id "
               "WHERE customers.region = 'eu' "
               "GROUP BY items.cat ORDER BY items.cat")
    assert cluster.query(sql).rows == cluster.query(swapped).rows
    assert cluster.query(sql).rows[0][1] > 0


def test_build_side_swap_preserves_inner_join(cluster):
    # big LEFT side, small right side and vice versa give identical rows
    a = cluster.query(
        "SELECT COUNT(*) FROM facts JOIN items "
        "ON facts.item_id = items.item_id WHERE items.cat = 'b'")
    b = cluster.query(
        "SELECT COUNT(*) FROM items JOIN facts "
        "ON facts.item_id = items.item_id WHERE items.cat = 'b'")
    assert a.rows == b.rows
    assert a.rows[0][0] > 0


def test_explain_shows_estimates(cluster):
    res = cluster.query(
        "EXPLAIN PLAN FOR SELECT COUNT(*) FROM facts "
        "JOIN items ON facts.item_id = items.item_id")
    ops = [r[0] for r in res.rows]
    assert any("est_rows" in op and "HASH_JOIN" in op for op in ops)
    assert any("LEAF_SCAN" in op and "est_rows" in op for op in ops)


def test_explain_shows_dynamic_filter(cluster):
    r = cluster.query(
        "EXPLAIN PLAN FOR SELECT COUNT(*) FROM items JOIN facts "
        "ON items.item_id = facts.item_id")
    scans = [row[0] for row in r.rows if row[0].startswith("LEAF_SCAN")]
    assert any("dynamic_filter:" in s for s in scans), scans


# ---------------------------------------------------------------------------
# Group-by kernel strategy selector (round-6): the cost model must keep the
# SSB sub-5x queries on the fast path. A heuristic change that flips q2.x
# back to a slow strategy fails HERE, not in a hardware capture.
# ---------------------------------------------------------------------------

from pinot_tpu.multistage.costs import (choose_group_strategy,  # noqa: E402
                                        compact_slots_cap, ir_selectivity)
from pinot_tpu.ops.ir import And, Cmp, Col, EqId, IdRange, InSet, \
    Or, TrueP  # noqa: E402

SSB_ROWS = 1 << 27      # SSB at 134M rows


def _ssb_shape(qid):
    """(pred, param_values, col_cards, space, needs_sort, n_payloads)
    mirroring the corpus's SSB query shapes (tools/corpus.py)."""
    if qid == "q2.2":   # p_brand1 BETWEEN (8 of 1000) AND s_region eq
        pred = And((IdRange(0, 0, 1), EqId(1, 2)))
        params = [100, 107, 1]
        cards = {0: 1000, 1: 5}
        return pred, params, cards, 7 * 1000, True, 1
    if qid == "q2.3":   # p_brand1 eq AND s_region eq
        pred = And((EqId(0, 0), EqId(1, 1)))
        return pred, [5, 2], {0: 1000, 1: 5}, 7 * 1000, True, 1
    if qid == "q3.2":   # c_nation eq, s_nation eq, d_year between
        pred = And((EqId(0, 0), EqId(1, 1), IdRange(2, 2, 3)))
        return pred, [7, 7, 0, 5], {0: 25, 1: 25, 2: 7}, \
            250 * 250 * 7, True, 1
    if qid == "q3.4":   # two 2-city IN sets + d_yearmonth eq
        pred = And((InSet(0, 0, 2), InSet(1, 1, 2), EqId(2, 2)))
        return pred, [np.array([10, 15]), np.array([10, 15]), 42], \
            {0: 250, 1: 250, 2: 84}, 250 * 250 * 7, True, 1
    assert qid == "q4.3"  # c_region eq, s_nation eq, d_year in, p_cat eq
    pred = And((EqId(0, 0), EqId(1, 1),
                Or((EqId(2, 2), EqId(2, 3))), EqId(3, 4)))
    return pred, [1, 7, 5, 6, 13], {0: 5, 1: 25, 2: 7, 3: 25}, \
        7 * 250 * 1000, True, 1


@pytest.mark.parametrize("qid", ["q2.2", "q2.3", "q3.2", "q3.4", "q4.3"])
@pytest.mark.parametrize("scatter", [False, True])
def test_ssb_sub5x_queries_stay_compact(qid, scatter):
    """Every round-5 sub-5x query keeps the compact strategy on both the
    MXU (TPU-shaped) and scatter (CPU) cores, with a capacity far below
    the input size (the whole point of the rework)."""
    pred, params, cards, space, needs_sort, n_pay = _ssb_shape(qid)
    sel = ir_selectivity(pred, params, cards)
    assert sel < 0.05, f"{qid} selectivity estimate {sel} implausibly high"
    strategy, trace = choose_group_strategy(
        SSB_ROWS, space, sel, "cpu", scatter, needs_sort, n_pay,
        dense_viable=True, compact_ok=True)
    assert strategy == "compact", trace
    cap = compact_slots_cap(SSB_ROWS, sel, "cpu", scatter)
    # tight capacity: the post-aggregation must not run over the old
    # n/16 default (65k slot rows at 134M)
    assert cap * 128 < SSB_ROWS // 8, (qid, cap)


def test_small_space_prefers_dense():
    strategy, trace = choose_group_strategy(
        SSB_ROWS, 64, 0.05, "cpu", False, False, 1,
        dense_viable=True, compact_ok=True)
    assert strategy == "dense", trace


def test_all_match_scatter_prefers_dense():
    """With nothing to filter out, compaction is pure overhead on the
    scatter core — the selector must not pay it."""
    strategy, trace = choose_group_strategy(
        1 << 20, 2000, 1.0, "cpu", True, False, 1,
        dense_viable=True, compact_ok=True)
    assert strategy == "dense", trace


def test_structural_gates_beat_costs():
    s, _ = choose_group_strategy(SSB_ROWS, 2000, 1.0, "cpu", True, False,
                                 1, dense_viable=False, compact_ok=True)
    assert s == "compact"
    s, _ = choose_group_strategy(SSB_ROWS, 2000, 0.001, "cpu", True,
                                 False, 1, dense_viable=True,
                                 compact_ok=False)
    assert s == "dense"


def test_force_option_overrides_costs():
    s, t = choose_group_strategy(1 << 20, 2000, 1.0, "cpu", True, False,
                                 1, dense_viable=True, compact_ok=True,
                                 force="compact")
    assert s == "compact" and t.get("forced") == "compact"
    # a forced strategy that is structurally impossible is ignored
    s, _ = choose_group_strategy(1 << 20, 2000, 1.0, "cpu", True, False,
                                 1, dense_viable=True, compact_ok=False,
                                 force="compact")
    assert s == "dense"


def test_capacity_quantization_is_stable():
    """Nearby selectivity estimates must share one capacity (stable jit
    cache key => zero retrace across iterations of similar queries)."""
    caps = {compact_slots_cap(SSB_ROWS, s, "cpu", True)
            for s in (0.00100, 0.00104, 0.00108)}
    assert len(caps) == 1, caps


def test_ir_selectivity_resolved_ranges():
    """IdRange spans over the dictionary cardinality are exact — the
    advantage over AST-level estimates that cannot see through string
    dictionaries."""
    sel = ir_selectivity(IdRange(0, 0, 1), [100, 107], {0: 1000})
    assert sel == pytest.approx(8 / 1000)
    sel = ir_selectivity(And((EqId(0, 0), TrueP())), [3], {0: 25})
    assert sel == pytest.approx(1 / 25)
    # negation + unprofiled fallbacks stay in (0, 1]
    assert 0 < ir_selectivity(EqId(0, 0, negated=True), [3], {0: 25}) <= 1
    assert 0 < ir_selectivity(Cmp(Col(0), "<", 0), [5], {}) <= 1
