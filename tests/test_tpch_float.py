"""Float aggregation held to a stated bound (PR 35): TPC-H's Q1 and Q6 over
a LINEITEM of DOUBLE measures, through the in-process Broker, through the
HTTP trio and on a four-device mesh, against ``benchmark/tpch/oracle.py``'s
exact integer reference at its 1e-12.

The arithmetic the TPU runs is what these tests run: conftest pins the
MXU-shaped group-by (no CPU scatter), so the dense group-by's float sums
are ``ops/kernels._float_sums`` here as on the chip, and the accumulator
rule has no platform in it. What the chip adds is XLA:TPU's lowering of
float64 to float32 pairs; that the rule hands the TPU float64 at all is
pinned below by exporting the kernel for the platform, no device needed.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.entries import served_http_tpch as entry  # noqa: E402
from benchmark.tpch import data, oracle, statements  # noqa: E402
from pinot_tpu.broker import Broker  # noqa: E402
from pinot_tpu.ops import float_acc_dtype, kernels  # noqa: E402
from pinot_tpu.ops.ir import Col  # noqa: E402
from pinot_tpu.query.context import build_query_context  # noqa: E402
from pinot_tpu.query.planner import SegmentPlanner  # noqa: E402
from pinot_tpu.query.sql import parse_sql  # noqa: E402
from pinot_tpu.server import TableDataManager  # noqa: E402
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

SEED, SEGMENTS, ROWS = 35, 2, 1 << 15
SHAPES = statements.load_shapes()
Q1, Q6 = SHAPES["q1.d90"], SHAPES["q6.y1994"]


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """(host segments, segment directories, a Broker over them)."""
    out = str(tmp_path_factory.mktemp("lineitem"))
    host = [data.gen_segment(ROWS, SEED, k) for k in range(SEGMENTS)]
    dirs = [entry.build_segment(cols, data.MEASURES, out, f"seg_{k}")
            for k, cols in enumerate(host)]
    dm = TableDataManager(entry.TABLE)
    for d in dirs:
        dm.add_segment_dir(d)
    broker = Broker()
    broker.register_table(dm)
    return host, dirs, broker, dm


@pytest.fixture(scope="module")
def served(table, tmp_path_factory):
    host, dirs, _broker, _dm = table
    system = entry.start({}, dirs, str(tmp_path_factory.mktemp("trio")))
    yield system
    system.stop()


def float_counters():
    c = global_metrics.snapshot()["counters"]
    return {k: c.get(k, 0) for k in ("float_acc_wide", "float_acc_narrow")}


def plan_of(sql, dm):
    seg = dm.acquire_segments()[0]
    return SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()


@pytest.mark.parametrize("key", list(SHAPES))
def test_broker_answers_within_the_bound(table, key):
    host, _dirs, broker, _dm = table
    shape = SHAPES[key]
    rows = broker.query(statements.to_sql(shape)).rows
    expected = oracle.answer(host, shape)
    assert expected and oracle.same(rows, expected, shape), (rows, expected)


@pytest.mark.parametrize("key", list(SHAPES))
def test_http_trio_answers_within_the_bound(table, served, key):
    """Per-segment float partials and AVG's (sum, count) pairs cross the
    wire and merge on the broker without losing bits."""
    host = table[0]
    shape = SHAPES[key]
    rows = served.execute(statements.to_sql(shape))
    assert oracle.same(rows, oracle.answer(host, shape), shape), rows


@pytest.mark.parametrize("key", ["q1.d90", "q6.y1994"])
def test_both_statements_plan_as_kernels_and_count_wide(table, key):
    _host, _dirs, broker, dm = table
    sql = statements.to_sql(SHAPES[key])
    plan = plan_of(sql, dm)
    assert plan.kind == "kernel" and plan.kernel_plan.strategy == "dense"
    n_float = sum(1 for a in SHAPES[key]["aggs"] if a[0] != "COUNT")
    for platform in ("cpu", "tpu"):
        assert kernels.float_acc_forms(plan.kernel_plan, platform) == (
            n_float, 0)
    before = float_counters()
    broker.query(sql)
    after = float_counters()
    # one vmapped launch answers both segments: once a launched plan
    assert after["float_acc_wide"] - before["float_acc_wide"] == n_float
    assert after["float_acc_narrow"] == before["float_acc_narrow"]


def test_a_compact_float_plan_counts_narrow_where_float64_is_emulated(table):
    dm = table[3]
    sql = ("SELECT l_shipdate, SUM(l_extendedprice), MAX(l_tax) FROM lineitem "
           "GROUP BY l_shipdate LIMIT 10 OPTION(groupByStrategy=compact)")
    plan = plan_of(sql, dm)
    assert plan.kernel_plan.strategy == "compact"
    assert kernels.float_acc_forms(plan.kernel_plan, "cpu") == (2, 0)
    assert kernels.float_acc_forms(plan.kernel_plan, "tpu") == (0, 2)
    dense = plan_of("SELECT l_shipdate, SUM(l_extendedprice) FROM lineitem "
                    "GROUP BY l_shipdate LIMIT 10", dm)
    assert dense.kernel_plan.group_space > kernels.FLOAT_UNROLL_GROUPS
    assert kernels.float_acc_forms(dense.kernel_plan, "tpu") == (0, 1)
    integral = plan_of("SELECT l_returnflag, SUM(l_shipdate), COUNT(*) FROM "
                       "lineitem GROUP BY l_returnflag", dm)
    assert kernels.float_acc_forms(integral.kernel_plan, "tpu") == (0, 0)


def test_between_whose_ends_are_column_values(table):
    """3 of l_discount's 11 values sit exactly on BETWEEN 0.05 AND 0.07's
    ends: the predicate compares as float64 compares."""
    host, _dirs, broker, _dm = table
    got = broker.query("SELECT COUNT(*) FROM lineitem WHERE l_discount "
                       "BETWEEN 0.05 AND 0.07").rows[0][0]
    hundredths = np.concatenate([oracle.integers(s, "l_discount")
                                 for s in host])
    want = int(((hundredths >= 5) & (hundredths <= 7)).sum())
    assert got == want and 0.2 < want / len(hundredths) < 0.35
    lt = broker.query("SELECT COUNT(*) FROM lineitem WHERE l_discount "
                      "< 0.07").rows[0][0]
    assert lt == int((hundredths < 7).sum())


def test_avg_and_count_beside_sum_ungrouped(table):
    host, _dirs, broker, _dm = table
    shape = {"preds": [["l_quantity", "lt", 24]], "group": [], "order": [],
             "aggs": [["SUM", "charge"], ["AVG", "l_extendedprice"],
                      ["COUNT", "*"], ["AVG", "l_discount"]]}
    rows = broker.query(statements.to_sql(shape)).rows
    assert oracle.same(rows, oracle.answer(host, shape), shape), rows


def test_an_empty_group_set(table, served):
    _host, _dirs, broker, _dm = table
    shape = dict(Q1, preds=[["l_shipdate", "le", 0]])
    sql = statements.to_sql(shape)
    assert broker.query(sql).rows == []
    assert served.execute(sql) == []
    assert oracle.answer(table[0], shape) == []


def test_min_and_max_over_a_double_are_its_values(table):
    host, _dirs, broker, _dm = table
    price = np.concatenate([s["l_extendedprice"] for s in host])
    rows = broker.query("SELECT MIN(l_extendedprice), MAX(l_extendedprice) "
                        "FROM lineitem WHERE l_quantity < 24").rows
    qty = np.concatenate([s["l_quantity"] for s in host])
    assert list(rows[0]) == [price[qty < 24].min(), price[qty < 24].max()]


def test_the_rule_is_float64_and_has_no_platform_in_it():
    import inspect
    assert np.dtype(float_acc_dtype()) == np.float64
    assert "default_backend" not in inspect.getsource(float_acc_dtype)
    assert not inspect.signature(float_acc_dtype).parameters


@pytest.mark.parametrize("key", ["q1.d90", "q6.y1994"])
def test_the_kernel_exported_for_the_tpu_accumulates_float64(table, key):
    """The plan cache keys a kernel by its platform string ("tpu"); built
    and exported for it with no device, the float partials it hands back
    are float64 and no float32 matmul carries a sum."""
    from jax import export

    from pinot_tpu.engine.executor import resolve_params
    dm = table[3]
    seg = dm.acquire_segments()[0]
    plan = plan_of(statements.to_sql(SHAPES[key]), dm)
    kernel = kernels.build_kernel(plan.kernel_plan, seg.bucket,
                                  platform="tpu", scatter=False)
    args = (seg.device_cols(plan.col_names), np.int32(seg.n_docs),
            resolve_params(plan))
    exported = export.export(jax.jit(kernel), platforms=["tpu"])(*args)
    outs = dict(zip(sorted(jax.eval_shape(kernel, *args)),
                    exported.out_avals))
    sums = {k: v for k, v in outs.items() if k.startswith("agg")
            and not k.endswith("_cnt") and "count" not in k}
    assert sums and all(v.dtype == jnp.float64 for v in sums.values()), outs
    dots = [line for line in exported.mlir_module().splitlines()
            if "dot_general" in line]
    assert not any("xf32>" in line for line in dots), dots


def exact_sums(cents, keys, space):
    return [int(cents[keys == g].sum()) for g in range(space)]


def test_float_sums_of_2_20_prices_meet_the_bound_and_float32_does_not():
    """ops/kernels._float_sums itself, blocked (256 blocks of 4,096
    rows), over seeded prices: every group's sum within 1e-12 of the
    exact integers; the same sums accumulated in float32 miss it by
    orders of magnitude."""
    n = 1 << 20
    assert n > kernels.FLOAT_SUM_BLOCK == 1 << 12
    rng = np.random.default_rng(20)
    cents = rng.integers(1, 51, n) * data.retail_cents(
        rng.integers(1, data.PARTS + 1, n))
    keys = rng.integers(0, 5, n).astype(np.int32)     # 4: in no sum
    price = cents / 100.0
    got = jax.jit(lambda p, k: kernels._float_sums(
        [Col(0)], (p,), (), k, 4))(price, keys)
    assert got.dtype == jnp.float64 and got.shape == (1, 4)
    want = [t / 100 for t in exact_sums(cents, keys, 4)]
    rel = [abs(float(g) - w) / w for g, w in zip(got[0], want)]
    assert max(rel) < 1e-12, rel
    narrow = [float(jnp.sum(jnp.where(keys == g, price, 0).astype(
        jnp.float32))) for g in range(4)]
    assert min(abs(g - w) / w for g, w in zip(narrow, want)) > 1e-9


def test_rows_that_no_block_divides_and_a_case_are_summed_flat():
    from pinot_tpu.ops.ir import Case, Cmp, Lit
    n = (1 << 12) * 3 + 5
    v = np.arange(n, dtype=np.float64) / 100.0
    got = kernels._float_sums([Col(0)], (jnp.asarray(v),), (),
                              jnp.zeros(n, jnp.int32), 1)
    assert float(got[0, 0]) == pytest.approx(v.sum(), rel=1e-14)
    # CASE WHEN v < 10.0 THEN v ELSE 0.0 END, over rows that do block
    v = np.arange(1 << 14, dtype=np.float64) / 100.0
    case = Case(((Cmp(Col(0), "<", 0), Col(0)),), Lit(1))
    got = kernels._float_sums(
        [case], (jnp.asarray(v),), (np.float64(10.0), np.float64(0.0)),
        jnp.zeros(len(v), jnp.int32), 1)
    assert float(got[0, 0]) == pytest.approx(v[v < 10.0].sum(), rel=1e-14)


@pytest.fixture(scope="module")
def mesh_table(tmp_path_factory):
    """LINEITEM as four segments that share their dictionaries, resident
    across four virtual devices."""
    from pinot_tpu.parallel import DistributedTable, segment_mesh
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.segment.builder import build_table_dictionaries
    from pinot_tpu.spi import TableConfig
    host = [data.gen_segment(1 << 13, SEED + 1, k) for k in range(4)]
    plain = [{n: c if isinstance(c, np.ndarray)
              else np.asarray(c.values)[c.codes] for n, c in seg.items()}
             for seg in host]
    schema = entry._schema(host[0], data.MEASURES)
    cfg = TableConfig(entry.TABLE)
    shared = build_table_dictionaries(schema, cfg, plain)
    out = str(tmp_path_factory.mktemp("lineitem_mesh"))
    dm = TableDataManager(entry.TABLE)
    for i, cols in enumerate(plain):
        dm.add_segment_dir(SegmentBuilder(schema, cfg).build(
            cols, out, f"seg_{i}", shared_dicts=shared))
    return host, DistributedTable(dm.acquire_segments(), segment_mesh(4))


@pytest.mark.parametrize("key", ["q1.d90", "q6.y1994"])
def test_the_mesh_holds_its_float_partials_to_the_bound(mesh_table, key):
    from pinot_tpu.engine.reduce import reduce_partials
    host, dist = mesh_table
    shape = SHAPES[key]
    ctx = build_query_context(parse_sql(statements.to_sql(shape)))
    before = float_counters()
    partial = dist.try_execute(ctx)
    assert partial is not None
    rows = reduce_partials(ctx, [partial]).rows
    assert oracle.same(rows, oracle.answer(host, shape), shape), rows
    after = float_counters()
    assert after["float_acc_wide"] > before["float_acc_wide"]
    assert after["float_acc_narrow"] == before["float_acc_narrow"]
