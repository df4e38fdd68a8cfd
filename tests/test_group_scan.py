"""The full-scan group-by (strategy 'scan', ops/kernels._scan_group_aggs)
and the taxi deployment that forced it: the five statements of
``benchmark/taxi`` against their exact reference through the HTTP trio,
ROUND's ties, the fixed-point float sums held to 1e-12 (and a float32
accumulation that is not), the routes the planner gives as the TPU asks
for them at 2^23 rows, and the host path's counter.

conftest pins the MXU-shaped group-by (no CPU scatter), so what runs here
is the kernel the chip runs, at a small size."""
import os
import sys
from fractions import Fraction

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from benchmark.entries import served_http as ssb_entry  # noqa: E402
from benchmark.entries import served_http_taxi as entry  # noqa: E402
from benchmark.entries import served_http_tpch as tpch_entry  # noqa: E402
from benchmark.ssb import data as ssb_data  # noqa: E402
from benchmark.ssb import statements as ssb_statements  # noqa: E402
from benchmark.taxi import data, oracle, statements  # noqa: E402
from benchmark.tpch import data as tpch_data  # noqa: E402
from benchmark.tpch import statements as tpch_statements  # noqa: E402
from pinot_tpu.analysis.plan_verify import verify_kernel_plan  # noqa: E402
from pinot_tpu.broker import Broker  # noqa: E402
from pinot_tpu.multistage import costs  # noqa: E402
from pinot_tpu.ops import kernels  # noqa: E402
from pinot_tpu.ops.ir import AggSpec, Col, KernelPlan, TrueP  # noqa: E402
from pinot_tpu.query.context import build_query_context  # noqa: E402
from pinot_tpu.query.planner import SegmentPlanner  # noqa: E402
from pinot_tpu.query.sql import parse_sql  # noqa: E402
from pinot_tpu.segment import ImmutableSegment  # noqa: E402
from pinot_tpu.server import TableDataManager  # noqa: E402
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

SHAPES = statements.load_shapes()
SCAN = " OPTION(groupByStrategy=scan)"
SEEDS = (7, 39, 2_147_483_659)
SEGMENTS, ROWS = 2, 1 << 15


def build(tmp, host, name="trips"):
    """(segment directories, a Broker over them, their data manager)."""
    dirs = [entry.build_segment(cols, data.MEASURES, tmp, f"seg_{k}")
            for k, cols in enumerate(host)]
    dm = TableDataManager(name)
    for d in dirs:
        dm.add_segment_dir(d)
    broker = Broker()
    broker.register_table(dm)
    return dirs, broker, dm


def plan_of(sql, seg):
    return SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    host = [data.gen_segment(ROWS // SEGMENTS, SEEDS[0], k)
            for k in range(SEGMENTS)]
    dirs, broker, dm = build(str(tmp_path_factory.mktemp("trips")), host)
    return host, dirs, broker, dm


@pytest.fixture(scope="module", params=SEEDS, ids=lambda s: f"seed{s}")
def served(request, tmp_path_factory):
    """(host segments, the HTTP trio over them) for one seed."""
    host = [data.gen_segment(ROWS // SEGMENTS, request.param, k)
            for k in range(SEGMENTS)]
    tmp = str(tmp_path_factory.mktemp("served"))
    dirs = [entry.build_segment(cols, data.MEASURES, tmp, f"seg_{k}")
            for k, cols in enumerate(host)]
    system = entry.start({}, dirs, tmp)
    yield host, system
    system.stop()


@pytest.mark.parametrize("key", list(SHAPES))
def test_the_trio_answers_each_statement_on_the_scan_strategy(served, key):
    host, system = served
    shape = SHAPES[key]
    before = global_metrics.snapshot()["counters"]
    rows = system.execute(statements.to_sql(shape) + SCAN)
    after = global_metrics.snapshot()["counters"]
    assert oracle.same(rows, oracle.answer(host, shape), shape), rows
    assert after.get("kernel_dispatches_group_scan", 0) \
        > before.get("kernel_dispatches_group_scan", 0)
    assert after["segments_host"] == before["segments_host"]


def test_the_entry_refuses_a_host_route(served, monkeypatch):
    """What the entry asks of the planner: nothing on the host path, no
    float32 on the path; a forced host plan is refused by name."""
    _host, system = served
    seg = system._segments()[0]
    sqls = [statements.to_sql(s) for s in SHAPES.values()]
    assert entry.refusals(seg, sqls) == []
    forced = sqls[2] + " OPTION(forceHostExecution=true)"
    assert entry.refusals(seg, [forced]) == [f"host path: {forced}"]


def test_round_ties_go_to_even(tmp_path):
    """Distances planted on x.50 land in the even group, on the device as
    on the host path and in the reference, and a ROUND key comes back a
    DOUBLE."""
    cols = data.gen_segment(4096, 5, 0)
    ties = np.array([0.5, 1.5, 2.5, 3.5, 4.5, 2.49, 2.51, 198.5, 199.5])
    cols["trip_distance"][2:2 + len(ties)] = ties
    _dirs, broker, _dm = build(str(tmp_path), [cols])
    sql = ("SELECT ROUND(trip_distance), COUNT(*) FROM trips GROUP BY "
           "ROUND(trip_distance) LIMIT 1000")
    want = dict(zip(*np.unique(np.round(cols["trip_distance"]),
                               return_counts=True)))
    assert np.round(2.5) == 2.0 and np.round(3.5) == 4.0
    for option in (SCAN, "", " OPTION(forceHostExecution=true)"):
        rows = broker.query(sql + option).rows
        assert {r[0]: r[1] for r in rows} == want, option
        assert all(isinstance(r[0], float) for r in rows), option
    plan = plan_of(sql + SCAN, ImmutableSegment.load(_dirs[0]))
    assert plan.kernel_plan.strategy == "scan"
    assert plan.group_decoders == [("double", 0, 1, 201)]


def test_round_with_a_scale_of_zero_plans_as_round(table):
    seg = table[3].acquire_segments()[0]
    one = plan_of("SELECT ROUND(trip_distance), COUNT(*) FROM trips GROUP "
                  "BY ROUND(trip_distance)" + SCAN, seg)
    two = plan_of("SELECT ROUND(trip_distance, 0), COUNT(*) FROM trips "
                  "GROUP BY ROUND(trip_distance, 0)" + SCAN, seg)
    assert one.kind == two.kind == "kernel"
    assert one.kernel_plan == two.kernel_plan
    assert plan_of("SELECT ROUND(trip_distance, 1), COUNT(*) FROM trips "
                   "GROUP BY ROUND(trip_distance, 1)", seg).kind == "host"


def test_a_float32_accumulation_of_fares_fails_the_bound(table):
    """The tolerance tells the precisions apart: the zone tile's AVG with
    every fare held in float32 (the reference's control), or summed in a
    float32 accumulator, is not the answer; within 2e-13 it is."""
    host = table[0]
    shape = SHAPES["zone"]
    exact = oracle.answer(host, shape)
    assert oracle.same([list(r) for r in exact], exact, shape)
    assert not oracle.same(oracle.answer(host, shape, round_to=np.float32),
                           exact, shape)
    zone = np.concatenate([s["pu_location_id"] for s in host])
    fare = np.concatenate([s["fare_amount"] for s in host])
    acc32 = []
    for r in exact:
        held = fare[zone == r[0]].astype(np.float32)
        acc32.append(r[:2] + (float(np.cumsum(held)[-1]) / r[1],))
    assert not oracle.same(acc32, exact, shape)
    near = [r[:2] + (r[2] * (1 + 2e-13),) for r in exact]
    assert oracle.same(near, exact, shape)


def test_signed_float_sums_are_exact_on_the_scan(tmp_path):
    """Negative doubles ride the signed high half: every SUM and AVG
    within 1e-12 of the exact rational sum, on the scan as on the host."""
    rng = np.random.default_rng(3)
    cols = data.gen_segment(8192, 3, 0)
    cents = rng.integers(-5_000_000, 5_000_000, 8192)
    cols["fare_amount"] = cents / 100.0
    _dirs, broker, _dm = build(str(tmp_path), [cols])
    sql = ("SELECT pu_location_id, SUM(fare_amount), AVG(fare_amount), "
           "COUNT(*) FROM trips GROUP BY pu_location_id LIMIT 1000")
    zone = cols["pu_location_id"]
    for option in (SCAN, " OPTION(forceHostExecution=true)"):
        rows = broker.query(sql + option).rows
        assert len(rows) == len(np.unique(zone))
        for z, s, a, n in rows:
            exact = Fraction(int(cents[zone == z].sum()), 100)
            assert n == int((zone == z).sum())
            assert abs(Fraction(s) - exact) <= abs(exact) * Fraction(
                1, 10 ** 12), (option, z)
            assert abs(Fraction(a) - exact / n) <= abs(exact / n) \
                * Fraction(1, 10 ** 12), (option, z)


def test_the_sorted_post_serves_a_large_scan_space(table):
    """A space over FACTORIZED_GROUP_LIMIT (265 zones x 201 distances) takes
    the sorted post and, at 2^15 groups or more, the live-group transfer;
    integral sums ride it too. The host path is the reference."""
    _host, _dirs, broker, dm = table
    sql = ("SELECT pu_location_id, ROUND(trip_distance), COUNT(*), "
           "SUM(passenger_count), AVG(fare_amount) FROM trips GROUP BY "
           "pu_location_id, ROUND(trip_distance) LIMIT 100000")
    plan = plan_of(sql + SCAN, dm.acquire_segments()[0])
    assert plan.kernel_plan.strategy == "scan"
    assert plan.kernel_plan.group_space > kernels.GROUP_XFER_SPACE \
        > kernels.FACTORIZED_GROUP_LIMIT
    got = {tuple(r[:2]): r[2:] for r in broker.query(sql + SCAN).rows}
    want = {tuple(r[:2]): r[2:] for r in broker.query(
        sql + " OPTION(forceHostExecution=true)").rows}
    assert got.keys() == want.keys()
    for k, (n, s, a) in want.items():
        assert tuple(got[k][:2]) == (n, s), k
        assert abs(got[k][2] - a) <= 1e-12 * abs(a), k


def test_float_acc_forms_count_the_scan_wide_everywhere(table):
    seg = table[3].acquire_segments()[0]
    sql = statements.to_sql(SHAPES["zone"])
    scan = plan_of(sql + SCAN, seg).kernel_plan
    compact = plan_of(sql + " OPTION(groupByStrategy=compact)",
                      seg).kernel_plan
    assert scan.strategy == "scan" and compact.strategy == "compact"
    for platform in ("cpu", "tpu"):
        assert kernels.float_acc_forms(scan, platform) == (1, 0)
    assert kernels.float_acc_forms(compact, "tpu") == (0, 1)


def test_segments_host_counts_each_segment_the_host_path_answers(table):
    _host, _dirs, broker, _dm = table
    counters = global_metrics.snapshot()["counters"]
    assert "segments_host" in counters       # declared before any fallback
    before = counters["segments_host"]
    broker.query(statements.to_sql(SHAPES["q3"])
                 + " OPTION(forceHostExecution=true)")
    assert global_metrics.snapshot()["counters"]["segments_host"] \
        == before + SEGMENTS
    broker.query(statements.to_sql(SHAPES["q3"]) + SCAN)
    assert global_metrics.snapshot()["counters"]["segments_host"] \
        == before + SEGMENTS


# -- the routes, as the TPU asks for them at 2^23 rows a segment --------

# the parent's strategies for every statement the benchmark's other cells
# send (the 13 SSB statements, the 6 TPC-H ones): they must not move
PARENT = {**{k: "dense" for k in ("q1.1", "q1.2", "q1.3")},
          **{k: "compact" for k in ("q2.1", "q2.2", "q2.3", "q3.1", "q3.2",
                                    "q3.3", "q3.4", "q4.1", "q4.2",
                                    "q4.3")},
          **{k: "dense" for k in tpch_statements.load_shapes()}}
TAXI = {"q1": "dense", "q2": "dense", "q3": "scan", "q4": "scan",
        "zone": "scan"}
DATASETS = {"ssb": (ssb_data, ssb_statements, ssb_entry),
            "tpch": (tpch_data, tpch_statements, tpch_entry),
            "taxi": (data, statements, entry)}


@pytest.fixture(scope="module")
def one_segment(tmp_path_factory):
    """{dataset: one loaded segment of 2^14 rows}."""
    out = {}
    for name, (gen, _st, ent) in DATASETS.items():
        cols = gen.gen_segment(1 << 14, 5, 0)
        d = ent.build_segment(cols, gen.MEASURES,
                              str(tmp_path_factory.mktemp(name)), "seg_0")
        out[name] = ImmutableSegment.load(d)
    return out


@pytest.mark.parametrize("dataset,key,strategy", [
    (ds, k, s) for ds, want in (("ssb", PARENT), ("tpch", PARENT),
                                ("taxi", TAXI))
    for k, s in want.items()
    if k in DATASETS[ds][1].load_shapes()])
def test_the_route_as_the_tpu_asks_for_it(one_segment, monkeypatch, dataset,
                                          key, strategy):
    """The planner with the backend and a segment's bucket patched to
    what the chip holds (TPU, 2^23 rows): q3, q4 and the zone tile take
    the scan strategy, counted wide; every other cell's statement keeps
    its strategy."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ImmutableSegment, "bucket",
                        property(lambda self: 1 << 23))
    _gen, st, _ent = DATASETS[dataset]
    plan = plan_of(st.to_sql(st.load_shapes()[key]), one_segment[dataset])
    assert plan.kind == "kernel", plan.kind
    assert plan.kernel_plan.strategy == strategy
    assert kernels.float_acc_forms(plan.kernel_plan, "tpu")[1] == 0


@pytest.mark.parametrize("sel,compact_ok,want", [
    (1.0, True, "compact"), (0.25, True, "compact"),
    (1.0, False, "scan"), (0.001, True, "compact"),
    (0.001, False, "scan")])
def test_scan_or_compact_by_the_estimated_selectivity(sel, compact_ok, want):
    """Over the dense budget the selectivity does not decide: the compact
    strategy takes every plan it can lower, at any estimate, and the scan
    the rest."""
    s, trace = costs.choose_group_strategy(
        1 << 23, 265, sel, "tpu", False, False, 1, False, compact_ok,
        None, True)
    assert s == want and "reason" in trace
    # a viable dense plan never moves; nor one the scan cannot lower
    assert costs.choose_group_strategy(
        1 << 20, 265, sel, "tpu", False, False, 1, True, compact_ok, None,
        True)[0] != "scan"
    assert costs.choose_group_strategy(
        1 << 23, 265, sel, "tpu", False, False, 1, False, True, None,
        False)[0] == "compact"


def test_the_verifier_holds_the_scan_gates():
    ok = KernelPlan(pred=TrueP(), aggs=(AggSpec("count", None, True),
                                        AggSpec("sum", Col(1), False,
                                                bits=9)),
                    group_keys=((0, 300),), strategy="scan")
    assert verify_kernel_plan(ok, n_cols=2, n_params=0) == []
    for aggs in ((AggSpec("max", Col(1), False),),
                 (AggSpec("sum", Col(1), False, bits=63),)):
        bad = KernelPlan(pred=TrueP(), aggs=aggs, group_keys=((0, 300),),
                         strategy="scan")
        assert "PV107" in {d.rule for d in verify_kernel_plan(
            bad, n_cols=2, n_params=0)}


@pytest.fixture(scope="module")
def mesh_table(tmp_path_factory):
    """The trips as four segments that share their dictionaries, resident
    across four virtual devices."""
    from pinot_tpu.parallel import DistributedTable, segment_mesh
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.segment.builder import build_table_dictionaries
    from pinot_tpu.spi import IndexingConfig, TableConfig
    host = [data.gen_segment(1 << 12, SEEDS[1], k) for k in range(4)]
    plain = [{n: c if isinstance(c, np.ndarray)
              else np.asarray(c.values)[c.codes] for n, c in seg.items()}
             for seg in host]
    schema = entry._schema(host[0], data.MEASURES)
    cfg = TableConfig(entry.TABLE, indexing=IndexingConfig(
        no_dictionary_columns=list(entry.RAW_LONGS)))
    shared = build_table_dictionaries(schema, cfg, plain)
    out = str(tmp_path_factory.mktemp("trips_mesh"))
    dm = TableDataManager(entry.TABLE)
    for i, cols in enumerate(plain):
        dm.add_segment_dir(SegmentBuilder(schema, cfg).build(
            cols, out, f"seg_{i}", shared_dicts=shared))
    return host, DistributedTable(dm.acquire_segments(), segment_mesh(4))


@pytest.mark.parametrize("key", ["q3", "q4", "zone"])
def test_the_mesh_program_runs_the_scan_strategy(mesh_table, key):
    """One mesh program a statement on the scan strategy (the mesh's
    dense route, the local shard flattened): the same answers."""
    from pinot_tpu.engine.reduce import reduce_partials
    host, dist = mesh_table
    shape = SHAPES[key]
    ctx = build_query_context(parse_sql(statements.to_sql(shape) + SCAN))
    before = global_metrics.snapshot()["counters"]
    partial = dist.try_execute(ctx)
    assert partial is not None
    rows = reduce_partials(ctx, [partial]).rows
    assert oracle.same(rows, oracle.answer(host, shape), shape), rows
    after = global_metrics.snapshot()["counters"]
    assert after["kernel_dispatches_mesh_dense"] \
        == before.get("kernel_dispatches_mesh_dense", 0) + 1


@pytest.mark.parametrize("name,ref", [("round", np.round),
                                      ("floor", np.floor)])
def test_whole_numbers_follow_numpy_beside_every_tie(name, ref):
    """The kernel's ROUND (half to even) and FLOOR of a double, from a
    conversion and a subtraction: numpy's answer at ties, beside them,
    past 2^52, and for NaN and the infinities (the chip's check is the
    same function, tests/tpu_hw_script.py)."""
    import tpu_hw_script
    assert tpu_hw_script.whole_number_misses(name, ref).size == 0


def test_the_civil_date_fields_follow_numpy_over_int64_millis():
    """YEAR, MONTH, DAY and QUARTER of milliseconds since 1970, with the
    era in int64 and the rest in int32, against numpy's datetime64 over
    +-2^52 ms (past 140,000 years either way), the day edges and the
    taxi table's first and last pickup."""
    import tpu_hw_script
    assert tpu_hw_script.TAXI_MS == (data.FIRST_MS, data.END_MS - 1)
    for name in ("year", "month", "day", "quarter"):
        assert tpu_hw_script.civil_date_misses(name).size == 0, name


def test_floor_keys_and_what_stays_on_the_host(table):
    """FLOOR(x) is a key of its own range; FLOOR with a scale and ROUND
    with a scale other than 0 have no device key and stay on the host
    path, which answers them (or refuses them) as it always did."""
    _host, _dirs, broker, dm = table
    seg = dm.acquire_segments()[0]
    sql = ("SELECT FLOOR(fare_amount), COUNT(*) FROM trips GROUP BY "
           "FLOOR(fare_amount) LIMIT 1000")
    plan = plan_of(sql + SCAN, seg)
    assert plan.kernel_plan.strategy == "scan"
    lo = int(np.floor(seg.columns["fare_amount"].min))
    assert plan.group_decoders[0][:2] == ("double", lo)
    got = broker.query(sql + SCAN).rows
    assert sorted(got) == sorted(broker.query(
        sql + " OPTION(forceHostExecution=true)").rows)
    assert plan_of("SELECT FLOOR(fare_amount, 0), COUNT(*) FROM trips "
                   "GROUP BY FLOOR(fare_amount, 0)", seg).kind == "host"


@pytest.mark.parametrize("agg,where,strategy", [
    ("AVG(fare_amount)", "", "scan"),
    ("AVG(fare_amount)", " WHERE passenger_count = 5", "scan"),
    ("SUM(passenger_count)", "", "compact"),
    ("SUM(passenger_count)", " WHERE passenger_count = 5", "compact"),
], ids=["float_all_rows", "float_selective", "integer_all_rows",
        "integer_selective"])
def test_a_float_sum_never_compacts_where_float64_is_emulated(
        one_segment, monkeypatch, agg, where, strategy):
    """As the TPU asks: over the one-hot budget an integer plan takes the
    compact strategy at any selectivity; a float SUM or AVG takes the
    scan at any selectivity (the compact post's float64 one-hot needs
    22 GB a segment there)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ImmutableSegment, "bucket",
                        property(lambda self: 1 << 23))
    plan = plan_of(f"SELECT pu_location_id, COUNT(*), {agg} FROM trips"
                   f"{where} GROUP BY pu_location_id LIMIT 1000",
                   one_segment["taxi"])
    assert plan.kernel_plan.strategy == strategy
    assert kernels.float_acc_forms(plan.kernel_plan, "tpu")[1] == 0
