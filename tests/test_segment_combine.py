"""The server's combine of a statement's group-by segments
(engine/executor.place_group_partials, from engine/batch.py): the one
partial it builds answers, through the broker's reduce, what the
segments' own partials answer, rows and their order, every float alike
to the bit. Held on the taxi statements, three SSB group-bys and TPC-H
Q1 over small tables of four segments, on each route that hands a
segment's groups over in array form, beside a host-path and a spilled
segment, and on the plans that stay per segment (a DISTINCTCOUNT, a
null-aware plan)."""
import os
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.entries import served_http as ssb_entry  # noqa: E402
from benchmark.entries import served_http_taxi as taxi_entry  # noqa: E402
from benchmark.entries import served_http_tpch as tpch_entry  # noqa: E402
from benchmark.ssb import data as ssb_data  # noqa: E402
from benchmark.ssb import statements as ssb_statements  # noqa: E402
from benchmark.taxi import data as taxi_data  # noqa: E402
from benchmark.taxi import statements as taxi_statements  # noqa: E402
from benchmark.tpch import data as tpch_data  # noqa: E402
from benchmark.tpch import statements as tpch_statements  # noqa: E402
from pinot_tpu.engine import batch as eb  # noqa: E402
from pinot_tpu.engine.executor import (GroupByPartial,  # noqa: E402
                                       GroupColumns, combine_group_columns,
                                       execute_plan, group_partial)
from pinot_tpu.engine.reduce import merge_groups, reduce_partials  # noqa
from pinot_tpu.ops import kernels as K  # noqa: E402
from pinot_tpu.query.context import build_query_context  # noqa: E402
from pinot_tpu.query.planner import SegmentPlanner  # noqa: E402
from pinot_tpu.query.sql import parse_sql  # noqa: E402
from pinot_tpu.segment import ImmutableSegment  # noqa: E402
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

N_SEG, ROWS = 4, 1 << 12
SEED = 2_147_483_659
DATASETS = {"taxi": (taxi_data, taxi_statements, taxi_entry),
            "ssb": (ssb_data, ssb_statements, ssb_entry),
            "tpch": (tpch_data, tpch_statements, tpch_entry)}
SCAN = " OPTION(groupByStrategy=scan)"
# (dataset, statement, option): the taxi shapes on the planner's
# strategy and on the scan the chip gives q3, q4 and the zone tile
STATEMENTS = [("taxi", k, o) for k in taxi_statements.load_shapes()
              for o in ("", SCAN)] + \
    [("ssb", k, "") for k in ("q2.1", "q3.1", "q4.3")] + \
    [("tpch", "q1.d90", "")]
# every numeric state over the taxi table: COUNT, integral and DOUBLE
# SUM, AVG, MIN and MAX, under YEAR and ROUND keys, ordered on a column
# with ties and cut by a LIMIT that falls among them
EVERY_STATE = (
    "SELECT passenger_count, YEAR(pickup_datetime), ROUND(trip_distance), "
    "COUNT(*), SUM(passenger_count), AVG(pu_location_id), "
    "MIN(pu_location_id), MAX(pu_location_id), SUM(fare_amount), "
    "AVG(total_amount), MIN(fare_amount), MAX(total_amount) FROM trips "
    "GROUP BY passenger_count, YEAR(pickup_datetime), ROUND(trip_distance) "
    "ORDER BY COUNT(*) DESC LIMIT 300")
# DOUBLE states over TPC-H's two flags: every segment holds the same
# dictionaries, so the statement is one vmapped launch
MIXED = ("SELECT l_returnflag, l_linestatus, COUNT(*), SUM(l_extendedprice), "
         "AVG(l_discount), MIN(l_tax), MAX(l_quantity) FROM lineitem "
         "GROUP BY l_returnflag, l_linestatus")
COUNTERS = ("segments_combined", "segments_extracted", "segments_host")


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    """{dataset: four loaded segments, each drawn on its own}."""
    out = {}
    for name, (gen, _st, entry) in DATASETS.items():
        tmp = str(tmp_path_factory.mktemp(name))
        out[name] = [ImmutableSegment.load(entry.build_segment(
            gen.gen_segment(ROWS, SEED, k), gen.MEASURES, tmp, f"seg_{k}"))
            for k in range(N_SEG)]
    return out


def sql_of(dataset, key):
    st = DATASETS[dataset][1]
    return st.to_sql(st.load_shapes()[key])


def plans_for(segments, sql):
    ctx = build_query_context(parse_sql(sql))
    return [SegmentPlanner(ctx, s).plan() for s in segments]


def bits(v):
    """A value with its type, a float as its exact bits."""
    if isinstance(v, float):
        return ("float", v.hex())
    if isinstance(v, (tuple, list)):
        return tuple(bits(x) for x in v)
    return (type(v).__name__, v)


def moved(fn):
    before = global_metrics.snapshot()["counters"]
    out = fn()
    after = global_metrics.snapshot()["counters"]
    return out, {c: after.get(c, 0) - before.get(c, 0) for c in COUNTERS}


def same_answer(plans, got, serial):
    """``got`` merges and reduces as ``serial`` (the segments' own
    partials) does: groups in the same order, values to the bit."""
    ctx = plans[0].ctx
    assert len(got) == len(serial)
    assert bits(list(merge_groups(ctx.aggregations, got).items())) == \
        bits(list(merge_groups(ctx.aggregations, serial).items()))
    assert bits(reduce_partials(ctx, got).rows) == \
        bits(reduce_partials(ctx, serial).rows)


def combined_whole(plans):
    """The statement through the batched dispatch, held to its segments
    one by one; every segment entered the combine."""
    serial = [execute_plan(p) for p in plans]
    got, counts = moved(lambda: eb.execute_plans_batched(plans))
    same_answer(plans, got, serial)
    assert [bool(p.groups) for p in got[1:]] == [False] * (N_SEG - 1)
    assert counts == {"segments_combined": N_SEG, "segments_extracted": 0,
                      "segments_host": 0}
    return serial, got


@pytest.fixture
def window(monkeypatch):
    """Compact plans down the per-segment launch window, as the chip's
    2^23-row segments send them (the segmented kernel refused)."""
    monkeypatch.setattr(K, "segmented_compact_fits",
                        lambda plan, bucket, n: False)


@pytest.mark.parametrize("dataset,key,option", STATEMENTS,
                         ids=[f"{d}-{k}{'-scan' if o else ''}"
                              for d, k, o in STATEMENTS])
def test_the_combined_partial_answers_as_the_segments_do(
        tables, dataset, key, option):
    plans = plans_for(tables[dataset], sql_of(dataset, key) + option)
    assert {p.kind for p in plans} == {"kernel"}
    serial, got = combined_whole(plans)
    assert any(p.groups for p in serial)


@pytest.mark.parametrize("key", ["q2.1", "q3.1", "q4.3"])
def test_the_launch_window_route_combines_too(tables, window, key):
    plans = plans_for(tables["ssb"], sql_of("ssb", key))
    assert {p.kernel_plan.strategy for p in plans} == {"compact"}
    combined_whole(plans)


ROUTES = {"dense_vmap": ("tpch", MIXED),
          "compact_segmented": ("ssb", sql_of("ssb", "q3.1")),
          "dense_per_segment": ("taxi", sql_of("taxi", "q4"))}


@pytest.mark.parametrize("family", sorted(ROUTES))
def test_each_route_hands_its_segments_to_the_combine(tables, family):
    """The vmapped launch, the segmented compact launch and the launch
    window (where the segments' dictionaries differ, as the taxi
    table's do here, each segment is a group of its own)."""
    dataset, sql = ROUTES[family]
    key = "kernel_dispatches_" + family
    before = global_metrics.snapshot()["counters"].get(key, 0)
    combined_whole(plans_for(tables[dataset], sql))
    assert global_metrics.snapshot()["counters"][key] > before


@pytest.mark.parametrize("option", ["", SCAN])
def test_every_numeric_state_combines_to_the_bit(tables, option):
    plans = plans_for(tables["taxi"], EVERY_STATE + option)
    serial, _got = combined_whole(plans)
    # the ORDER BY meets ties, and the LIMIT cuts among them
    counts = sorted((s[0] for p in serial for s in p.groups.values()),
                    reverse=True)
    assert len(counts) > 300 and counts[299] == counts[300]


@pytest.mark.parametrize("column,key", [("pu_location_id", "zone"),
                                        ("passenger_count", "q4")])
def test_dictionaries_differ_between_the_segments(tables, column, key):
    """The key's dictionary differs between the segments: one
    dictionary id names another value elsewhere, and the combine joins
    values, not ids."""
    dicts = [tuple(s.dictionary(column).values) for s in tables["taxi"]]
    assert len(set(dicts)) > 1
    combined_whole(plans_for(tables["taxi"], sql_of("taxi", key)))


def spill_vmapped(monkeypatch, segment):
    """The vmapped launch reports ``segment``'s live groups over the
    transfer cap: that segment is run again alone, to dense outputs."""
    real = eb._vmapped_kernel

    def spilling(plan_struct, bucket):
        fn = real(plan_struct, bucket)

        def run(cols, n_docs, params):
            out = dict(fn(cols, n_docs, params))
            flags = np.zeros(len(n_docs), dtype=np.int32)
            flags[segment] = 1
            out["group_overflow"] = jnp.asarray(flags)
            return out
        return run
    monkeypatch.setattr(eb, "_vmapped_kernel", spilling)


@pytest.mark.parametrize("host,spill,combined", [
    (2, 3, 2),      # the host segment stops the combine at segment 2
    (3, 1, 1),      # the spilled one at segment 1: nothing to combine
    (3, 3, 3),      # the host segment last: the three ahead combine
], ids=["host2-spill3", "host3-spill1", "host3"])
def test_a_host_and_a_spilled_segment_stay_on_their_own(
        tables, monkeypatch, host, spill, combined):
    segs = tables["tpch"]
    plans = plans_for(segs, MIXED)
    plans[host] = plans_for(
        [segs[host]], MIXED + " OPTION(forceHostExecution=true)")[0]
    assert plans[host].kind == "host"
    serial = [execute_plan(p) for p in plans]
    if spill != host:
        spill_vmapped(monkeypatch, spill - (spill > host))
    got, counts = moved(lambda: eb.execute_plans_batched(plans))
    same_answer(plans, got, serial)
    n_combined = combined if combined > 1 else 0
    assert counts == {"segments_combined": n_combined,
                      "segments_extracted": N_SEG - 1 - n_combined,
                      "segments_host": 1}
    if n_combined:
        assert all(not got[i].groups for i in range(1, n_combined))


@pytest.mark.parametrize("at,combined", [(0, 0), (2, 2), (3, 3)],
                         ids=["first", "between", "last"])
def test_a_rollup_partial_stops_the_combine_where_it_sits(
        tables, at, combined):
    """A rollup's partial (engine/serving.execute_planned puts it among
    the executed ones) is met by the merge where it sits: no combine
    reaches over it."""
    from pinot_tpu.engine.serving import TableExecution, execute_planned
    segs = tables["tpch"]
    real = plans_for(segs[:3], MIXED)
    rollup = execute_plan(plans_for(segs[3:], MIXED)[0])
    assert rollup.groups
    ex = TableExecution(real[:at] + [None] + real[at:], real)
    ex._precomputed = {at: rollup}
    serial = [execute_plan(p) for p in real]
    serial.insert(at, rollup)
    got, counts = moved(lambda: execute_planned(ex))
    same_answer(real, got, serial)
    assert counts == {"segments_combined": combined if combined > 1 else 0,
                      "segments_extracted": 3 - combined if combined > 1
                      else 3, "segments_host": 0}


@pytest.mark.parametrize("sql", [
    "SELECT passenger_count, DISTINCTCOUNT(pu_location_id), COUNT(*) "
    "FROM trips GROUP BY passenger_count LIMIT 100",
    "SELECT passenger_count, YEAR(pickup_datetime), SUM(fare_amount), "
    "MIN(pu_location_id) FROM trips GROUP BY passenger_count, "
    "YEAR(pickup_datetime) LIMIT 1000 OPTION(enableNullHandling=true)",
], ids=["distinctcount", "null_aware"])
def test_what_stays_per_segment(tables, sql):
    plans = plans_for(tables["taxi"], sql)
    assert {p.kind for p in plans} == {"kernel"}
    serial = [execute_plan(p) for p in plans]
    got, counts = moved(lambda: eb.execute_plans_batched(plans))
    assert [list(p.groups.items()) for p in got] == \
        [list(p.groups.items()) for p in serial]
    same_answer(plans, got, serial)
    assert counts == {"segments_combined": 0, "segments_extracted": N_SEG,
                      "segments_host": 0}


def test_one_segment_is_extracted_as_it_was(tables):
    plans = plans_for(tables["taxi"][:1], sql_of("taxi", "q4"))
    (got,), counts = moved(lambda: eb.execute_plans_batched(plans))
    assert list(got.groups.items()) == \
        list(execute_plan(plans[0]).groups.items())
    assert counts == {"segments_combined": 0, "segments_extracted": 1,
                      "segments_host": 0}


def test_the_counters_are_declared_and_show_in_prometheus():
    counters = global_metrics.snapshot()["counters"]
    assert "segments_combined" in counters
    assert "segments_extracted" in counters
    text = global_metrics.prometheus()
    assert "segments_combined" in text and "segments_extracted" in text


# -- the combine on hand-made columns: what it refuses, what it keeps ----

def form(keys, states, kinds):
    return GroupColumns([np.asarray(k) for k in keys],
                        [tuple(np.asarray(p) for p in s) for s in states],
                        kinds, True)


def held(forms, kinds):
    """The combine equals the merge of the forms' own partials."""
    aggs = [SimpleNamespace(kind=k) for k in kinds]
    want = merge_groups(aggs, [group_partial(f) for f in forms])
    got = combine_group_columns(forms)
    assert got is not None
    assert bits(list(got.groups.items())) == bits(list(want.items()))
    return got


def test_float_sums_add_in_segment_order():
    """1e16 + 1 + 1 - 1e16 is 0 in segment order, 2 in any other."""
    kinds = ["sum", "avg"]
    forms = [form([[7]], [([v],), ([v], [1])], kinds)
             for v in (1e16, 1.0, 1.0, -1e16)]
    got = held(forms, kinds)
    assert got.groups[(7,)] == [0.0, (0.0, 4)]


def test_min_and_max_keep_what_min_and_max_keep():
    """min(a, b) keeps a unless b < a: a signed zero and a NaN stay
    where the merge leaves them (np.minimum would move both)."""
    kinds = ["min", "max"]
    vals = [0.0, -0.0, np.nan, -1.0, np.nan]
    forms = [form([["k"]], [([v],), ([v],)], kinds) for v in vals]
    held(forms, kinds)
    held(forms[2:], kinds)


def test_first_seen_order_and_the_first_key_value():
    """Groups come in the order the merge first meets them; a key equal
    across segments (0.0 and -0.0) keeps its first segment's value."""
    kinds = ["count"]
    forms = [form([["b", "a"], [0.0, 1.0]], [([1, 2],)], kinds),
             form([["c", "b"], [2.0, -0.0]], [([3, 4],)], kinds),
             form([["a", "c"], [1.0, 2.0]], [([5, 6],)], kinds)]
    got = held(forms, kinds)
    assert list(got.groups) == [("b", 0.0), ("a", 1.0), ("c", 2.0)]


@pytest.mark.parametrize("forms", [
    # a NaN key: the merge never joins two of them
    [form([[np.nan]], [([1],)], ["count"]),
     form([[np.nan]], [([1],)], ["count"])],
    # a key column of another kind in another segment
    [form([[1]], [([1],)], ["count"]), form([[1.0]], [([1],)], ["count"])],
    # an integral sum that could leave int64
    [form([[1]], [([1 << 61],)], ["sum"]),
     form([[1]], [([1 << 61],)], ["sum"])],
    # an integral state beside a float one of the same aggregation
    [form([[1]], [([1],)], ["sum"]), form([[1]], [([1.0],)], ["sum"])],
], ids=["nan_key", "key_kind", "int64_bound", "state_dtype"])
def test_what_the_combine_refuses(forms):
    assert combine_group_columns(forms) is None


def test_an_uncombinable_segment_refuses_the_combine():
    kinds = ["count"]
    forms = [form([[1]], [([1],)], kinds), form([[1]], [([2],)], kinds)]
    forms[1].combinable = False
    assert combine_group_columns(forms) is None


def test_segments_without_groups_combine_to_nothing():
    kinds = ["count"]
    empty = form([np.zeros(0, np.int64)], [(np.zeros(0, np.int64),)], kinds)
    assert combine_group_columns([empty, empty]) == GroupByPartial({})
    full = form([[3]], [([2],)], kinds)
    assert held([empty, full, empty, full], kinds).groups == {(3,): [4]}
