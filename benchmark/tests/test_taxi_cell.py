"""The taxi cell through the harness at a small size on the CPU: the
program's answers are ``correct`` on seeds, the plain reference put in
its place is too, and a planted fault is not. The configuration's file,
its statements and its metrics are what the contract asks."""
import numpy as np
import pytest

from benchmark import catalog as cat
from benchmark.taxi import bytes as tbytes
from benchmark.taxi import data, oracle, statements

CELL = "taxi1.rides_c1"
TINY = {"rows": 1 << 15, "segments": 2}
SHAPES = statements.load_shapes()


class ReferenceInPlace:
    """The plain reference in the program's place, answering from
    ``segments`` with every addend rounded to ``round_to``."""

    def __init__(self, system, segments, round_to=None):
        self._system, self._segments = system, segments
        self._round_to = round_to
        self._by_sql = {statements.to_sql(s): s for s in SHAPES.values()}

    def execute(self, sql):
        shape = self._by_sql[sql.split(" OPTION(")[0]]
        return [list(r) for r in oracle.answer(self._segments, shape,
                                               round_to=self._round_to)]

    execute_warm = execute

    def __getattr__(self, name):     # counters, resident_itemsize, stop ...
        return getattr(self._system, name)


def tiny_run(seed, wrap=None, seconds=0.3):
    from benchmark import run
    return run.run_cell(CELL, seed, seconds, False, catalog=cat.Catalog(),
                        check_chip=False, config_override=TINY,
                        wrap_system=wrap)


def segments(seed, keep=None):
    n = TINY["segments"]
    return [data.gen_segment(TINY["rows"] // n, seed, k)
            for k in range(n)][:keep]


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_program_is_correct_on_the_cell(seed):
    res = tiny_run(seed, seconds=2.0)
    assert res["correct"], res["compared"]
    assert res["compared"]["answers_compared"]["value"] >= 5
    assert set(res["metrics"]) == {"queries_per_s", "query_p50_ms",
                                   "query_p90_ms", "setup_s"}


@pytest.mark.parametrize("fault", [
    lambda seed: lambda system, own: ReferenceInPlace(
        system, own, round_to=np.float32),              # float32 addends
    lambda seed: lambda system, own: ReferenceInPlace(
        system, segments(seed, keep=1)),                # a segment left out
    lambda seed: lambda system, own: ReferenceInPlace(
        system, segments(seed + 1)),                    # a stale table
], ids=["float32_addends", "a_segment_left_out", "stale_table"])
def test_a_planted_fault_comes_out_as_not_correct(fault):
    assert tiny_run(17, lambda s, o: ReferenceInPlace(s, o))["correct"]
    res = tiny_run(17, fault(17))
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_the_configurations_file_and_the_cells_metrics():
    c = cat.Catalog()
    conf = c.config("nyc_taxi_trips_1chip")
    assert (conf["rows"], conf["segments"], conf["chips"],
            conf["replication"]) == (1 << 26, 8, 1, 1)
    assert conf["dataset"] == "taxi" and conf["entry"] == "served_http_taxi"
    assert sorted(conf["reduced"]) == ["columns", "rows"]
    assert any("1e-12" in g for g in conf["guarantees"])
    read = set().union(*(tbytes.columns_read(s) for s in SHAPES.values()))
    assert set(conf["schema"]) == read and len(read) == conf["columns"]
    cell = c.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "rides_c1"
    mix = c.traffic("rides_c1")
    assert mix["shapes"] == ["q1", "q2", "q3", "q4", "zone"]
    assert mix["clients"] == 1
    assert {m["name"] for m in c.metrics_for(CELL, True)} >= {
        "host_segments_per_query", "group_scan_roofline",
        "flight_p50_ms.taxi_q4", "flight_p50_ms.taxi_zone",
        "float_acc_wide_share", "kernel_ms_per_query", "scan_roofline"}
    assert statements.to_sql(SHAPES["q4"]) == (
        "SELECT passenger_count, YEAR(pickup_datetime), "
        "ROUND(trip_distance), COUNT(*) FROM trips GROUP BY "
        "passenger_count, YEAR(pickup_datetime), ROUND(trip_distance) "
        "ORDER BY YEAR(pickup_datetime), COUNT(*) DESC LIMIT 100000")
    assert tbytes.logical_bytes(SHAPES["zone"], 10, {
        "fare_amount": 8, "pu_location_id": 4}.__getitem__) == 120


def test_the_generator_holds_its_assumptions():
    seg = data.gen_segment(1 << 16, 3, 1)
    again = data.gen_segment(1 << 16, 3, 1)
    assert all((np.asarray(seg[c] if isinstance(seg[c], np.ndarray)
                           else seg[c].codes)
                == np.asarray(again[c] if isinstance(again[c], np.ndarray)
                              else again[c].codes)).all() for c in seg)
    years = oracle.key_values(seg, "year")[0]
    assert years.tolist() == list(range(2009, 2016))
    assert (np.diff(seg["pickup_datetime"]) >= 0).all()
    assert 0.83 < seg["cab_type"].codes.mean() < 0.87
    assert sorted(set(seg["pu_location_id"])) == list(range(1, 266))
    share = np.bincount(seg["passenger_count"], minlength=10) / (1 << 16)
    assert 0.68 < share[1] < 0.72 and 0.12 < share[2] < 0.16
    d = seg["trip_distance"]
    assert d.min() == 0.0 and 199.5 <= d.max() < 200
    assert 1.4 < np.median(d[d > 0]) < 1.8
    assert 8.5 < np.median(seg["fare_amount"]) < 10.5
    assert seg["fare_amount"].max() < 400 <= seg["total_amount"].max() < 512
    assert (seg["total_amount"] > seg["fare_amount"]).all()
    cents = oracle.cents(seg, "fare_amount")
    assert (cents / 100.0 == seg["fare_amount"]).all()


def test_the_full_size_control_reads_both_sides_of_the_limit():
    """The chip's reading script at a small size: the program's largest
    AVG error is under the limit and the float32 control's over it."""
    from benchmark.tests import control_taxi_full_size as control
    r = control.reading(23, TINY, check_chip=False, seconds=1.0)
    assert r["program"]["correct"] and not r["control"]["correct"]
    assert max(r["program"]["worst"].values()) < oracle.TOLERANCE
    assert set(r["program"]["worst"]) == set(SHAPES)
    assert min(r["control"]["worst"][k] for k in ("q2", "zone")) \
        > oracle.TOLERANCE


@pytest.mark.parametrize("fault,want", [
    (lambda rows: rows, 0.0),
    (lambda rows: rows[1:], float("inf")),
    (lambda rows: [r[:1] + (r[1] + 1,) + r[2:] if i == 0 else r
                   for i, r in enumerate(rows)], float("inf")),
    (lambda rows: [r[:-1] + (r[-1] * (1 + 3e-12),) if i == 0 else r
                   for i, r in enumerate(rows)], 3e-12),
], ids=["exact", "a_group_missing", "a_count_off", "an_avg_off"])
def test_worst_error_reads_what_same_judges(fault, want):
    shape = SHAPES["zone"]
    exact = oracle.answer(segments(5), shape)
    got = fault(list(exact))
    err = oracle.worst_error(got, exact, shape)
    assert err == pytest.approx(want, rel=1e-3) if want else err == want
    assert oracle.same(got, exact, shape) is (err <= oracle.TOLERANCE)
