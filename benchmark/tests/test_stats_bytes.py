"""Percentile and rate arithmetic, the logical bytes of a query, the
statements' text, the table's rules and the comparison of answers."""
import numpy as np
import pytest

from benchmark import stats
from benchmark.ssb import bytes as ssb_bytes
from benchmark.ssb import data, oracle, statements


def test_percentile_linear_between_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_over_the_whole_window_stall_included():
    # 10 answers in the first second, then a 4 s stall: the rate is over
    # all 5 s, not over the busy second
    done = [100.0 + 0.1 * k for k in range(1, 11)]
    assert stats.rate(done, 100.0, 5.0) == pytest.approx(2.0)
    # an answer that lands after the window closed is not counted
    assert stats.rate(done + [105.5], 100.0, 5.0) == pytest.approx(2.0)


def test_a_stalled_request_moves_the_tail_not_the_median():
    ms = [100.0] * 19 + [4000.0]
    assert stats.percentile(ms, 50) == 100.0
    assert stats.percentile(ms, 90) == 100.0
    assert stats.percentile(ms, 99) > 3000.0


SHAPES = statements.load_shapes()
FOUR = {c: 4 for s in SHAPES.values() for c in ssb_bytes.columns_read(s)}


@pytest.mark.parametrize("sid,columns", [
    # hand-counted: distinct columns of predicates, value and group-by
    ("q1.1", ["d_year", "lo_discount", "lo_extendedprice", "lo_quantity"]),
    ("q1.3", ["d_weeknuminyear", "d_year", "lo_discount", "lo_extendedprice",
              "lo_quantity"]),
    ("q2.1", ["d_year", "lo_revenue", "p_brand", "p_category", "s_region"]),
    ("q3.4", ["c_city", "d_year", "d_yearmonthnum", "lo_revenue", "s_city"]),
    ("q4.3", ["d_year", "lo_revenue", "lo_supplycost", "p_brand",
              "p_category", "s_city", "s_nation"]),
])
def test_columns_read(sid, columns):
    assert ssb_bytes.columns_read(SHAPES[sid]) == columns


def test_logical_bytes_follow_the_resident_width():
    rows = 1 << 26
    assert ssb_bytes.logical_bytes(SHAPES["q1.1"], rows, FOUR.get) \
        == rows * 16
    # a narrowed dictionary-id column moves the bytes with it
    narrow = {**FOUR, "d_year": 1}
    assert ssb_bytes.logical_bytes(SHAPES["q1.1"], rows, narrow.get) \
        == rows * 13


@pytest.mark.parametrize("sid,sql", [
    # the source's statements, its date functions read from the
    # materialised columns
    ("q1.1", "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder "
             "WHERE d_year = 1993 AND lo_discount BETWEEN 1 AND 3 "
             "AND lo_quantity < 25"),
    ("q2.3", "SELECT d_year, p_brand, SUM(lo_revenue) FROM lineorder "
             "WHERE p_brand = 'MFGR#2239' AND s_region = 'EUROPE' "
             "GROUP BY d_year, p_brand ORDER BY d_year, p_brand "
             "LIMIT 100000"),
    ("q3.4", "SELECT c_city, s_city, d_year, SUM(lo_revenue) FROM lineorder "
             "WHERE (c_city = 'UNITED KI1' OR c_city = 'UNITED KI5') "
             "AND (s_city = 'UNITED KI1' OR s_city = 'UNITED KI5') "
             "AND d_yearmonthnum = 199712 GROUP BY c_city, s_city, d_year "
             "ORDER BY d_year, SUM(lo_revenue) DESC LIMIT 100000"),
    ("q4.3", "SELECT d_year, s_city, p_brand, "
             "SUM(lo_revenue - lo_supplycost) FROM lineorder "
             "WHERE s_nation = 'UNITED STATES' "
             "AND (d_year = 1997 OR d_year = 1998) "
             "AND p_category = 'MFGR#14' GROUP BY d_year, s_city, p_brand "
             "ORDER BY d_year, s_city, p_brand LIMIT 100000"),
])
def test_statement_text(sid, sql):
    assert statements.to_sql(SHAPES[sid]) == sql


def test_every_literal_is_a_value_the_table_can_hold():
    domains = {"p_brand": data.BRANDS, "p_category": data.CATEGORIES,
               "p_mfgr": data.MFGRS, "s_region": data.REGIONS,
               "c_region": data.REGIONS, "s_nation": data.NATIONS,
               "c_nation": data.NATIONS, "s_city": data.CITIES,
               "c_city": data.CITIES}
    columns = set(data.gen_segment(64, 1, 0))
    assert len(columns) == 17
    for shape in SHAPES.values():
        for col, _op, val in shape["preds"]:
            assert col in columns
            for v in val if isinstance(val, list) else [val]:
                assert col not in domains or v in domains[col], (col, v)
        for col in shape["group"] + [c for c in shape["value"]
                                     if c not in "*-"]:
            assert col in columns


def test_the_table_follows_dbgens_rules():
    assert (data.CUSTOMERS, data.SUPPLIERS, data.PARTS) \
        == (3_000_000, 200_000, 1_400_000)
    seg = data.gen_segment(1 << 14, 3_000_000_019, 1)
    price = seg["lo_extendedprice"].astype(np.int64)
    qty, disc = seg["lo_quantity"], seg["lo_discount"]
    assert (price % qty == 0).all()
    retail = price // qty
    assert retail.min() >= 90_000 and retail.max() <= 209_900
    assert (seg["lo_revenue"] == price * (100 - disc) // 100).all()
    assert (seg["lo_supplycost"] == 6 * retail // 10).all()
    assert qty.min() == 1 and qty.max() == 50
    assert disc.min() == 0 and disc.max() == 10
    # the date columns are one date's: the month of a week's days
    ym, week = seg["d_yearmonthnum"], seg["d_weeknuminyear"]
    assert (ym // 100 == seg["d_year"]).all()
    assert ((ym % 100 - 1) * 28 // 7 <= week).all()
    assert (week <= (ym % 100) * 31 // 7 + 1).all()
    assert ym.min() >= 199201 and ym.max() <= 199808
    # the hierarchies hold
    brand = np.asarray(seg["p_brand"].values)[seg["p_brand"].codes]
    cat_ = np.asarray(seg["p_category"].values)[seg["p_category"].codes]
    assert all(b.startswith(c) for b, c in zip(brand[:500], cat_[:500]))
    assert (seg["c_city"].codes // 10 == seg["c_nation"].codes).all()
    assert (seg["s_nation"].codes // 5 == seg["s_region"].codes).all()
    # the same seed and segment give the same rows; another segment others
    again = data.gen_segment(1 << 14, 3_000_000_019, 1)
    assert (again["lo_revenue"] == seg["lo_revenue"]).all()
    other = data.gen_segment(1 << 14, 3_000_000_019, 2)
    assert (other["lo_revenue"] != seg["lo_revenue"]).any()


Q31 = SHAPES["q3.1"]          # ORDER BY d_year, SUM DESC
ROWS = [("CHINA", "INDIA", 1992, 900), ("INDIA", "JAPAN", 1992, 700),
        ("JAPAN", "CHINA", 1992, 700), ("CHINA", "CHINA", 1993, 50)]


@pytest.mark.parametrize("got,verdict", [
    (ROWS, True),
    # the wire brings lists and a SUM as a double
    ([list(r[:3]) + [float(r[3])] for r in ROWS], True),
    # rows whose ORDER BY keys tie may come in either order
    ([ROWS[0], ROWS[2], ROWS[1], ROWS[3]], True),
    # the right rows against the ORDER BY
    ([ROWS[1], ROWS[0], ROWS[2], ROWS[3]], False),
    (list(reversed(ROWS)), False),
    # a sum off by one, a row missing, a row twice, a null
    ([ROWS[0], ROWS[1], ROWS[2], ROWS[3][:3] + (51,)], False),
    (ROWS[:3], False),
    (ROWS + ROWS[3:], False),
    ([ROWS[0], ROWS[1], ROWS[2], ROWS[3][:3] + (None,)], False),
    ([ROWS[0], ROWS[1], ROWS[2], ROWS[3][:3] + (50.5,)], False),
])
def test_same_rows_in_an_order_the_statement_allows(got, verdict):
    assert oracle.same(got, ROWS, Q31) is verdict


def test_the_reference_orders_its_answer_as_the_statement_says():
    segs = [data.gen_segment(1 << 14, 11, k) for k in range(2)]
    rows = oracle.answer(segs, Q31)
    keys = [(r[2], -r[3]) for r in rows]
    assert keys == sorted(keys) and len(rows) > 10
    total = sum(int(s["lo_revenue"][
        (s["c_region"].codes == 2) & (s["s_region"].codes == 2)
        & (s["d_year"] <= 1997)].astype(np.int64).sum()) for s in segs)
    assert sum(r[3] for r in rows) == total
    assert not oracle.same(oracle.answer(segs, Q31, round_to=np.float32)
                           if total > 1 << 24 else [], rows, Q31) \
        or total < 1 << 24
