"""The TPC-H data set: the generator follows the rules its ``ASSUMED``
lists, the statements are the specification's, the exact reference agrees
with a brute-force loop, and ``same`` refuses what the configuration's
guarantee refuses."""
import json
import os
from fractions import Fraction

import numpy as np
import pytest

from benchmark import catalog as cat
from benchmark.tpch import bytes as tbytes
from benchmark.tpch import data, oracle, statements

SHAPES = statements.load_shapes()
KEYS = ["q1.d90", "q6.y1994", "q1.d60", "q6.y1993", "q1.d120", "q6.y1997"]


@pytest.fixture(scope="module")
def seg():
    return data.gen_segment(1 << 17, 35, 0)


def test_the_flags_follow_the_current_date(seg):
    flag = np.asarray(seg["l_returnflag"].values)[seg["l_returnflag"].codes]
    status = np.asarray(seg["l_linestatus"].values)[seg["l_linestatus"].codes]
    ship = seg["l_shipdate"]
    assert ((status == "O") == (ship > data.CURRENT_DAY)).all()
    # a receipt is 1-30 days after its shipment: shipped after the current
    # date means not returned yet, received by it means R or A by a coin
    assert (flag[ship > data.CURRENT_DAY] == "N").all()
    early = ship + 30 <= data.CURRENT_DAY
    assert set(flag[early]) == {"A", "R"}
    assert 0.45 < (flag[early] == "R").mean() < 0.55
    assert data.EPOCH_FIRST_DAY + 1 <= ship.min()
    assert ship.max() <= data.EPOCH_FIRST_DAY + data.DAYS - 1 + 121 == 10561
    assert seg["l_returnflag"].values == sorted(seg["l_returnflag"].values)


def test_a_price_is_quantity_times_a_retail_price(seg):
    cents = oracle.integers(seg, "l_extendedprice")
    qty = oracle.integers(seg, "l_quantity")
    assert (cents % qty == 0).all()
    retail = cents // qty
    assert 90_000 <= retail.min() and retail.max() <= 90_000 + 20_000 + 99_900
    assert data.retail_cents(np.array([1, 10, 1_000, 19_999_999])).tolist() \
        == [90_100, 90_001 + 1_000, 90_100, 90_000 + 19_900 + 99_900]
    assert qty.min() == 1 and qty.max() == 50
    assert set(oracle.integers(seg, "l_discount")) == set(range(11))
    assert set(oracle.integers(seg, "l_tax")) == set(range(9))
    # the double handed over is the decimal's nearest
    assert seg["l_extendedprice"][0] == float(
        Fraction(int(cents[0]), 100))
    assert seg["l_discount"].dtype == np.float64


def test_an_order_has_one_to_seven_lines_in_dbgens_order():
    a = data.gen_segment(4096, 3, 1)
    b = data.gen_segment(4096, 3, 1)
    assert all((a[c] == b[c]).all() if isinstance(a[c], np.ndarray)
               else (a[c].codes == b[c].codes).all() for c in a)
    other = data.gen_segment(4096, 3, 2)
    assert not (a["l_shipdate"] == other["l_shipdate"]).all()
    with pytest.raises(ValueError):
        data.gen_segment(16, 1, 0, at_most=[([], 1)])


def test_the_four_q1_groups_and_q6s_selectivity(seg):
    rows = oracle.answer([seg], SHAPES["q1.d90"])
    assert [r[:2] for r in rows] == [("A", "F"), ("N", "F"), ("N", "O"),
                                     ("R", "F")]
    counts = [r[-1] for r in rows]
    assert 0.97 < sum(counts) / len(seg["l_shipdate"]) < 0.995
    assert counts[1] < 0.02 * sum(counts)          # (N, F) is the small one
    for key in ("q6.y1994", "q6.y1993", "q6.y1997"):
        share = oracle._mask(seg, SHAPES[key]["preds"]).mean()
        assert 0.015 < share < 0.023, (key, share)


def test_to_sql_of_the_six_keys():
    assert list(SHAPES) == KEYS
    mix = cat.Catalog().traffic("q1q6_c1")
    assert mix["shapes"] == KEYS and mix["clients"] == 1
    q1 = statements.to_sql(SHAPES["q1.d90"])
    assert q1 == (
        "SELECT l_returnflag, l_linestatus, SUM(l_quantity), "
        "SUM(l_extendedprice), SUM(l_extendedprice * (1 - l_discount)), "
        "SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)), "
        "AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*) "
        "FROM lineitem WHERE l_shipdate <= 10471 GROUP BY l_returnflag, "
        "l_linestatus ORDER BY l_returnflag, l_linestatus LIMIT 100")
    assert statements.to_sql(SHAPES["q6.y1997"]) == (
        "SELECT SUM(l_extendedprice * l_discount) FROM lineitem WHERE "
        "l_shipdate >= 9862 AND l_shipdate < 10227 AND l_discount BETWEEN "
        "0.08 AND 0.10 AND l_quantity < 24")
    day = lambda s: int((np.datetime64(s) - np.datetime64("1970-01-01"))
                        .astype(int))
    for key, delta in (("q1.d90", 90), ("q1.d60", 60), ("q1.d120", 120)):
        assert SHAPES[key]["preds"] == [
            ["l_shipdate", "le", day("1998-12-01") - delta]]
        assert SHAPES[key]["flight"] == "q1" and "#" not in key
    for key, year, disc, qty in (("q6.y1994", 1994, 6, 24),
                                 ("q6.y1993", 1993, 2, 25),
                                 ("q6.y1997", 1997, 9, 24)):
        assert SHAPES[key]["flight"] == "q6"
        assert SHAPES[key]["preds"] == [
            ["l_shipdate", "ge", day(f"{year}-01-01")],
            ["l_shipdate", "lt", day(f"{year + 1}-01-01")],
            ["l_discount", "between", [(disc - 1) / 100, (disc + 1) / 100]],
            ["l_quantity", "lt", qty]]
    assert tbytes.columns_read(SHAPES["q6.y1994"]) == [
        "l_discount", "l_extendedprice", "l_quantity", "l_shipdate"]
    assert len(tbytes.columns_read(SHAPES["q1.d90"])) == 7
    assert tbytes.logical_bytes(SHAPES["q6.y1994"], 10, {
        "l_discount": 8, "l_extendedprice": 8, "l_quantity": 8,
        "l_shipdate": 4}.__getitem__) == 280


def brute_force(segs, shape):
    """The statement row by row in Python integers and Fractions."""
    groups = {}
    for s in segs:
        flag, status = s["l_returnflag"], s["l_linestatus"]
        for i in range(len(s["l_shipdate"])):
            row = {c: Fraction(repr(float(s[c][i]))) for c in data.MEASURES}
            row["l_shipdate"] = int(s["l_shipdate"][i])
            ok = True
            for col, op, val in shape["preds"]:
                x = row[col]
                lim = [Fraction(repr(v)) for v in val] \
                    if op == "between" else Fraction(repr(val))
                ok &= {"le": lambda: x <= lim, "lt": lambda: x < lim,
                       "ge": lambda: x >= lim,
                       "between": lambda: lim[0] <= x <= lim[1]}[op]()
            if not ok:
                continue
            key = tuple({"l_returnflag": flag, "l_linestatus": status}[c]
                        .values[{"l_returnflag": flag, "l_linestatus":
                                 status}[c].codes[i]]
                        for c in shape["group"])
            price, disc, tax = (row["l_extendedprice"], row["l_discount"],
                                row["l_tax"])
            vals = {"l_quantity": row["l_quantity"],
                    "l_extendedprice": price, "l_discount": disc,
                    "disc_price": price * (1 - disc),
                    "charge": price * (1 - disc) * (1 + tax),
                    "revenue": price * disc}
            acc = groups.setdefault(key, {"n": 0})
            acc["n"] += 1
            for k, v in vals.items():
                acc[k] = acc.get(k, 0) + v
    rows = []
    for key in sorted(groups):
        acc = groups[key]
        rows.append(key + tuple(
            acc["n"] if fn == "COUNT" else float(acc[what]) if fn == "SUM"
            else float(acc[what] / acc["n"])
            for fn, what in shape["aggs"]))
    return rows


@pytest.mark.parametrize("key", KEYS)
def test_the_reference_equals_a_brute_force_loop(key):
    segs = [data.gen_segment(5_000, 11, k) for k in range(2)]
    assert oracle.answer(segs, SHAPES[key]) == brute_force(segs, SHAPES[key])


@pytest.mark.parametrize("key", ["q1.d90", "q6.y1994"])
def test_same_refuses_what_the_guarantee_refuses(seg, key):
    shape = SHAPES[key]
    exact = oracle.answer([seg], shape)
    assert oracle.same([list(r) for r in exact], exact, shape)
    # the control: every addend held in float32, then summed exactly
    assert not oracle.same(oracle.answer([seg], shape, round_to=np.float32),
                           exact, shape)
    # the last SUM of the first row off by two parts in 10^12, and by two
    # in 10^13, which the tolerance allows
    at = max(i for i, (fn, _w) in enumerate(shape["aggs"]) if fn == "SUM") \
        + len(shape["group"])

    def off_by(factor):
        rows = [list(r) for r in exact]
        rows[0][at] *= factor
        return rows
    assert not oracle.same(off_by(1 + 2e-12), exact, shape)
    assert oracle.same(off_by(1 + 2e-13), exact, shape)
    if shape["group"]:
        assert not oracle.same(list(reversed(exact)), exact, shape)
        assert not oracle.same(exact[:-1], exact, shape)
        more = [list(r) for r in exact]
        more[0][-1] += 1                            # COUNT(*) is exact
        assert not oracle.same(more, exact, shape)
    assert not oracle.same(None, exact, shape)
    assert oracle.TOLERANCE == 1e-12


def test_the_configurations_file():
    c = cat.Catalog()
    conf = c.config("tpch_lineitem_sf100_1chip")
    for k in ("dataset", "entry", "chips", "rows", "segments", "guarantees",
              "assumed", "deployment"):                # test_catalog's keys
        assert k in conf
    assert (conf["rows"], conf["segments"], conf["chips"],
            conf["replication"]) == (1 << 26, 8, 1, 1)
    assert conf["dataset"] == "tpch" and conf["entry"] == "served_http_tpch"
    assert sorted(conf["reduced"]) == ["columns", "rows"]
    assert len(conf["source"]) <= 200 and "2.1.3.5" in conf["source"]
    assert any("1e-12" in g for g in conf["guarantees"])
    assert set(conf["schema"]) == set(tbytes.columns_read(SHAPES["q1.d90"]))
    cell = c.cell("tpch1.q1q6_c1")
    assert cell["chips"] == 1 and cell["traffic"] == "q1q6_c1"
    assert {m["name"] for m in c.metrics_for("tpch1.q1q6_c1", True)} >= {
        "float_acc_wide_share", "flight_p50_ms.q6", "flight_p50_ms.q1",
        "scan_roofline", "kernel_ms_per_query", "compiles_in_window"}
    with open(os.path.join(cat.HERE, "metrics",
                           "float_acc_wide_share.json")) as f:
        spec = json.load(f)
    assert spec["args"]["counters"] == ["float_acc_wide"]
    assert spec["args"]["over"] == ["float_acc_wide", "float_acc_narrow"]


# -- the cell at a tiny size on the CPU: the control and the planted faults
# (test_cells_cpu.py plants its faults through SSB's reference; these are
# the same faults through this data set's)

TINY = {"rows": 1 << 16, "segments": 4}


def tiny_run(seed, wrap):
    from benchmark import run
    return run.run_cell("tpch1.q1q6_c1", seed, 0.3, False,
                        catalog=cat.Catalog(), check_chip=False,
                        config_override=TINY, wrap_system=wrap)


def in_place(segments=None, **kw):
    from benchmark.tests.control_tpch_full_size import ReferenceInPlace
    return lambda system, own: ReferenceInPlace(
        system, own if segments is None else segments, **kw)


def tiny_segments(seed, keep=None):
    n = TINY["segments"]
    return [data.gen_segment(TINY["rows"] // n, seed, k)
            for k in range(n)][:keep]


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_reference_in_place_is_correct_and_its_control_is_not(seed):
    assert tiny_run(seed, in_place())["correct"]
    control = tiny_run(seed, in_place(round_to=np.float32))
    assert not control["correct"]
    assert control["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault", [
    lambda seed: in_place(tiny_segments(seed, keep=2)),   # half left out
    lambda seed: in_place(tiny_segments(seed, keep=1)),   # one shard alone
    lambda seed: in_place(tiny_segments(seed + 1)),       # a stale table
], ids=["half_the_segments_left_out", "one_shard_answers_alone",
        "stale_table"])
def test_a_planted_fault_comes_out_as_not_correct(fault):
    assert not tiny_run(17, fault(17))["correct"]


def test_the_full_size_control_script_at_a_tiny_size():
    from benchmark.tests import control_tpch_full_size as control
    assert control.main([5], config_override=TINY, check_chip=False) == 0
