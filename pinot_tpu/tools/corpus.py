"""Seeded test corpus: flat SSB and NYC-taxi-shaped tables, their
statements and a numpy oracle for each.

Shared by the tests (tests/test_ssb.py, tests/test_taxi.py and the
suites that borrow their segments), tools/chaos_smoke.py,
tools/check_static.py and chip_smoke.py. It is kept apart from
``benchmark/ssb/`` on purpose: the benchmark's reference imports nothing
of the program, and the tests' oracle does not depend on the benchmark's
files. Seeds, column order and dtypes are part of the contract: digests
recorded against this data must not move.

SSB: the 13 queries (reference:
pinot-integration-tests/src/test/resources/ssb/ssb_query_set.yaml:22+)
with dimension-table predicates denormalized onto a flat lineorder table
— the dimension attributes each query touches (d_year, p_brand1,
s_region, c_city, ...) are dictionary-encoded columns, hierarchically
consistent with the SSB spec (brand -> category -> mfgr; city -> nation
-> region).

Taxi: two group keys — PULocationID (~265 zones: low cardinality, many
rows a group) and a ~100k-cardinality key that must take the compact
sort path.
"""
from __future__ import annotations

import math

import numpy as np

from ..segment import ImmutableSegment, SegmentBuilder
from ..segment.builder import Categorical
from ..spi import (DataType, FieldSpec, FieldType, Schema,
                   TableConfig)

OPTION = " OPTION(timeoutMs=600000)"

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    # 5 per region, region r owns nations r*5..r*5+4 (SSB nation list)
    "ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE",
    "ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES",
    "INDIA", "INDONESIA", "JAPAN", "CHINA", "VIETNAM",
    "FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM",
    "EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA",
]
# SSB cities: nation name truncated to 9 chars + digit 0-9
CITIES = [n[:9] + str(d) for n in NATIONS for d in range(10)]
MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun",
          "Jul", "Aug", "Sep", "Oct", "Nov", "Dec"]
YEARS = list(range(1992, 1999))
YEARMONTHS = [f"{m}{y}" for y in YEARS for m in MONTHS]
# brands: MFGR#<m><c><b>, m 1-5, c 1-5, b 1-40; category MFGR#<m><c>
BRANDS = [f"MFGR#{m}{c}{b}" for m in range(1, 6) for c in range(1, 6)
          for b in range(1, 41)]
CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]


def ssb_columns(n: int, seed=1992):
    """Generate the flat denormalized lineorder columns from ``seed``
    (an int, or a sequence of ints such as (seed, segment index))."""
    rng = np.random.default_rng(seed)
    year = rng.integers(0, 7, n).astype(np.int16)          # 1992..1998
    month = rng.integers(0, 12, n).astype(np.int8)
    brand = rng.integers(0, 1000, n).astype(np.int16)
    s_nation = rng.integers(0, 25, n).astype(np.int8)
    c_nation = rng.integers(0, 25, n).astype(np.int8)
    s_city = (s_nation.astype(np.int16) * 10
              + rng.integers(0, 10, n).astype(np.int16))
    c_city = (c_nation.astype(np.int16) * 10
              + rng.integers(0, 10, n).astype(np.int16))
    return {
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_extendedprice": rng.integers(900, 55451, n).astype(np.int32),
        "lo_revenue": rng.integers(10000, 6000000, n).astype(np.int32),
        "lo_supplycost": rng.integers(10000, 120000, n).astype(np.int32),
        "d_year": (year.astype(np.int32) + 1992),
        "d_yearmonthnum": ((year.astype(np.int32) + 1992) * 100
                           + month + 1),
        "d_weeknuminyear": rng.integers(1, 54, n).astype(np.int32),
        "d_yearmonth": Categorical(year.astype(np.int16) * 12 + month,
                                   YEARMONTHS),
        "p_brand1": Categorical(brand, BRANDS),
        "p_category": Categorical((brand // 40).astype(np.int8), CATEGORIES),
        "p_mfgr": Categorical((brand // 200).astype(np.int8), MFGRS),
        "s_region": Categorical((s_nation // 5).astype(np.int8), REGIONS),
        "s_nation": Categorical(s_nation, NATIONS),
        "s_city": Categorical(s_city, CITIES),
        "c_region": Categorical((c_nation // 5).astype(np.int8), REGIONS),
        "c_nation": Categorical(c_nation, NATIONS),
        "c_city": Categorical(c_city, CITIES),
    }


def ssb_fields(cols):
    """FieldSpecs for ``ssb_columns`` output, in column order."""
    fields = []
    for name in cols:
        if name.startswith("lo_") and name not in ("lo_quantity",
                                                   "lo_discount"):
            fields.append(FieldSpec(name, DataType.INT, FieldType.METRIC))
        elif isinstance(cols[name], np.ndarray):
            fields.append(FieldSpec(name, DataType.INT, FieldType.DIMENSION))
        else:
            fields.append(FieldSpec(name, DataType.STRING,
                                    FieldType.DIMENSION))
    return fields


def build_ssb_segment(n: int, out_dir: str):
    """Build the flat SSB segment at n rows under out_dir; returns it."""
    cols = ssb_columns(n)
    schema = Schema("lineorder", ssb_fields(cols))
    builder = SegmentBuilder(schema, TableConfig("lineorder"))
    seg_dir = builder.build(cols, out_dir, "seg_0")
    return ImmutableSegment.load(seg_dir)


# ---------------------------------------------------------------------------
# SSB query specs: (qid, preds, value_expr, group_cols)
# preds: (col, op, value) with op in {eq, in, between, lt}
# value_expr: (col,) | (col, '*', col) | (col, '-', col)
# ---------------------------------------------------------------------------

SSB_QUERIES = [
    ("q1.1", [("d_year", "eq", 1993), ("lo_discount", "between", (1, 3)),
              ("lo_quantity", "lt", 25)],
     ("lo_extendedprice", "*", "lo_discount"), []),
    ("q1.2", [("d_yearmonthnum", "eq", 199401),
              ("lo_discount", "between", (4, 6)),
              ("lo_quantity", "between", (26, 35))],
     ("lo_extendedprice", "*", "lo_discount"), []),
    ("q1.3", [("d_weeknuminyear", "eq", 6), ("d_year", "eq", 1994),
              ("lo_discount", "between", (5, 7)),
              ("lo_quantity", "between", (26, 35))],
     ("lo_extendedprice", "*", "lo_discount"), []),
    ("q2.1", [("p_category", "eq", "MFGR#12"), ("s_region", "eq", "AMERICA")],
     ("lo_revenue",), ["d_year", "p_brand1"]),
    ("q2.2", [("p_brand1", "between", ("MFGR#2221", "MFGR#2228")),
              ("s_region", "eq", "ASIA")],
     ("lo_revenue",), ["d_year", "p_brand1"]),
    ("q2.3", [("p_brand1", "eq", "MFGR#2221"), ("s_region", "eq", "EUROPE")],
     ("lo_revenue",), ["d_year", "p_brand1"]),
    ("q3.1", [("c_region", "eq", "ASIA"), ("s_region", "eq", "ASIA"),
              ("d_year", "between", (1992, 1997))],
     ("lo_revenue",), ["c_nation", "s_nation", "d_year"]),
    ("q3.2", [("c_nation", "eq", "UNITED STATES"),
              ("s_nation", "eq", "UNITED STATES"),
              ("d_year", "between", (1992, 1997))],
     ("lo_revenue",), ["c_city", "s_city", "d_year"]),
    ("q3.3", [("c_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("s_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("d_year", "between", (1992, 1997))],
     ("lo_revenue",), ["c_city", "s_city", "d_year"]),
    ("q3.4", [("c_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("s_city", "in", ("UNITED KI1", "UNITED KI5")),
              ("d_yearmonth", "eq", "Jul1995")],
     ("lo_revenue",), ["c_city", "s_city", "d_year"]),
    ("q4.1", [("c_region", "eq", "AMERICA"), ("s_region", "eq", "AMERICA"),
              ("p_mfgr", "in", ("MFGR#1", "MFGR#2"))],
     ("lo_revenue", "-", "lo_supplycost"), ["d_year", "c_nation"]),
    ("q4.2", [("c_region", "eq", "AMERICA"), ("s_region", "eq", "AMERICA"),
              ("d_year", "in", (1997, 1998)),
              ("p_mfgr", "in", ("MFGR#1", "MFGR#2"))],
     ("lo_revenue", "-", "lo_supplycost"),
     ["d_year", "s_nation", "p_category"]),
    ("q4.3", [("c_region", "eq", "AMERICA"),
              ("s_nation", "eq", "UNITED STATES"),
              ("d_year", "in", (1997, 1998)),
              ("p_category", "eq", "MFGR#14")],
     ("lo_revenue", "-", "lo_supplycost"),
     ["d_year", "s_city", "p_brand1"]),
]


def _sql_lit(v) -> str:
    return f"'{v}'" if isinstance(v, str) else str(v)


def spec_to_sql(preds, value_expr, group_cols) -> str:
    """The SQL statement of one SSB spec."""
    agg = "SUM(" + " ".join(value_expr) + ")"
    sel = ", ".join(group_cols + [agg]) if group_cols else agg
    conds = []
    for col, op, val in preds:
        if op == "eq":
            conds.append(f"{col} = {_sql_lit(val)}")
        elif op == "lt":
            conds.append(f"{col} < {_sql_lit(val)}")
        elif op == "between":
            conds.append(f"{col} BETWEEN {_sql_lit(val[0])} "
                         f"AND {_sql_lit(val[1])}")
        elif op == "in":
            # the reference queries write 2-value sets as OR-of-equals;
            # keep that form so the planner's Or folding is exercised
            conds.append("(" + " OR ".join(
                f"{col} = {_sql_lit(v)}" for v in val) + ")")
    sql = f"SELECT {sel} FROM lineorder WHERE {' AND '.join(conds)}"
    if group_cols:
        sql += (" GROUP BY " + ", ".join(group_cols)
                + " ORDER BY " + ", ".join(group_cols) + " LIMIT 100000")
    return sql


# ---------------------------------------------------------------------------
# numpy oracle for the SSB specs (on dict ids, like Pinot)
# ---------------------------------------------------------------------------

def _pred_mask(seg, col, op, val):
    ids = np.asarray(seg.fwd(col))
    d = seg.dictionary(col)
    vals = None if d is None else np.asarray(d.values)
    if op == "eq":
        if d is None:
            return ids == val
        i = d.index_of(val)
        return (ids == i) if i >= 0 else np.zeros(len(ids), dtype=bool)
    if op == "in":
        if d is None:
            return np.isin(ids, list(val))
        tgt = [i for i in (d.index_of(v) for v in val) if i >= 0]
        return np.isin(ids, tgt)
    if op == "lt":
        if d is None:
            return ids < val
        return ids < int(np.searchsorted(vals, val, side="left"))
    assert op == "between"
    lo_v, hi_v = val
    if d is None:
        return (ids >= lo_v) & (ids <= hi_v)
    lo = int(np.searchsorted(vals, lo_v, side="left"))
    hi = int(np.searchsorted(vals, hi_v, side="right"))
    return (ids >= lo) & (ids < hi)


def _value(seg, value_expr, mask):
    def col_vals(c):
        ids = np.asarray(seg.fwd(c))[mask]
        d = seg.dictionary(c)
        if d is None:
            return ids.astype(np.int64)
        return np.asarray(d.values)[ids].astype(np.int64)

    if len(value_expr) == 1:
        return col_vals(value_expr[0])
    a, op, b = value_expr
    return col_vals(a) * col_vals(b) if op == "*" \
        else col_vals(a) - col_vals(b)


def ssb_oracle(seg, preds, value_expr, group_cols):
    """Evaluate one SSB spec over one segment with numpy; returns the
    result rows (group keys then the sum, empty groups left out)."""
    mask = None
    for p in preds:
        m = _pred_mask(seg, *p)
        mask = m if mask is None else (mask & m)
    vals = _value(seg, value_expr, mask)
    if not group_cols:
        return [(int(vals.sum()),)]
    dims = [(c, seg.columns[c].cardinality) for c in group_cols]
    key = np.zeros(int(mask.sum()), dtype=np.int64)
    for c, card in dims:
        key = key * card + np.asarray(seg.fwd(c))[mask].astype(np.int64)
    space = math.prod(card for _, card in dims)
    sums = np.bincount(key, weights=vals.astype(np.float64),
                       minlength=space)
    cnts = np.bincount(key, minlength=space)
    idxs = np.nonzero(cnts)[0]
    keycols = []
    rem = idxs.copy()
    for c, card in reversed(dims):
        keycols.append(seg.dictionary(c).values_for(rem % card))
        rem = rem // card
    keycols.reverse()
    return [tuple(_py(kc[i]) for kc in keycols) + (int(sums[idxs[i]]),)
            for i in range(len(idxs))]


def _py(v):
    return v.item() if isinstance(v, np.generic) else v


def digest(rows):
    """Comparable form of a result: rows sorted, strings kept, every
    other value (numpy or Python, int or integral float) as an int."""
    return sorted(tuple(str(x) if isinstance(x, str) else int(x)
                        for x in r) for r in rows)


# ---------------------------------------------------------------------------
# NYC-taxi-shaped table
# ---------------------------------------------------------------------------

N_ZONES = 265
HC_CARD = 100_000


def taxi_columns(n: int):
    rng = np.random.default_rng(2016)
    return {
        "pu_loc": rng.integers(0, N_ZONES, n).astype(np.int32),
        "hc_key": rng.integers(0, HC_CARD, n).astype(np.int32),
        "fare": rng.integers(250, 20_000, n).astype(np.int32),  # cents
        "distance": rng.integers(1, 3_000, n).astype(np.int32),
        "passengers": rng.integers(1, 7, n).astype(np.int32),
    }


def build_taxi_segment(n: int, out_dir: str):
    """Build the trips segment at n rows under out_dir; returns it."""
    schema = Schema("trips", [
        FieldSpec("pu_loc", DataType.INT, FieldType.DIMENSION),
        FieldSpec("hc_key", DataType.INT, FieldType.DIMENSION),
        FieldSpec("fare", DataType.INT, FieldType.METRIC),
        FieldSpec("distance", DataType.INT, FieldType.METRIC),
        FieldSpec("passengers", DataType.INT, FieldType.DIMENSION),
    ])
    cfg = TableConfig("trips")
    cfg.indexing.dictionary_columns.append("hc_key")  # keep dict past 2^17
    builder = SegmentBuilder(schema, cfg)
    d = builder.build(taxi_columns(n), out_dir, "seg_0")
    return ImmutableSegment.load(d)


# (qid, group key, WHERE clause or None)
TAXI_QUERIES = [
    ("zones_265", "pu_loc", None),
    ("zones_filtered", "pu_loc", "passengers >= 2"),
    ("hc_100k", "hc_key", None),
    ("hc_100k_filtered", "hc_key", "distance < 1500"),
]


def taxi_sql(key, where):
    """The SQL statement of one taxi spec."""
    w = f" WHERE {where}" if where else ""
    return (f"SELECT {key}, COUNT(*), AVG(fare) FROM trips{w} "
            f"GROUP BY {key} LIMIT 200000")


def taxi_oracle(seg, key, where):
    """Evaluate one taxi spec with numpy (dict-id space); returns
    {key value: (count, mean fare)}."""
    ids = np.asarray(seg.fwd(key)).astype(np.int64)
    card = seg.columns[key].cardinality
    fare = np.asarray(seg.dictionary("fare").values_for(
        np.asarray(seg.fwd("fare")))) if seg.columns["fare"].has_dict \
        else np.asarray(seg.fwd("fare"))
    if where is None:
        sel_ids, sel_fare = ids, fare.astype(np.float64)
    elif where.startswith("passengers"):
        p = np.asarray(seg.raw_values("passengers"))
        m = p >= 2
        sel_ids, sel_fare = ids[m], fare[m].astype(np.float64)
    else:
        dist = np.asarray(seg.raw_values("distance"))
        m = dist < 1500
        sel_ids, sel_fare = ids[m], fare[m].astype(np.float64)
    cnt = np.bincount(sel_ids, minlength=card)
    s = np.bincount(sel_ids, weights=sel_fare, minlength=card)
    live = np.nonzero(cnt)[0]
    keys = seg.dictionary(key).values_for(live)
    return {int(keys[i]): (int(cnt[live[i]]), s[live[i]] / cnt[live[i]])
            for i in range(len(live))}
