"""The flat, denormalised SSB table, made from a seed by dbgen's rules.

The source's ``lineorder_flat`` joins ``lineorder`` with ``customer``,
``supplier`` and ``part``. So do these rows: the three dimension tables
are drawn once per seed at the source scale factor's sizes, every fact
row draws its keys, and each dimension attribute is looked up by key. An
order has 1 to 7 lines that share its customer and date; a line's
extended price is its quantity times its part's retail price (dbgen's
``rpb_routine``, in cents), its revenue is that price less its discount,
its supply cost is six tenths of the retail price. The three date
attributes are the SSB date table's ``D_YEAR``, ``D_YEARMONTHNUM`` and
``D_WEEKNUMINYEAR`` of the line's order date, materialised at ingestion
(the source computes them in the statement, ``toYear(LO_ORDERDATE)``).

Only the columns a statement of ``shapes.json`` reads are made; the
configuration's file says which of the source's columns are left out and
why. Nothing of the program is in here: a column is a plain ``numpy``
array, or a ``Coded`` pair of integer codes and the values they index.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Union

import numpy as np

SCALE = 100                      # the scale factor the dimensions follow
CUSTOMERS = 30_000 * SCALE
SUPPLIERS = 2_000 * SCALE
PARTS = 200_000 * (1 + int(math.log2(SCALE)))
FIRST_DAY, LAST_DAY = "1992-01-01", "1998-08-02"     # dbgen's order dates

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    # 5 per region, region r owns nations r*5..r*5+4 (SSB nation list)
    "ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE",
    "ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES",
    "INDIA", "INDONESIA", "JAPAN", "CHINA", "VIETNAM",
    "FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM",
    "EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA",
]
# SSB cities: nation name padded or cut to 9 characters + a digit 0-9
CITIES = [n[:9].ljust(9) + str(d) for n in NATIONS for d in range(10)]
# brands: MFGR#<m><c><b>, m 1-5, c 1-5, b 1-40; category MFGR#<m><c>
BRANDS = [f"MFGR#{m}{c}{b}" for m in range(1, 6) for c in range(1, 6)
          for b in range(1, 41)]
CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]


class Coded(NamedTuple):
    """A string column as integer codes into ``values``."""
    codes: np.ndarray
    values: List[str]


Column = Union[np.ndarray, Coded]

# integer columns that are measures (the rest are dimensions)
MEASURES = ("lo_extendedprice", "lo_revenue", "lo_supplycost")


class Dimensions(NamedTuple):
    c_city: np.ndarray           # by customer key - 1: code into CITIES
    s_city: np.ndarray           # by supplier key - 1
    p_brand: np.ndarray          # by part key - 1: code into BRANDS
    p_retail: np.ndarray         # by part key - 1: retail price, cents
    d_year: np.ndarray           # by day since FIRST_DAY
    d_yearmonthnum: np.ndarray
    d_weeknuminyear: np.ndarray


@functools.lru_cache(maxsize=2)
def dimensions(seed: int) -> Dimensions:
    """The customer, supplier, part and date tables of ``seed`` (a tenth
    of a second to draw; kept so that a table's segments share them)."""
    rng = np.random.default_rng((seed, 1_000_003))

    def city(n):     # a random nation, a random digit: dbgen's customer.c
        return (rng.integers(0, 25, n).astype(np.int16) * 10
                + rng.integers(0, 10, n).astype(np.int16))

    c_city, s_city = city(CUSTOMERS), city(SUPPLIERS)
    p_brand = rng.integers(0, 1000, PARTS).astype(np.int16)
    key = np.arange(1, PARTS + 1, dtype=np.int64)
    retail = 90_000 + (key // 10) % 20_001 + 100 * (key % 1_000)
    days = np.arange(np.datetime64(FIRST_DAY), np.datetime64(LAST_DAY) + 1)
    year = days.astype("datetime64[Y]").astype(np.int32) + 1970
    month = days.astype("datetime64[M]").astype(np.int32) % 12 + 1
    day_of_year = (days - days.astype("datetime64[Y]")).astype(np.int32)
    return Dimensions(c_city, s_city, p_brand, retail.astype(np.int32),
                      year, year * 100 + month, day_of_year // 7 + 1)


def gen_segment(n: int, seed: int, segment: int) -> Dict[str, Column]:
    """``n`` rows for segment ``segment`` of the table ``seed`` names, in
    dbgen's order: by order, an order's lines together."""
    dim = dimensions(seed)
    rng = np.random.default_rng((seed, segment))
    lines = rng.integers(1, 8, n // 3 + 8)       # 1-7 lines an order
    while int(lines.sum()) < n:
        lines = np.concatenate([lines, rng.integers(1, 8, n // 3 + 8)])
    order = np.repeat(np.arange(len(lines)), lines)[:n]
    cust = rng.integers(0, CUSTOMERS, len(lines))[order]
    day = rng.integers(0, len(dim.d_year), len(lines))[order]
    part = rng.integers(0, PARTS, n)
    supp = rng.integers(0, SUPPLIERS, n)
    quantity = rng.integers(1, 51, n).astype(np.int32)
    discount = rng.integers(0, 11, n).astype(np.int32)
    retail = dim.p_retail[part]
    price = quantity * retail                    # at most 10,494,950
    revenue = (price.astype(np.int64) * (100 - discount) // 100)
    brand, s_city, c_city = dim.p_brand[part], dim.s_city[supp], \
        dim.c_city[cust]
    return {
        "lo_quantity": quantity,
        "lo_discount": discount,
        "lo_extendedprice": price,
        "lo_revenue": revenue.astype(np.int32),
        "lo_supplycost": 6 * retail // 10,
        "d_year": dim.d_year[day],
        "d_yearmonthnum": dim.d_yearmonthnum[day],
        "d_weeknuminyear": dim.d_weeknuminyear[day],
        "p_brand": Coded(brand, BRANDS),
        "p_category": Coded((brand // 40).astype(np.int8), CATEGORIES),
        "p_mfgr": Coded((brand // 200).astype(np.int8), MFGRS),
        "s_region": Coded((s_city // 50).astype(np.int8), REGIONS),
        "s_nation": Coded((s_city // 10).astype(np.int8), NATIONS),
        "s_city": Coded(s_city, CITIES),
        "c_region": Coded((c_city // 50).astype(np.int8), REGIONS),
        "c_nation": Coded((c_city // 10).astype(np.int8), NATIONS),
        "c_city": Coded(c_city, CITIES),
    }
