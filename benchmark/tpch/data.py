"""TPC-H's LINEITEM, made from a seed by dbgen's rules.

LINEITEM is the table SSB's ``lineorder`` was cut from, so the rules are
the ones ``benchmark/ssb/data.py`` follows: an order has 1 to 7 lines
that share its order date; a line's extended price is its quantity times
its part's retail price (dbgen's ``rpb_routine``, in cents). What this
table adds is what the two statements read: the ship date (order date +
1 to 121 days), the receipt date behind it (ship date + 1 to 30 days),
the two flags dbgen derives from them against its current date
1995-06-17, the tax, and above all the types: quantity, extended price,
discount and tax are the specification's decimals, which Pinot's TPC-H
schemas hold as ``DOUBLE``.

Every measure is dealt as an integer (units, cents, hundredths) and
handed over as the correctly rounded double of its decimal value (an
int64 over 100.0 is one IEEE division of two exact operands). So the
reference recovers the integer from the double exactly and sums in
integers; nothing here is a float before that last division.

Only the seven columns the statements of ``shapes.json`` read are made
(the configuration's file says which are left out and why). Nothing of
the program is in here: a column is a plain ``numpy`` array, or a
``Coded`` pair of integer codes and the values they index.

``ASSUMED`` lists what is written from memory of the specification and of
dbgen (no network here), to be checked by whoever has both.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.ssb.data import DAYS, FIRST_DAY, Coded, Column

SCALE = 100                       # the scale factor the key ranges follow
PARTS = 200_000 * SCALE           # l_partkey uniform over P_PARTKEY
EPOCH_FIRST_DAY = 8035            # FIRST_DAY (1992-01-01) in days since 1970
CURRENT_DAY = 9298                # dbgen's CURRENTDATE, 1995-06-17
RETURN_FLAGS = ["A", "N", "R"]    # sorted, as a dictionary holds them
LINE_STATUSES = ["F", "O"]

# the DOUBLE measures (the rest are dimensions)
MEASURES = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")

ASSUMED = {
    "lines": "1-7 lines an order (O_ORDERKEY's lines), uniform",
    "l_partkey": "uniform over 1..200,000 x SF = 20,000,000",
    "l_quantity": "uniform 1..50",
    "p_retailprice": "rpb_routine: (90000 + (partkey / 10) % 20001 + "
                     "100 x (partkey % 1000)) cents",
    "l_extendedprice": "l_quantity x p_retailprice",
    "l_discount": "uniform 0.00..0.10", "l_tax": "uniform 0.00..0.08",
    "o_orderdate": "uniform over 1992-01-01..1998-08-02 (STARTDATE to "
                   "ENDDATE - 151 days)",
    "l_shipdate": "o_orderdate + uniform 1..121 days",
    "l_receiptdate": "l_shipdate + uniform 1..30 days",
    "l_returnflag": "'R' or 'A' by a fair coin where l_receiptdate <= "
                    "1995-06-17, else 'N'",
    "l_linestatus": "'O' where l_shipdate > 1995-06-17, else 'F'",
    "streams": "numpy's generator seeded by (seed, segment), not dbgen's "
               "own random streams",
}
assert FIRST_DAY == "1992-01-01"


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """dbgen's ``rpb_routine``: a part's retail price, in cents."""
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)


def gen_segment(n: int, seed: int, segment: int,
                at_most=()) -> Dict[str, Column]:
    """``n`` rows for segment ``segment`` of the table ``seed`` names, in
    dbgen's order (by order, an order's lines together). ``at_most`` is
    the harness's ``segment_rows_at_most``; this configuration has none."""
    if at_most:
        raise ValueError("tpch: no segment_rows_at_most is defined")
    rng = np.random.default_rng((seed, segment, 35))
    lines = rng.integers(1, 8, n // 3 + 8)       # 1-7 lines an order
    while int(lines.sum()) < n:
        lines = np.concatenate([lines, rng.integers(1, 8, n // 3 + 8)])
    order = np.repeat(np.arange(len(lines)), lines)[:n]
    order_day = rng.integers(0, DAYS, len(lines))[order] + EPOCH_FIRST_DAY
    ship = (order_day + rng.integers(1, 122, n)).astype(np.int32)
    receipt = ship + rng.integers(1, 31, n)
    coin = rng.integers(0, 2, n)                 # 0 -> 'A', 1 -> 'R'
    flag = np.where(receipt <= CURRENT_DAY, 2 * coin, 1).astype(np.int8)
    status = (ship > CURRENT_DAY).astype(np.int8)
    quantity = rng.integers(1, 51, n)
    price = quantity * retail_cents(rng.integers(1, PARTS + 1, n))
    return {
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": price / 100.0,        # at most 104,949.50
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_shipdate": ship,
        "l_returnflag": Coded(flag, RETURN_FLAGS),
        "l_linestatus": Coded(status, LINE_STATUSES),
    }
