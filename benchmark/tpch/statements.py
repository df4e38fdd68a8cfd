"""Statements: TPC-H's Q1 and Q6 as data (``shapes.json``: predicates,
aggregates over named expressions, group-by and ORDER BY columns) written
as this system's SQL. The date arithmetic of the specification (``date
'1998-12-01' - interval DELTA day``, ``DATE + interval '1' year``,
``DISCOUNT +- 0.01``) is done by whoever writes a shape: a shape holds
days since 1970-01-01 and two-decimal literals."""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
Shape = Dict[str, Any]

# a named expression: its SQL, and the columns it reads
EXPRESSIONS = {
    "l_quantity": ("l_quantity", ["l_quantity"]),
    "l_extendedprice": ("l_extendedprice", ["l_extendedprice"]),
    "l_discount": ("l_discount", ["l_discount"]),
    "disc_price": ("l_extendedprice * (1 - l_discount)",
                   ["l_extendedprice", "l_discount"]),
    "charge": ("l_extendedprice * (1 - l_discount) * (1 + l_tax)",
               ["l_extendedprice", "l_discount", "l_tax"]),
    "revenue": ("l_extendedprice * l_discount",
                ["l_extendedprice", "l_discount"]),
}


def load_shapes(path: str = os.path.join(HERE, "shapes.json")
                ) -> Dict[str, Shape]:
    with open(path) as f:
        doc = json.load(f)
    return {s["id"]: s for s in doc["shapes"]}


def _lit(v) -> str:
    # a decimal literal keeps its two places: 0.05, not 0.05000000000000001
    return f"{v:.2f}" if isinstance(v, float) else str(v)


def agg_sql(agg: List[str]) -> str:
    fn, what = agg
    return "COUNT(*)" if fn == "COUNT" else f"{fn}({EXPRESSIONS[what][0]})"


def to_sql(shape: Shape, table: str = "lineitem") -> str:
    """The SQL text of one shape: the group-by columns, then the
    aggregates in the specification's order."""
    group = list(shape["group"])
    sel = ", ".join(group + [agg_sql(a) for a in shape["aggs"]])
    conds = []
    for col, op, val in shape["preds"]:
        if op == "between":
            conds.append(f"{col} BETWEEN {_lit(val[0])} AND {_lit(val[1])}")
        else:
            sym = {"le": "<=", "lt": "<", "ge": ">="}[op]
            conds.append(f"{col} {sym} {_lit(val)}")
    sql = f"SELECT {sel} FROM {table} WHERE {' AND '.join(conds)}"
    if group:
        sql += (" GROUP BY " + ", ".join(group) + " ORDER BY "
                + ", ".join(shape["order"]) + " LIMIT 100")
    return sql
